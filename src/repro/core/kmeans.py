"""KMeans in pure JAX: k-means++ seeding + Lloyd iterations via lax.scan.

The paper's KMeans-DRE learns centroid positions from a client's private
data (Algorithm 1 line 3). Time O(k·n·c·d), space O(c·d + n) — Table IV.

The assignment step is the compute hot-spot; ``repro.kernels.kmeans_dist``
provides the Pallas TPU kernel for it (matmul-form distances, fused argmin
+ per-centroid accumulation). ``kmeans_fit``/``kmeans_fit_batched`` route
through the kernel when the resolved ``kernel_backend`` is ``"pallas"``
(``repro.kernels.dispatch``); the default jnp path below is kept inline
and op-for-op unchanged — the default-backend bit-for-bit guarantee rides
on it.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
# canonical impl moved to the dispatch layer; re-exported for importers
from repro.kernels.dispatch import pairwise_sq_dists as pairwise_sq_dists
from repro.models.sharding import shard_local


class KMeansResult(NamedTuple):
    centroids: jax.Array     # (c, d)
    assignments: jax.Array   # (n,) int32
    inertia: jax.Array       # scalar — sum of squared distances
    n_iter: jax.Array        # iterations executed


def kmeans_plus_plus(key, x, k: int):
    """k-means++ seeding (faithful to sklearn's default, which the paper uses)."""
    n = x.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    centroids = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])

    def body(carry, i):
        centroids, key, min_d2 = carry
        d2 = jnp.sum(jnp.square(x - centroids[i - 1]), axis=-1)
        min_d2 = jnp.minimum(min_d2, d2)
        key, sub = jax.random.split(key)
        probs = min_d2 / jnp.maximum(jnp.sum(min_d2), 1e-12)
        nxt = jax.random.choice(sub, n, p=probs)
        centroids = centroids.at[i].set(x[nxt])
        return (centroids, key, min_d2), None

    if k > 1:
        init_d2 = jnp.full((n,), jnp.inf, x.dtype)
        (centroids, _, _), _ = jax.lax.scan(
            body, (centroids, key, init_d2), jnp.arange(1, k))
    return centroids


@partial(jax.jit, static_argnames=("k", "max_iter"))
def _kmeans_fit_jnp(key, x, k: int, max_iter: int, tol):
    """Reference Lloyd's algorithm — the historical ``kmeans_fit`` body,
    unchanged (two matmuls per step: distances, then the (n, k) one-hot
    scatter ``one_hot.T @ x``)."""
    x = x.astype(jnp.float32)
    n, d = x.shape
    init = kmeans_plus_plus(key, x, k)

    def step(carry, _):
        cents, done, iters = carry
        d2 = pairwise_sq_dists(x, cents)
        assign = jnp.argmin(d2, axis=-1)
        one_hot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
        counts = jnp.sum(one_hot, axis=0)                       # (k,)
        sums = one_hot.T @ x                                    # (k, d)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0),
                        cents)
        shift = jnp.sum(jnp.square(new - cents))
        new_done = done | (shift < tol)
        cents = jnp.where(done, cents, new)
        iters = iters + jnp.where(done, 0, 1)
        return (cents, new_done, iters), None

    (cents, _, iters), _ = jax.lax.scan(
        step, (init, jnp.bool_(False), jnp.int32(0)), None, length=max_iter)
    d2 = pairwise_sq_dists(x, cents)
    assign = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    inertia = jnp.sum(jnp.min(d2, axis=-1))
    return KMeansResult(cents, assign, inertia, iters)


@partial(jax.jit, static_argnames=("k", "max_iter"))
def _kmeans_fit_batched_jnp(keys, xs, k: int, max_iter: int, tol):
    return jax.vmap(
        lambda kk, xx: _kmeans_fit_jnp(kk, xx, k, max_iter, tol))(keys, xs)


@partial(jax.jit, static_argnames=("k", "max_iter"))
def _kmeans_fit_pallas(keys, xs, k: int, max_iter: int, tol):
    """Fused-Lloyd fit over a stacked client axis: keys (C, …), xs (C, n, d).

    Each scan step is one ``lloyd_step`` kernel call — the client axis is a
    grid dimension, so the cohort engines' vmapped DRE fit compiles once
    for any C instead of retracing per client, and the (n, k) one-hot /
    second matmul of the reference body never materialise.
    """
    from repro.kernels.kmeans_dist import ops as kd_ops

    xs = xs.astype(jnp.float32)
    c = xs.shape[0]
    init = jax.vmap(lambda kk, xx: kmeans_plus_plus(kk, xx, k))(keys, xs)
    # on a client mesh each device steps the kernel over its own clients
    lloyd_step = shard_local(
        kd_ops.lloyd_step, [("clients", None, None)] * 2,
        [("clients", None), ("clients", None), ("clients", None, None),
         ("clients", None)])

    def step(carry, _):
        cents, done, iters = carry
        _, _, sums, counts = lloyd_step(xs, cents)
        new = jnp.where(counts[..., None] > 0,
                        sums / jnp.maximum(counts[..., None], 1.0), cents)
        shift = jnp.sum(jnp.square(new - cents), axis=(-2, -1))
        new_done = done | (shift < tol)
        cents = jnp.where(done[..., None, None], cents, new)
        iters = iters + jnp.where(done, 0, 1)
        return (cents, new_done, iters), None

    (cents, _, iters), _ = jax.lax.scan(
        step, (init, jnp.zeros((c,), bool), jnp.zeros((c,), jnp.int32)),
        None, length=max_iter)
    assign, min_d2, _, _ = lloyd_step(xs, cents)
    inertia = jnp.sum(min_d2, axis=-1)
    return KMeansResult(cents, assign, inertia, iters)


def kmeans_fit(key, x, k: int, max_iter: int = 50, tol: float = 1e-6, *,
               backend: Optional[str] = None):
    """Lloyd's algorithm. x: (n, d) -> KMeansResult. Runs a fixed-shape scan
    with a convergence flag (jit-stable; converged iterations are no-ops).

    ``backend`` selects the assignment-step implementation via
    ``repro.kernels.dispatch`` (None/"auto" = ambient policy): "pallas"
    fuses distances + argmin + per-centroid accumulation in one kernel,
    "jnp" is the reference two-matmul body.
    """
    if dispatch.count_route("kmeans_fit",
                            dispatch.resolve(backend)) == "pallas":
        res = _kmeans_fit_pallas(jnp.asarray(key)[None],
                                 jnp.asarray(x)[None], k, max_iter, tol)
        return KMeansResult(*(leaf[0] for leaf in res))
    return _kmeans_fit_jnp(key, x, k, max_iter, tol)


def kmeans_fit_batched(keys, xs, k: int, max_iter: int = 50, tol: float = 1e-6,
                       *, backend: Optional[str] = None):
    """Fit one KMeans per leading-axis slice in a single compiled call.

    keys: (C, 2) PRNG keys; xs: (C, n, d) stacked per-client data (same n and
    k for every slice — the cohort engine's homogeneity rule). Returns a
    ``KMeansResult`` whose fields carry a leading client axis. Equivalent to
    looping ``kmeans_fit`` per slice (same keys ⇒ same seeding draws), which
    ``tests/test_dre_contract.py`` checks. On the "pallas" backend the
    client axis is a kernel grid dimension (one trace for any C).
    """
    if dispatch.count_route("kmeans_fit",
                            dispatch.resolve(backend)) == "pallas":
        return _kmeans_fit_pallas(jnp.asarray(keys), jnp.asarray(xs),
                                  k, max_iter, tol)
    return _kmeans_fit_batched_jnp(keys, xs, k, max_iter, tol)


def min_dist_to_centroids(x, centroids):
    """Euclidean distance of each row of x to its nearest centroid."""
    d2 = pairwise_sq_dists(x.astype(jnp.float32), centroids.astype(jnp.float32))
    return jnp.sqrt(jnp.min(d2, axis=-1))
