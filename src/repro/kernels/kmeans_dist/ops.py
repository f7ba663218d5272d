"""Jit'd public wrapper for the kmeans_dist kernel (padding + dtype)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret, pad_to
from repro.kernels.kmeans_dist.kernel import (BLOCK_T, kmeans_dist_pallas,
                                              lloyd_step_pallas)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def _run(x, centroids, threshold, block_t, interpret):
    xp, t = pad_to(x, 0, block_t)
    dist, mask = kmeans_dist_pallas(xp, centroids, threshold,
                                    block_t=block_t, interpret=interpret)
    return dist[:t], mask[:t].astype(bool)


def min_dist_and_mask(x, centroids, threshold, *, block_t: int = BLOCK_T,
                      interpret: bool | None = None):
    """Public op: (min_dist (t,), is_id (t,) bool)."""
    if interpret is None:
        interpret = default_interpret()
    return _run(jnp.asarray(x), jnp.asarray(centroids),
                jnp.float32(threshold), block_t, interpret)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def _run_lloyd(x, centroids, block_t, interpret):
    xp, n = pad_to(x, 1, block_t)
    assign, min_d2, sums, counts = lloyd_step_pallas(
        xp, centroids, block_t=block_t, n_true=n, interpret=interpret)
    return assign[:, :n], min_d2[:, :n], sums, counts


def lloyd_step(x, centroids, *, block_t: int | None = None,
               interpret: bool | None = None):
    """Public op: one fused Lloyd iteration of the KMeans-DRE fit.

    ``x``: (n, d) or (C, n, d); ``centroids``: (k, d) / (C, k, d).
    Returns (assign i32, min_d2 f32, sums (…, k, d) f32, counts (…, k)
    f32) with matching leading axes; padded rows never reach sums/counts.
    ``block_t`` None halves the row tile for rows wider than 2048: for a
    v5e, the (C=10, t=4000, d=3072, k=10) step runs out of VMEM with
    256-row tiles and fits with 128 (``tests/test_tpu_compile.py``).
    """
    if interpret is None:
        interpret = default_interpret()
    x = jnp.asarray(x)
    centroids = jnp.asarray(centroids)
    if block_t is None:
        block_t = BLOCK_T if x.shape[-1] <= 2048 else BLOCK_T // 2
    if x.ndim == 2:
        out = _run_lloyd(x[None], centroids[None], block_t, interpret)
        return tuple(o[0] for o in out)
    return _run_lloyd(x, centroids, block_t, interpret)
