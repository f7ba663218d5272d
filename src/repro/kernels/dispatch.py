"""Kernel backend dispatch: route hot-path ops to Pallas or pure jnp.

Every compute hot-spot of the federated round — the Lloyd assignment step
of the KMeans-DRE fit, the temperature-KL distillation loss, and the
KuLSIF RBF gram matrices — exists twice in this repo: a purpose-built
Pallas TPU kernel (``repro.kernels.*``) and the pure-jnp reference the
framework historically ran. This module is the single switch between
them.

Backends
--------
``kernel_backend ∈ {"auto", "pallas", "jnp"}``:

* ``"auto"`` (the default everywhere) — Pallas on TPU, jnp elsewhere.
  Interpret-mode Pallas is deliberately **never** an ``auto`` choice: it
  emits the kernel body as ordinary jnp ops (a test/CI vehicle, not a
  fast path), so on CPU/GPU hosts ``auto`` means the tuned XLA reference
  code.
* ``"pallas"`` — force the Pallas kernels. On a TPU they lower through
  Mosaic; on any other backend they run in interpret mode, which is how
  CI exercises the kernel code paths end-to-end
  (``REPRO_KERNEL_BACKEND=pallas`` on a CPU matrix entry).
* ``"jnp"`` — force the reference path. On CPU this is bit-for-bit the
  pre-dispatch behavior (``tests/test_kernel_dispatch.py`` pins it
  against golden round logs).

Resolution order for an ``"auto"``/unset request: the innermost
:func:`kernel_backend` context manager, then the ``REPRO_KERNEL_BACKEND``
environment variable, then the platform rule above. An explicit
``"pallas"``/``"jnp"`` (e.g. ``FedConfig.kernel_backend``) always wins.

Resolution happens at *trace* time: jitted round phases bake the resolved
backend in when they first compile, so flipping the ambient backend never
retraces an already-compiled phase (and selecting a backend per config is
one compile per backend, cached thereafter).

The jnp fallbacks in this module are the **canonical** reference
implementations — ``repro.core.kmeans.pairwise_sq_dists`` and
``repro.core.dre.rbf_kernel`` delegate here. Their op sequences must not
change: the default-backend bit-for-bit guarantee rides on them.
"""
from __future__ import annotations

import collections
import contextlib
import os
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.models.sharding import shard_local

BACKENDS = ("auto", "pallas", "jnp")
ENV_VAR = "REPRO_KERNEL_BACKEND"

_context_stack: List[str] = []

# (op, route) -> how often an op was dispatched that way; inside jit that
# is once per trace. ``chip_smoke.py`` prints it, so a reference route
# taken on the chip is seen rather than assumed away.
route_counts: collections.Counter = collections.Counter()


def count_route(op: str, route: str) -> str:
    route_counts[(op, route)] += 1
    return route


def _validate(name: str, source: str) -> str:
    if name not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {name!r} (from {source}); "
            f"known: {', '.join(BACKENDS)}")
    return name


def requested_backend(backend: Optional[str] = None) -> str:
    """The raw request before platform resolution (may be ``"auto"``)."""
    if backend is not None and _validate(backend, "argument") != "auto":
        return backend
    if _context_stack and _context_stack[-1] != "auto":
        return _context_stack[-1]
    env = os.environ.get(ENV_VAR, "")
    if env and _validate(env, f"${ENV_VAR}") != "auto":
        return env
    return "auto"


def resolve(backend: Optional[str] = None) -> str:
    """Resolve a request down to the concrete backend: "pallas" or "jnp".

    ``None`` and ``"auto"`` defer to the ambient request (context manager,
    then ``REPRO_KERNEL_BACKEND``), and finally to the platform rule:
    Pallas iff running on TPU.
    """
    b = requested_backend(backend)
    if b != "auto":
        return b
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


@contextlib.contextmanager
def kernel_backend(name: str):
    """Scoped ambient-backend override (tests/benchmarks).

    Overrides ``"auto"``/unset requests inside the ``with`` block; an
    explicit per-call/per-config ``"pallas"``/``"jnp"`` still wins. Note
    that jitted functions resolve at trace time — state built *before*
    entering the context keeps the backend it compiled with.
    """
    _validate(name, "kernel_backend()")
    _context_stack.append(name)
    try:
        yield
    finally:
        _context_stack.pop()


# ---------------------------------------------------------------------------
# Canonical jnp reference implementations (bit-for-bit sensitive)
# ---------------------------------------------------------------------------

def pairwise_sq_dists(x, c):
    """‖x−c‖² via the matmul form (MXU-friendly): x:(n,d), c:(k,d) -> (n,k).

    The cross term is a full-f32 matmul: the form cancels badly at wide
    inputs, and a TPU runs a default-precision f32 matmul in bf16 passes."""
    x2 = jnp.sum(jnp.square(x), axis=-1, keepdims=True)        # (n,1)
    c2 = jnp.sum(jnp.square(c), axis=-1)                       # (k,)
    cross = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)  # (n,k)
    return jnp.maximum(x2 - 2.0 * cross + c2[None, :], 0.0)


def _rbf_matrix_jnp(a, b, sigma):
    """K(a,b) = exp(−‖a−b‖²/(2σ²)) — the historical ``dre.rbf_kernel``."""
    d2 = pairwise_sq_dists(a, b)
    return jnp.exp(-d2 / (2.0 * sigma * sigma))


def _lloyd_step_jnp(x, centroids):
    """One fused-Lloyd equivalent in plain jnp (matmul distances, one-hot
    scatter): x (n,d), centroids (k,d) -> (assign (n,) i32, min_d2 (n,),
    sums (k,d), counts (k,)). This is the op sequence ``kmeans_fit``'s
    reference scan body has always used — including its f32 accumulation,
    which the Pallas kernel matches for any input dtype."""
    x = x.astype(jnp.float32)
    centroids = centroids.astype(jnp.float32)
    k = centroids.shape[0]
    d2 = pairwise_sq_dists(x, centroids)
    assign = jnp.argmin(d2, axis=-1)
    one_hot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
    counts = jnp.sum(one_hot, axis=0)                          # (k,)
    sums = one_hot.T @ x                                       # (k, d)
    return (assign.astype(jnp.int32), jnp.min(d2, axis=-1), sums, counts)


# ---------------------------------------------------------------------------
# Dispatched ops
# ---------------------------------------------------------------------------

def lloyd_step(x, centroids, *, backend: Optional[str] = None):
    """Fused Lloyd assignment + accumulation step of the KMeans-DRE fit.

    ``x``: (n, d) or batched (C, n, d); ``centroids``: (k, d) / (C, k, d).
    Returns ``(assign int32, min_d2 f32, sums f32, counts f32)`` with
    matching leading axes. Pallas fuses the matmul-form distances, the
    argmin and the per-centroid sum/count accumulation in VMEM — the
    (n, k) one-hot never reaches HBM and there is no second full matmul
    pass over the data.
    """
    if count_route("lloyd_step", resolve(backend)) == "pallas":
        from repro.kernels.kmeans_dist import ops as kd_ops
        return kd_ops.lloyd_step(x, centroids)
    if x.ndim == 3:
        return jax.vmap(_lloyd_step_jnp)(x, centroids)
    return _lloyd_step_jnp(x, centroids)


# Narrower than one 128-lane tile, a class axis pads every kernel operand
# to 128 lanes (and each vmapped call's rows to the kernel's block), so the
# kernel moves mostly padding; XLA fuses the reference formula over the
# real (n, K) instead. From one full tile on, the fused kernel keeps its
# premise: one VMEM pass in place of four HBM intermediates.
KD_KL_MIN_CLASSES = 128


def kd_kl_route(num_classes: int, backend: Optional[str] = None) -> str:
    """The counted route of one temperature-KL call over ``num_classes``
    logits: the resolved backend where the class axis fills at least one
    lane tile (K ≥ 128), else ``"jnp (K<128)"`` whatever backend resolved.
    The one place the K rule lives; ``core.distill.kd_kl_loss`` asks it."""
    route = (resolve(backend) if num_classes >= KD_KL_MIN_CLASSES
             else "jnp (K<128)")
    return count_route("kd_kl", route)


def kd_kl_pallas(student_logits, teacher_logits, temperature: float):
    """The fused Pallas route, (n, K) -> (n,), uncounted: callers take it
    only where :func:`kd_kl_route` said ``"pallas"``."""
    from repro.kernels.distill_kl import ops as kl_ops
    temp = float(temperature)
    return shard_local(
        lambda s, t: kl_ops.kd_kl_per_sample_vjp(s, t, temp),
        [("batch", None)] * 2, ("batch",))(student_logits, teacher_logits)


def kd_kl_per_sample(student_logits, teacher_logits, temperature: float,
                     *, backend: Optional[str] = None):
    """Per-sample temperature-KL (Hinton) distillation loss, (n, K) -> (n,).

    Takes the Pallas kernel only where the resolved backend is "pallas"
    *and* K ≥ ``KD_KL_MIN_CLASSES`` (128, one lane tile); a narrower class
    axis always takes the jnp reference, counted as ``"jnp (K<128)"``
    (:func:`kd_kl_route`). Differentiable on both routes: the Pallas path
    carries a ``jax.custom_vjp`` whose backward pass is a second fused
    kernel (softmax recompute + both logit gradients in one VMEM tile).
    ``temperature`` is compile-time static on the Pallas path — gradients
    w.r.t. it are not defined there (they never are in the FD protocol).
    """
    if kd_kl_route(student_logits.shape[-1], backend) == "pallas":
        return kd_kl_pallas(student_logits, teacher_logits, temperature)
    from repro.kernels.distill_kl import ref as kl_ref
    return kl_ref.kd_kl_per_sample(student_logits, teacher_logits,
                                   temperature)


def rbf_matrix(a, b, sigma, *, backend: Optional[str] = None):
    """RBF gram matrix K(a, b), (n, d) × (m, d) -> (n, m) f32.

    The KuLSIF-DRE learn/estimate hot-spot; the Pallas path tiles the
    gram matrix through VMEM (peak memory one tile, not n×m).
    """
    if count_route("rbf_matrix", resolve(backend)) == "pallas":
        from repro.kernels.kulsif_rbf import ops as rbf_ops
        return shard_local(rbf_ops.rbf_matrix, [(None, None)] * 2 + [()],
                           (None, None))(a, b, jnp.float32(sigma))
    return _rbf_matrix_jnp(a, b, sigma)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    backend: Optional[str] = None):
    """Full-sequence attention in the model layout: q/k/v (B, S, N, h),
    kv already GQA-expanded. Returns (B, S, N, h) in ``v.dtype``.

    The transformer local-train/distill hot path. The jnp route is
    op-for-op ``models.layers``' historical mask + scores sequence (the
    default-backend bit-for-bit guarantee rides on it); the Pallas route
    is the fused flash kernel (O(S) memory, online softmax), which covers
    causal/full attention only — a sliding ``window`` always takes the
    reference path regardless of backend. Differentiable on both routes
    (the kernel carries a ``custom_vjp``; see ``flash_attention.ops``).
    """
    route = resolve(backend) if window == 0 else "jnp (window)"
    if count_route("flash_attention", route) == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        def attend(q, k, v):
            return fa_ops.attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                    v.swapaxes(1, 2),
                                    causal=causal).swapaxes(1, 2)

        axes = ("batch", "seq", "heads", "head_dim")
        return shard_local(attend, [axes] * 3, axes)(q, k, v).astype(v.dtype)
    from repro.models import layers as L
    mask = L.make_mask(q.shape[1], k.shape[1], causal=causal, window=window)
    return L.attention_scores(q, k, v, mask)
