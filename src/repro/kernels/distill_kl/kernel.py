"""Pallas TPU kernels: fused temperature-softmax KL loss, forward + backward.

Per distillation batch the loss touches two (n, K) logit tensors; unfused,
XLA materialises four intermediates (two log-softmaxes, probs, pointwise
product) in HBM. The forward kernel computes both stabilised log-softmaxes
and the weighted KL reduction inside one VMEM tile — one read of each
operand, one (n,) write.

The backward kernel closes the loop for training through the kernel
(``ops.kd_kl_per_sample_vjp``): it recomputes both softmaxes from the saved
logits (cheaper than storing probabilities) and emits the analytic
gradients in the same tile —

    ∂(T²·KL_i)/∂s = g_i · T · (softmax(s/T) − softmax(t/T))
    ∂(T²·KL_i)/∂t = g_i · T · softmax(t/T) · ((log t̂ − log ŝ) − KL_i/T²)

so a fused distill step never materialises probabilities in HBM in either
direction.

Grid: 1-D over tiles of n; the class axis K stays whole inside a tile
(K ≤ a few thousand for FD logits). Dispatch routes calls here only at
K ≥ 128 (``repro.kernels.dispatch.kd_kl_route``): a narrower K pads every
operand to one 128-lane tile, and XLA's fusion of the reference wins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 512


def _kernel(s_ref, t_ref, temp_ref, out_ref):
    s = s_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    temp = temp_ref[0]
    s = s / temp
    t = t / temp
    s_max = jnp.max(s, axis=-1, keepdims=True)
    t_max = jnp.max(t, axis=-1, keepdims=True)
    s_lse = jnp.log(jnp.sum(jnp.exp(s - s_max), axis=-1, keepdims=True)) + s_max
    t_lse = jnp.log(jnp.sum(jnp.exp(t - t_max), axis=-1, keepdims=True)) + t_max
    s_logp = s - s_lse
    t_logp = t - t_lse
    kl = jnp.sum(jnp.exp(t_logp) * (t_logp - s_logp), axis=-1, keepdims=True)
    out_ref[...] = kl * temp * temp


def kd_kl_pallas(student, teacher, temperature, *, block_n: int = BLOCK_N,
                 interpret: bool = True):
    """student/teacher: (n, K), n a multiple of block_n (ops pads).
    Returns per-sample KL (n,) f32.

    Per-sample values travel as an (n, 1) column with (block_n, 1) blocks:
    a 1-D (block_n,) block does not match XLA's 1-D tiling once n spans
    more than one block, and Mosaic refuses it."""
    n, k = student.shape
    temp = jnp.asarray([temperature], jnp.float32)
    grid = (n // block_n,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(student, teacher, temp)[:, 0]


def _log_softmaxes(s_ref, t_ref, temp: float):
    """Shared bwd recompute: stabilised log-softmaxes of both logit tiles."""
    s = s_ref[...].astype(jnp.float32) / temp
    t = t_ref[...].astype(jnp.float32) / temp
    s_max = jnp.max(s, axis=-1, keepdims=True)
    t_max = jnp.max(t, axis=-1, keepdims=True)
    s_lse = jnp.log(jnp.sum(jnp.exp(s - s_max), axis=-1, keepdims=True)) + s_max
    t_lse = jnp.log(jnp.sum(jnp.exp(t - t_max), axis=-1, keepdims=True)) + t_max
    return s - s_lse, t - t_lse


def _bwd_ds_kernel(s_ref, t_ref, g_ref, ds_ref, *, temp: float):
    s_logp, t_logp = _log_softmaxes(s_ref, t_ref, temp)
    gt = g_ref[...].astype(jnp.float32) * temp            # (bn, 1)
    ds_ref[...] = (gt * (jnp.exp(s_logp) - jnp.exp(t_logp))
                   ).astype(ds_ref.dtype)


def _bwd_dt_kernel(s_ref, t_ref, g_ref, dt_ref, *, temp: float):
    s_logp, t_logp = _log_softmaxes(s_ref, t_ref, temp)
    tp = jnp.exp(t_logp)
    # f = KL_i / T² — recomputed, not saved (one extra reduction in VMEM)
    f = jnp.sum(tp * (t_logp - s_logp), axis=-1, keepdims=True)
    gt = g_ref[...].astype(jnp.float32) * temp            # (bn, 1)
    dt_ref[...] = (gt * tp * ((t_logp - s_logp) - f)).astype(dt_ref.dtype)


def _bwd_call(kern, out_dtype, student, teacher, g, block_n, interpret):
    n, k = student.shape
    return pl.pallas_call(
        kern,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),
            pl.BlockSpec((block_n, k), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), out_dtype),
        interpret=interpret,
    )(student, teacher, g[:, None])


def kd_kl_bwd_pallas(student, teacher, g, temperature: float, *,
                     block_n: int = BLOCK_N, interpret: bool = True):
    """Backward pass: student/teacher (n, K), per-sample cotangent g (n,).
    Returns (d_student, d_teacher), each (n, K) in the primal dtype.
    ``temperature`` is compile-time static (baked into the kernels).

    The two gradients are *separate* kernel launches on purpose: in the FD
    protocol the teacher is the server's aggregated logits — a constant —
    so its cotangent is dead downstream and XLA eliminates the d_teacher
    launch entirely instead of fusing its cost into every distill step.
    The price is recomputing the two log-softmaxes when both gradients
    really are needed (rare), which is VMEM-cheap.
    """
    temp = float(temperature)
    ds = _bwd_call(functools.partial(_bwd_ds_kernel, temp=temp),
                   student.dtype, student, teacher, g, block_n, interpret)
    dt = _bwd_call(functools.partial(_bwd_dt_kernel, temp=temp),
                   teacher.dtype, student, teacher, g, block_n, interpret)
    return ds, dt
