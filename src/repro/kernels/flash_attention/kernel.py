"""Pallas TPU kernel: blocked online-softmax (flash) causal attention.

The prefill_32k roofline is dominated by the O(S²) attention; materialising
the (S, S) score matrix is what makes naive attention memory-bound on TPU.
This kernel streams KV tiles through VMEM with the online-softmax recurrence
(running max m, denominator l, accumulator acc as VMEM scratch), so HBM
traffic is O(S·h) per head instead of O(S²).

Grid: (B, N, Sq/bq, Sk/bk) with the KV axis innermost — the accumulator
carries across the innermost grid dimension (standard TPU flash pattern).
Causal masking uses global positions; KV tiles entirely above the diagonal
contribute nothing (masked to −inf) — the `block_skip` hillclimb variant
skips them at the grid level.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 256
BLOCK_K = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, causal: bool,
            num_kv_blocks: int, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, h)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, h)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, h)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < kv_len                       # mask KV padding
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        valid = valid & (kpos <= qpos)
    s = jnp.where(valid, s, NEG_INF)

    # running max/denominator are (bq, 1) columns: Mosaic has no layout
    # for a 1-D scratch, nor for reshaping a bool vector into a column
    m_prev = m_scr[...]                                   # (bq, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # masked keys carry no mass — also in rows with every key masked so
    # far, where exp(NEG_INF - NEG_INF) would be 1
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        lsum = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(lsum, 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, kv_len: int = 0,
                           block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                           interpret: bool = True):
    """q: (B, N, Sq, h); k, v: (B, N, Sk, h) GQA-expanded.
    Sq % block_q == 0 and Sk % block_k == 0 (ops.py pads); kv_len = true
    (unpadded) key count, 0 = Sk."""
    b, n, sq, h = q.shape
    sk = k.shape[2]
    grid = (b, n, sq // block_q, sk // block_k)
    scale = 1.0 / math.sqrt(h)
    kern = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, num_kv_blocks=sk // block_k, kv_len=kv_len or sk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, h), lambda b, n, qi, ki: (b, n, qi, 0)),
            pl.BlockSpec((1, 1, block_k, h), lambda b, n, qi, ki: (b, n, ki, 0)),
            pl.BlockSpec((1, 1, block_k, h), lambda b, n, qi, ki: (b, n, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, h), lambda b, n, qi, ki: (b, n, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, sq, h), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom l
            pltpu.VMEM((block_q, h), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
