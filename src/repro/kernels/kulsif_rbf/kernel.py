"""Pallas TPU kernel: tiled RBF Gram-matrix computation for KuLSIF-DRE.

The baseline estimator's K11/K12 construction is its learn-phase hot-spot
(paper Table IV: O(m²·d) time, O(m²) space). The kernel tiles the Gram
matrix into (BM × BN) VMEM blocks — matmul-form distances on the MXU, exp on
the VPU — so peak memory per step is one tile, not the full m×m matrix.

Grid: 2-D over (rows, cols) tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_M = 256
BLOCK_N = 256


def _kernel(a_ref, b_ref, sig_ref, out_ref):
    a = a_ref[...].astype(jnp.float32)            # (bm, d)
    b = b_ref[...].astype(jnp.float32)            # (bn, d)
    a2 = jnp.sum(a * a, axis=-1, keepdims=True)   # (bm, 1)
    b2 = jnp.sum(b * b, axis=-1)                  # (bn,)
    cross = jax.lax.dot_general(                  # full f32 on the MXU
        a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    d2 = jnp.maximum(a2 - 2.0 * cross + b2[None, :], 0.0)
    sig = sig_ref[0]
    out_ref[...] = jnp.exp(-d2 / (2.0 * sig * sig))


def rbf_matrix_pallas(a, b, sigma, *, block_m: int = BLOCK_M,
                      block_n: int = BLOCK_N, interpret: bool = True):
    """a: (n, d), b: (m, d) — n, m multiples of the block sizes (ops pads).
    Returns (n, m) f32 Gram matrix."""
    n, d = a.shape
    m = b.shape[0]
    sig = jnp.asarray([sigma], jnp.float32)
    grid = (n // block_m, m // block_n)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(a, b, sig)
