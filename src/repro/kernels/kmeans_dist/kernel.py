"""Pallas TPU kernels for KMeans-DRE: min-distance estimation + fused Lloyd.

``kmeans_dist_pallas`` is the *estimation* hot-spot of KMeans-DRE (paper
Table IV: O(t·c·d)). TPU-native formulation (DESIGN.md §3): ‖x−k‖² =
‖x‖² − 2·x·Kᵀ + ‖k‖² turns the distance into one MXU matmul per tile;
min-reduction and the threshold compare fuse in VMEM so the boolean mask
never round-trips to HBM.

``lloyd_step_pallas`` is the *fit* hot-spot (Algorithm 1 line 3,
O(k·n·c·d)): one Lloyd iteration — the same matmul-form distances, the
argmin assignment, and the per-centroid sum/count accumulation — fused in
a single kernel. The reference ``kmeans_fit`` scan body materialises an
(n, k) one-hot in HBM and pays a second full (k, n)·(n, d) matmul pass
over the data; here the one-hot lives only as a (block_t, k) VMEM tile
and the partial sums accumulate into a resident (k, d) output block
across grid steps.

Grid: 1-D over tiles of t (``kmeans_dist``), or (C, tiles-of-t) with a
leading client axis (``lloyd_step`` — the cohort engine fits every
client's filter in one call, so the batch axis is part of the grid, not a
per-client retrace). Centroid tiles (c ≤ 1024, d) stay resident in VMEM
across the tile axis (constant index_map).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_T = 256
# f32 on the MXU: the matmul-form distance ‖x‖² − 2x·c + ‖c‖² cancels badly
# at 3072-wide inputs unless the cross term keeps full f32 precision
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(x_ref, c_ref, thr_ref, dist_ref, mask_ref):
    x = x_ref[...].astype(jnp.float32)           # (bt, d)
    c = c_ref[...].astype(jnp.float32)           # (C, d)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)  # (bt, 1)
    c2 = jnp.sum(c * c, axis=-1)                 # (C,)
    cross = jax.lax.dot_general(                 # (bt, C) — the MXU matmul
        x, c, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    d2 = jnp.maximum(x2 - 2.0 * cross + c2[None, :], 0.0)
    md = jnp.sqrt(jnp.min(d2, axis=-1))
    dist_ref[...] = md
    mask_ref[...] = (md <= thr_ref[0]).astype(jnp.int8)


def kmeans_dist_pallas(x, centroids, threshold, *, block_t: int = BLOCK_T,
                       interpret: bool = True):
    """x: (t, d) — t must be a multiple of block_t (ops.py pads).
    centroids: (c, d); threshold: scalar.
    Returns (min_dist (t,) f32, is_id (t,) int8)."""
    t, d = x.shape
    c = centroids.shape[0]
    thr = jnp.asarray([threshold], jnp.float32)
    grid = (t // block_t,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((c, d), lambda i: (0, 0)),       # resident
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((block_t,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t,), jnp.float32),
            jax.ShapeDtypeStruct((t,), jnp.int8),
        ],
        interpret=interpret,
    )(x, centroids, thr)


def _lloyd_kernel(x_ref, c_ref, assign_ref, mind2_ref, sums_ref, counts_ref,
                  *, block_t: int, n_true: int):
    j = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)             # (bt, d)
    c = c_ref[0].astype(jnp.float32)             # (k, d)
    k = c.shape[0]
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)  # (bt, 1)
    c2 = jnp.sum(c * c, axis=-1)                 # (k,)
    cross = jax.lax.dot_general(                 # (bt, k) — the MXU matmul
        x, c, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    d2 = jnp.maximum(x2 - 2.0 * cross + c2[None, :], 0.0)
    assign = jnp.argmin(d2, axis=-1)             # (bt,)
    # per-sample outputs are lane-dense (1, bt) rows of a (C, 1, t) array
    assign_ref[0] = assign.astype(jnp.int32)[None, :]
    mind2_ref[0] = jnp.min(d2, axis=-1)[None, :]
    # (bt, k) one-hot lives only in this VMEM tile; rows past the true
    # sample count (ops.py pads t up to a block multiple) carry no mass
    row = j * block_t + jax.lax.broadcasted_iota(jnp.int32, (block_t, 1), 0)
    valid = (row < n_true).astype(jnp.float32)   # (bt, 1)
    oh = (assign[:, None]
          == jax.lax.broadcasted_iota(jnp.int32, (block_t, k), 1)
          ).astype(jnp.float32) * valid
    part_sums = jax.lax.dot_general(             # (k, d) — second MXU matmul
        oh, x, (((0,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    part_counts = jnp.sum(oh, axis=0, keepdims=True)  # (1, k)

    @pl.when(j == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    # the (k, d)/(1, k) output blocks have a constant index_map along the
    # tile axis, so they stay resident and accumulate across grid steps
    sums_ref[0] += part_sums
    counts_ref[0] += part_counts


def lloyd_step_pallas(x, centroids, *, block_t: int = BLOCK_T,
                      n_true: int | None = None, interpret: bool = True):
    """x: (C, t, d) — t a multiple of block_t (ops.py pads); centroids:
    (C, k, d); n_true = true (unpadded) row count, None = t.
    Returns (assign (C, t) i32, min_d2 (C, t) f32, sums (C, k, d) f32,
    counts (C, k) f32) — padded rows excluded from sums/counts."""
    bc, t, d = x.shape
    k = centroids.shape[1]
    grid = (bc, t // block_t)
    kern = functools.partial(_lloyd_kernel, block_t=block_t,
                             n_true=n_true if n_true is not None else t)
    assign, min_d2, sums, counts = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda c, j: (c, j, 0)),
            pl.BlockSpec((1, k, d), lambda c, j: (c, 0, 0)),   # resident
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_t), lambda c, j: (c, 0, j)),
            pl.BlockSpec((1, 1, block_t), lambda c, j: (c, 0, j)),
            pl.BlockSpec((1, k, d), lambda c, j: (c, 0, 0)),   # accumulated
            pl.BlockSpec((1, 1, k), lambda c, j: (c, 0, 0)),   # accumulated
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bc, 1, t), jnp.int32),
            jax.ShapeDtypeStruct((bc, 1, t), jnp.float32),
            jax.ShapeDtypeStruct((bc, k, d), jnp.float32),
            jax.ShapeDtypeStruct((bc, 1, k), jnp.float32),
        ],
        interpret=interpret,
    )(x, centroids)
    return assign[:, 0], min_d2[:, 0], sums, counts[:, 0]
