"""Pallas-kernel benchmarks: microbenchmarks + wired hot-path measurements.

Two sections, both per backend where it matters:

* ``micro`` — each kernel wrapper against its pure-jnp oracle at
  FD-realistic sizes (the historical microbenchmarks).
* ``wired`` — the *real* call sites the dispatch layer routes
  (``repro.kernels.dispatch``): a full ``kmeans_fit`` (fused Lloyd step
  vs the reference two-matmul body), one distillation step — forward AND
  backward through ``kd_kl_loss`` (the Pallas path differentiates through
  the custom-VJP backward kernel) — and a ``KuLSIFDRE.learn`` gram-matrix
  solve, each timed on both ``kernel_backend`` values.

On CPU the Pallas backend runs in interpret mode: correctness-scale
numbers only (expect jnp to win — interpret emits the kernel body as
unfused jnp ops). The BlockSpec tiling is the TPU deployment artifact;
on a TPU host the same script times the Mosaic-lowered kernels.

Results land at the repo root as ``BENCH_kernels.json`` (the BENCH_*
convention every other sweep uses); ``--out`` overrides.
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core.distill import kd_kl_loss
from repro.core.dre import KuLSIFDRE
from repro.core.kmeans import kmeans_fit
from repro.kernels import dispatch
from repro.kernels.distill_kl import ops as kl_ops, ref as kl_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.kmeans_dist import ops as kd_ops, ref as kd_ref
from repro.kernels.kulsif_rbf import ops as rbf_ops, ref as rbf_ref

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO_ROOT, "BENCH_kernels.json")
BACKENDS = ("jnp", "pallas")


def run_micro(quick=False):
    key = jax.random.PRNGKey(0)
    out = {}

    t, d, c = (1024, 50, 10) if quick else (8192, 50, 10)
    x = jax.random.normal(key, (t, d))
    cent = jax.random.normal(jax.random.fold_in(key, 1), (c, d))
    jit_ref = jax.jit(lambda a, b: kd_ref.min_dist_and_mask(a, b, 7.0))
    t_k = timeit(lambda: kd_ops.min_dist_and_mask(x, cent, 7.0))
    t_r = timeit(lambda: jit_ref(x, cent))
    out["kmeans_dist"] = {"pallas_s": t_k, "ref_s": t_r, "t": t, "d": d, "c": c}
    emit("kernel/kmeans_dist", t_k * 1e6, f"ref={t_r*1e6:.1f}us")

    jit_lref = jax.jit(kd_ref.lloyd_step)
    t_k = timeit(lambda: kd_ops.lloyd_step(x, cent))
    t_r = timeit(lambda: jit_lref(x, cent))
    out["lloyd_step"] = {"pallas_s": t_k, "ref_s": t_r, "t": t, "d": d, "c": c}
    emit("kernel/lloyd_step", t_k * 1e6, f"ref={t_r*1e6:.1f}us")

    n, m = (512, 512) if quick else (2048, 1024)
    a = jax.random.normal(key, (n, d))
    b = jax.random.normal(jax.random.fold_in(key, 2), (m, d))
    jit_ref = jax.jit(lambda p, q: rbf_ref.rbf_matrix(p, q, 3.0))
    t_k = timeit(lambda: rbf_ops.rbf_matrix(a, b, 3.0))
    t_r = timeit(lambda: jit_ref(a, b))
    out["kulsif_rbf"] = {"pallas_s": t_k, "ref_s": t_r, "n": n, "m": m}
    emit("kernel/kulsif_rbf", t_k * 1e6, f"ref={t_r*1e6:.1f}us")

    nn, k = (2048, 10) if quick else (16384, 10)
    s = jax.random.normal(key, (nn, k)) * 3
    tt = jax.random.normal(jax.random.fold_in(key, 3), (nn, k)) * 3
    jit_ref = jax.jit(lambda p, q: kl_ref.kd_kl_per_sample(p, q, 3.0))
    t_k = timeit(lambda: kl_ops.kd_kl_per_sample(s, tt, 3.0))
    t_r = timeit(lambda: jit_ref(s, tt))
    out["distill_kl"] = {"pallas_s": t_k, "ref_s": t_r, "n": nn, "k": k}
    emit("kernel/distill_kl", t_k * 1e6, f"ref={t_r*1e6:.1f}us")

    B, N, S, H = (1, 2, 256, 64) if quick else (1, 4, 1024, 64)
    q = jax.random.normal(key, (B, N, S, H))
    kk = jax.random.normal(jax.random.fold_in(key, 4), (B, N, S, H))
    v = jax.random.normal(jax.random.fold_in(key, 5), (B, N, S, H))
    jit_ref = jax.jit(lambda a1, a2, a3: fa_ref.attention(a1, a2, a3))
    t_k = timeit(lambda: fa_ops.attention(q, kk, v, block_q=128, block_k=128),
                 iters=3)
    t_r = timeit(lambda: jit_ref(q, kk, v), iters=3)
    out["flash_attention"] = {"pallas_s": t_k, "ref_s": t_r,
                              "B": B, "N": N, "S": S, "H": H}
    emit("kernel/flash_attention", t_k * 1e6, f"ref={t_r*1e6:.1f}us")
    return out


def run_wired(quick=False, backends=BACKENDS):
    """Time the dispatch layer's real call sites, per kernel_backend."""
    key = jax.random.PRNGKey(0)
    out = {}

    # full kmeans_fit: the fused Lloyd step (pallas) vs the reference body
    # that materialises the (n, k) one-hot and pays a second matmul (jnp)
    n, d, k, iters = (1024, 50, 10, 25) if quick else (8192, 50, 10, 50)
    x = jax.random.normal(key, (n, d)) * 2
    row = {"n": n, "d": d, "k": k, "max_iter": iters}
    for b in backends:
        row[f"{b}_s"] = timeit(
            lambda b=b: kmeans_fit(key, x, k, iters, backend=b), iters=3)
    out["kmeans_fit"] = row
    emit("wired/kmeans_fit", row["pallas_s"] * 1e6,
         f"jnp={row['jnp_s']*1e6:.1f}us")

    # one distill step: forward + backward through kd_kl_loss (the pallas
    # path exercises the custom-VJP backward kernel; at K < 128, as here,
    # both backends take the jnp route, dispatch.kd_kl_route)
    nn, kc = (2048, 10) if quick else (16384, 10)
    s = jax.random.normal(key, (nn, kc)) * 3
    tt = jax.random.normal(jax.random.fold_in(key, 3), (nn, kc)) * 3
    w = jnp.ones((nn,), jnp.float32)
    row = {"n": nn, "k": kc}
    for b in backends:
        step = jax.jit(jax.value_and_grad(
            lambda ss, b=b: kd_kl_loss(ss, tt, 3.0, w, backend=b)))
        row[f"{b}_s"] = timeit(lambda step=step: step(s))
    out["distill_step_fwd_bwd"] = row
    emit("wired/distill_step", row["pallas_s"] * 1e6,
         f"jnp={row['jnp_s']*1e6:.1f}us")

    # KuLSIF learn: gram construction + m×m solve (Table IV baseline cost)
    np_, aux = (512, 128) if quick else (2048, 256)
    priv = jax.random.normal(key, (np_, d))
    row = {"n_private": np_, "num_aux": aux}
    for b in backends:
        dre = KuLSIFDRE(sigma=3.0, num_aux=aux, kernel_backend=b)
        row[f"{b}_s"] = timeit(
            lambda dre=dre: dre.learn(jax.random.PRNGKey(1), priv).alpha,
            iters=3)
    out["kulsif_learn"] = row
    emit("wired/kulsif_learn", row["pallas_s"] * 1e6,
         f"jnp={row['jnp_s']*1e6:.1f}us")

    # flash attention at its wired call site — the layers layout
    # dispatch.flash_attention that attention_forward's non-chunked branch
    # routes — forward + backward (pallas = fused kernel forward +
    # oracle-recompute custom-VJP backward)
    Bq, Sq, Nh, Hd = (2, 128, 4, 32) if quick else (4, 512, 8, 64)
    qa = jax.random.normal(key, (Bq, Sq, Nh, Hd))
    ka = jax.random.normal(jax.random.fold_in(key, 6), (Bq, Sq, Nh, Hd))
    va = jax.random.normal(jax.random.fold_in(key, 7), (Bq, Sq, Nh, Hd))
    row = {"B": Bq, "S": Sq, "N": Nh, "h": Hd}
    for b in backends:
        step = jax.jit(jax.grad(lambda qq, b=b: jnp.sum(
            dispatch.flash_attention(qq, ka, va, causal=True,
                                     backend=b) ** 2)))
        row[f"{b}_s"] = timeit(lambda step=step: step(qa), iters=3)
    out["flash_attention_fwd_bwd"] = row
    emit("wired/flash_attention", row["pallas_s"] * 1e6,
         f"jnp={row['jnp_s']*1e6:.1f}us")
    return out


def run(quick=False):
    """Micro + wired sections (the registry entry benchmarks/run.py uses)."""
    return {
        "benchmark": "kernels",
        "platform": jax.default_backend(),
        "interpret_mode": jax.default_backend() != "tpu",
        "note": "off-TPU the pallas backend runs in interpret mode "
                "(kernel body emitted as unfused jnp ops): numbers "
                "validate the wiring, the tiling is the TPU artifact",
        "micro": run_micro(quick=quick),
        "wired": run_wired(quick=quick),
    }


def run_and_save(quick=False, out_path: str = DEFAULT_OUT):
    results = run(quick=quick)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"saved {out_path}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="output JSON path (default: repo-root "
                         "BENCH_kernels.json, like the other BENCH_* files)")
    args = ap.parse_args(argv)
    run_and_save(quick=args.quick, out_path=args.out)


if __name__ == "__main__":
    main()
