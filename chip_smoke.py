#!/usr/bin/env python3
"""Smoke test: EdgeFD rounds on a TPU through the normal entry point.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the multi-chip paths, four chips

One chip runs three phases in this one process:

1. ``kernels``: each Pallas kernel the round calls, compiled by Mosaic and
   run on a small input, against a float64 NumPy reference.
2. ``cifar_like``: ``repro.launch.fed_train.main`` with the Tables I/II
   CNN zoo at its published widths on a CIFAR-10-shaped set (32x32x3,
   50k samples): ten clients, each a singleton cohort.
3. ``mnist_feat``: one stacked C=10 cohort, so the batched Lloyd kernel
   and the vmapped distill-KL kernel run with a client axis.

``--four-chips`` runs only the mesh paths, each against the same run
without a mesh: the ``mnist_feat`` cohort on a 1-D client mesh
(``--devices 4``; C=10 pads to 12) and the ``lm_tokens`` transformer
cohort on a 2-D ``(clients, model)`` mesh (``--devices 4
--model-shards 2``).

Every phase prints its resolved kernel backend, the kernel routes taken,
compile and round times, per-client ID fractions, accuracy and peak
device memory. These are bring-up observations, not benchmark results.
All data comes from ``--seed``. The script exits non-zero, and prints no
result line, when JAX finds no TPU, when the default kernel backend is not
Pallas, when a check fails or when any phase raises. On success the last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# the repo's accuracy sanity band (edgefd/strong on mnist_feat, 3 rounds)
MNIST_FEAT_MIN_ACC = 0.7
# Mesh runs against the unsharded run. On the 1-D client mesh no number of
# a client crosses a device, but each device holds 3 clients where the
# unsharded program holds 10, and XLA may fuse and tile the two shapes
# differently: last-bit f32 differences, which SGD carries through every
# step of three rounds. That drift is held to 1e-4 relative on the losses
# and to 1 in 100 test predictions (or proxy samples kept) per client.
# On the 2-D mesh, contractions over a model-sharded dim (attention
# out-projection, MLP down-projection) also add partial sums across chips
# in another order, so the bound is 1e-3 and 2 in 100. A wrong shard or
# padding mix-up moves accuracies by tenths, far outside either bound.
MESH_1D_TOL = {"acc": 0.01, "loss_rtol": 1e-4, "id": 0.01}
MESH_2D_TOL = {"acc": 0.02, "loss_rtol": 1e-3, "id": 0.02}


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def tpu_devices(count: int):
    import jax
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"JAX found no TPU (platform {devices[0].platform!r})")
    check(len(devices) >= count,
          f"{count} TPU chips needed, {len(devices)} visible")
    return devices


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def check_kernels(key_seed: int) -> None:
    """Each wired kernel, compiled by Mosaic, against a float64 NumPy
    reference on a small input.

    Beside each kernel's error the line prints that of XLA's own f32 path
    on the same chip (the kernel's ``ref.py``, matmuls at full f32), the
    scale of f32 rounding there. A TPU's f32 ``exp`` and ``log`` are
    approximations good to a few 1e-4 relative, so distill-KL, all
    softmaxes, takes 1e-3 where the CPU tests take 1e-5: on a v5e XLA's
    f32 path misses the float64 KL by 2.5e-3 on values near 10, as the
    kernel does. Flash attention's matmuls run at the MXU's default
    precision (bf16 passes, f32 accumulation), as the model's attention
    does, so it takes the bf16 test tolerance.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.distill_kl import ops as kl_ops, ref as kl_ref
    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    from repro.kernels.kmeans_dist import ops as kd_ops, ref as kd_ref
    from repro.kernels.kulsif_rbf import ops as rbf_ops, ref as rbf_ref

    rng = np.random.default_rng(key_seed)

    def close(name, got, xla, want, tol):
        """|got − want| ≤ tol·(1 + |want|), elementwise."""
        got = np.asarray(got, np.float64)
        check(got.shape == want.shape,
              f"{name}: shape {got.shape} != {want.shape}")
        check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
        err = np.abs(got - want)
        xla_err = np.max(np.abs(np.asarray(xla, np.float64) - want))
        print(f"  {name:<20} shape={got.shape}  max|err| kernel "
              f"{np.max(err):.3g}, XLA f32 {xla_err:.3g}; tol {tol}",
              flush=True)
        check(bool(np.all(err <= tol * (1.0 + np.abs(want)))),
              f"{name}: kernel off the float64 reference")

    def xla_f32(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*(jnp.asarray(a, jnp.float32) for a in args))

    # Lloyd step, C=10 clients: well-separated clusters, so the assignment
    # has no near-ties and must match exactly
    c, n, d, k = 10, 600, 50, 4
    centers = 8.0 * rng.standard_normal((c, k, d))
    labels = rng.integers(0, k, (c, n))
    x = (np.take_along_axis(centers, labels[..., None], axis=1)
         + rng.standard_normal((c, n, d))).astype(np.float32)
    cents = (centers + 0.1 * rng.standard_normal((c, k, d))).astype(np.float32)
    d2 = np.sum(np.square(x.astype(np.float64)[:, :, None]
                          - cents.astype(np.float64)[:, None]), axis=-1)
    assign = np.argmin(d2, axis=-1)
    onehot = np.eye(k)[assign]                                # (c, n, k)
    got = kd_ops.lloyd_step(x, cents)
    xla = xla_f32(jax.vmap(kd_ref.lloyd_step), x, cents)
    check(bool(np.all(np.asarray(got[0]) == assign)),
          "lloyd_step: assignments differ from the float64 reference")
    close("lloyd_step min_d2", got[1], xla[1], np.min(d2, axis=-1), 1e-4)
    close("lloyd_step sums", got[2], xla[2],
          np.einsum("cnk,cnd->ckd", onehot, x.astype(np.float64)), 1e-4)
    close("lloyd_step counts", got[3], xla[3], onehot.sum(axis=1), 0.0)

    # distill-KL forward and backward at n > one 512-row block
    temp = 3.0
    s = (3.0 * rng.standard_normal((1024, 10))).astype(np.float32)
    t = (3.0 * rng.standard_normal((1024, 10))).astype(np.float32)

    def log_softmax(z):
        z = z.astype(np.float64) / temp
        z = z - z.max(axis=-1, keepdims=True)
        return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))

    s_logp, t_logp = log_softmax(s), log_softmax(t)
    t_p = np.exp(t_logp)
    kl = np.sum(t_p * (t_logp - s_logp), axis=-1)             # KL_i / T²
    close("kd_kl forward", kl_ops.kd_kl_per_sample_vjp(s, t, temp),
          xla_f32(kl_ref.kd_kl_per_sample, s, t, temp), kl * temp**2, 1e-3)
    g_kernel = jax.grad(lambda a, b: kl_ops.kd_kl_per_sample_vjp(
        a, b, temp).sum(), argnums=(0, 1))(s, t)
    g_xla = xla_f32(jax.grad(lambda a, b: kl_ref.kd_kl_per_sample(
        a, b, temp).sum(), argnums=(0, 1)), s, t)
    close("kd_kl d_student", g_kernel[0], g_xla[0],
          temp * (np.exp(s_logp) - t_p), 1e-3)
    close("kd_kl d_teacher", g_kernel[1], g_xla[1],
          temp * t_p * ((t_logp - s_logp) - kl[:, None]), 1e-3)

    # KuLSIF RBF gram
    sigma = 6.0
    a = rng.standard_normal((300, 50)).astype(np.float32)
    b = rng.standard_normal((260, 50)).astype(np.float32)
    ab2 = np.sum(np.square(a.astype(np.float64)[:, None]
                           - b.astype(np.float64)[None]), axis=-1)
    close("rbf_matrix", rbf_ops.rbf_matrix(a, b, sigma),
          xla_f32(rbf_ref.rbf_matrix, a, b, sigma),
          np.exp(-ab2 / (2.0 * sigma**2)), 1e-4)

    # flash attention forward, causal, past one 256-row block
    q, kk, v = (rng.standard_normal((2, 4, 300, 64)).astype(np.float32)
                for _ in range(3))
    logits = np.einsum("bnqh,bnkh->bnqk", q.astype(np.float64), kk) / 8.0
    logits = np.where(np.tril(np.ones((300, 300), bool)), logits, -np.inf)
    probs = np.exp(logits - logits.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    close("flash_attention", fa_ops.attention(q, kk, v),
          xla_f32(fa_ref.attention, q, kk, v),
          np.einsum("bnqk,bnkh->bnqh", probs, v), 5e-2)


def run_phase(name: str, argv, device, *, min_acc=None):
    """One in-process ``fed_train.main`` run; prints its observations and
    checks its round logs. Returns the logs."""
    import jax
    import numpy as np

    from repro.common import tracing
    from repro.kernels import dispatch
    from repro.launch import fed_train

    print(f"\n== phase {name}: fed_train {' '.join(argv)}", flush=True)
    dispatch.route_counts.clear()
    compiles0, compile_s0 = tracing.compiles()
    stamps = []

    def on_round(_log):
        # the round's device work is done when every live array is
        for arr in jax.live_arrays():
            arr.block_until_ready()
        stamps.append(time.perf_counter())

    t0 = time.perf_counter()
    res = fed_train.main(list(argv), on_round=on_round)
    logs = res.rounds
    check(len(logs) == len(stamps) and logs, f"{name}: no rounds retired")
    round_s = np.diff([t0, *stamps])
    routes = ", ".join(f"{op}={route} x{n}" for (op, route), n
                       in sorted(dispatch.route_counts.items()))
    print(f"[{name}] kernel backend: {dispatch.resolve(None)}")
    print(f"[{name}] kernel routes: {routes or 'none'}")
    compiles, compile_s = tracing.compiles()
    print(f"[{name}] backend compiles: {compiles - compiles0} taking "
          f"{compile_s - compile_s0:.6f} s")
    print(f"[{name}] first round incl. set-up and compile: "
          f"{round_s[0]:.6f} s")
    if len(round_s) > 1:
        print(f"[{name}] steady rounds: "
              + " ".join(f"{v:.6f}" for v in round_s[1:])
              + f" s (mean {float(np.mean(round_s[1:])):.6f} s)")
    last = logs[-1]
    print(f"[{name}] per-client ID fraction: "
          + " ".join(f"{v:.4f}" for v in last.client_id_fractions))
    print(f"[{name}] final accuracy {last.mean_acc:.4f}; per client "
          + " ".join(f"{a:.4f}" for a in last.accs))
    print(f"[{name}] peak device memory since start: {peak_bytes(device)}",
          flush=True)

    check("pallas" in {r for (_, r) in dispatch.route_counts},
          f"{name}: no op took the Pallas route")
    for log in logs:
        vals = [log.mean_acc, log.local_loss, log.distill_loss,
                log.id_fraction, *log.accs]
        check(all(math.isfinite(v) for v in vals),
              f"{name}: non-finite round {log.round} log")
        check(0.0 < log.id_fraction < 1.0,
              f"{name}: round {log.round} ID fraction {log.id_fraction} "
              "not strictly inside (0, 1)")
        check(all(0.0 <= v <= 1.0 for v in log.client_id_fractions),
              f"{name}: per-client ID fraction outside [0, 1]")
    if min_acc is not None:
        check(last.mean_acc > min_acc,
              f"{name}: final accuracy {last.mean_acc:.4f} <= {min_acc}")
    return logs


def compare_logs(name: str, base, other, tol) -> list:
    """Mesh run against the unsharded run, round by round. Prints the
    largest gaps and returns what is out of tolerance (empty when none)."""
    import numpy as np

    if len(base) != len(other):
        return [f"{name}: round counts differ"]
    problems = []
    worst = {"acc": 0.0, "loss_rtol": 0.0, "id": 0.0}
    for lb, lo in zip(base, other):
        if lb.bytes_up != lo.bytes_up or lb.bytes_down != lo.bytes_down:
            problems.append(f"{name}: round {lb.round} byte ledgers differ")
        worst["acc"] = max(worst["acc"], float(np.max(np.abs(
            np.subtract(lb.accs, lo.accs)))))
        for f in ("local_loss", "distill_loss"):
            a, b = getattr(lb, f), getattr(lo, f)
            worst["loss_rtol"] = max(worst["loss_rtol"],
                                     abs(a - b) / max(abs(a), 1e-12))
        worst["id"] = max(worst["id"], float(np.max(np.abs(np.subtract(
            lb.client_id_fractions, lo.client_id_fractions)))))
    print(f"[{name}] largest gaps vs unsharded: per-client acc "
          f"{worst['acc']:.6g}, loss (relative) {worst['loss_rtol']:.6g}, "
          f"per-client ID fraction {worst['id']:.6g}; tolerance {tol}",
          flush=True)
    problems += [f"{name}: {key} gap {worst[key]:.6g} > {tol[key]}"
                 for key in worst if worst[key] > tol[key]]
    return problems


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh paths, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    chips = 4 if args.four_chips else 1
    devices = tpu_devices(chips)

    from repro.common.compile_cache import enable_compile_cache
    from repro.kernels import dispatch

    backend = dispatch.resolve(None)
    check(backend == "pallas",
          f"default kernel backend resolves to {backend!r}, not 'pallas'")
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache: {enable_compile_cache()}", flush=True)
    common = ["--method", "edgefd", "--scenario", "strong", "--engine",
              "cohort", "--clients", "10", "--rounds", "3",
              "--seed", str(args.seed)]

    if args.four_chips:
        feat = [*common, "--dataset", "mnist_feat"]
        base = run_phase("mnist_feat", feat, devices[0],
                         min_acc=MNIST_FEAT_MIN_ACC)
        mesh = run_phase("mnist_feat --devices 4", [*feat, "--devices", "4"],
                         devices[0], min_acc=MNIST_FEAT_MIN_ACC)
        # both comparisons print their gaps before either may fail
        problems = compare_logs("1-D client mesh", base, mesh, MESH_1D_TOL)
        lm = [*common, "--dataset", "lm_tokens"]
        base = run_phase("lm_tokens", lm, devices[0])
        mesh = run_phase("lm_tokens --devices 4 --model-shards 2",
                         [*lm, "--devices", "4", "--model-shards", "2"],
                         devices[0])
        problems += compare_logs("2-D (clients, model) mesh", base, mesh,
                                 MESH_2D_TOL)
        check(not problems, "; ".join(problems))
    else:
        print("\n== phase kernels: Mosaic kernels against their references",
              flush=True)
        check_kernels(args.seed)
        run_phase("cifar_like", [*common, "--dataset", "cifar_like",
                                 "--n-train", "50000"], devices[0])
        run_phase("mnist_feat", [*common, "--dataset", "mnist_feat"],
                  devices[0], min_acc=MNIST_FEAT_MIN_ACC)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
