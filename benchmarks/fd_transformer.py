"""2-D federated mesh benchmark: round wall-clock and peak per-device
state bytes vs ``model_shards`` for a transformer cohort.

The tentpole question ISSUE 10 asks this benchmark to answer: does
folding the cohort's device mesh from 1-D ``(clients,)`` into 2-D
``(clients, model)`` actually shrink the per-device resident state —
stacked params + Adam state of a reduced-granite ``lm_tokens`` cohort —
~linearly with the model-shard count?

Sweep: ``model_shards ∈ {0, 2, 4}``, every row in a fresh subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (jax fixes
the device count at first init, so one process cannot sweep it). The
client axis is held at ONE device row (``num_devices = max(1,
model_shards)``) so the only thing changing between rows is how many
ways each client's weight matrices shard over the model axis:

    model_shards=0  ->  1-device 1-D mesh   (the unsharded baseline)
    model_shards=2  ->  (1, 2) mesh         (heads/ff/vocab split 2-way)
    model_shards=4  ->  (1, 4) mesh

Peak bytes are measured from the arrays themselves — max over device ids
of the summed ``addressable_shards`` sizes across every params/opt-state
leaf of every cohort — so replication (norm scales, biases) is counted
honestly: the shrink is ~linear on the shardable majority, not on the
small replicated residue.

    PYTHONPATH=src:. python benchmarks/fd_transformer.py --quick
    PYTHONPATH=src:. python benchmarks/fd_transformer.py --parse BENCH_fdx.json

``--parse FILE`` is CI's regression gate: rows for all three shard
counts, sane times, and peak bytes strictly decreasing with >= 1.3x
per shard doubling (honest about the replicated residue), else exit
non-zero. Results land at the repo root as ``BENCH_fdx.json``.

On CPU the timing rows validate the wiring (a forced-host-device CPU
mesh adds collective overhead, not speed); the bytes rows are the
deployment-relevant artifact — they are exact on any backend.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO_ROOT, "BENCH_fdx.json")
FORCED_DEVICES = 4
SHARD_SWEEP = (0, 2, 4)
CLIENTS = 4
SAMPLES_PER_CLIENT = 96
# gate: each shard doubling must shed at least this factor of peak bytes
# (2.0 would ignore the replicated residue — norms, biases, embeddings'
# unshardable mates — which is real and stays resident on every device)
MIN_SHRINK_PER_DOUBLING = 1.3


def peak_state_bytes_per_device(engine) -> int:
    """Max over devices of resident params + opt-state bytes, summed from
    each leaf's ``addressable_shards`` (replicated leaves count once per
    device, sharded leaves once per shard — the honest HBM number)."""
    import jax
    per_dev: dict = {}
    for cohort in engine.cohorts:
        for tree in (cohort.params, cohort.opt_state):
            for leaf in jax.tree.leaves(tree):
                for sh in getattr(leaf, "addressable_shards", ()):
                    d = sh.device.id
                    per_dev[d] = per_dev.get(d, 0) + sh.data.nbytes
    return int(max(per_dev.values())) if per_dev else 0


def bench_shards(model_shards: int, rounds: int, seed: int = 0) -> dict:
    """One sweep row: a transformer cohort (lm_tokens -> reduced granite
    backbones, flash-attention on the distill hot path) through warmup +
    timed rounds at the given model-shard count."""
    from repro.common.types import FedConfig
    from repro.core.methods import get_method
    from repro.core.protocol import run_round
    from repro.fed import simulator

    rounds = max(rounds, 1)
    # client axis held at ONE device row: shard count is the only variable
    num_devices = max(1, model_shards)
    cfg = FedConfig(num_clients=CLIENTS, rounds=rounds, method="edgefd",
                    proxy_batch=64, batch_size=16, lr=1e-2, seed=seed,
                    engine="cohort", num_devices=num_devices,
                    model_shards=model_shards)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "lm_tokens", n_train=SAMPLES_PER_CLIENT * CLIENTS, n_test=256)
    eng = simulator.build_engine(clients, cfg)
    method = get_method(cfg.method)

    import jax
    t0 = time.perf_counter()
    eng.learn_dres(jax.random.PRNGKey(cfg.seed))
    run_round(0, eng, server, method, cfg, x_test, y_test)
    warm_s = time.perf_counter() - t0
    peak = peak_state_bytes_per_device(eng)

    times = []
    for r in range(1, rounds + 1):
        log = run_round(r, eng, server, method, cfg, x_test, y_test)
        times.append(log.wall_s)
    # the sweep pins its children to the CPU: every row names its
    # platform, so no CPU row can pass for a chip measurement
    return {"model_shards": model_shards, "num_devices": num_devices,
            "platform": jax.devices()[0].platform,
            "mesh": "(1,)" if model_shards == 0 else f"(1, {model_shards})",
            "clients": CLIENTS, "warmup_s": warm_s,
            "round_s": float(np.median(times)),
            "peak_state_bytes_per_device": peak,
            "final_acc": log.mean_acc}


def shard_sweep(rounds: int) -> list:
    """One fresh subprocess per shard count, each with the same forced
    host-device topology (the cohort_scaling.device_sweep idiom)."""
    rows = []
    print(f"{'shards':>7} {'mesh':>7} {'warmup_s':>9} {'round_s':>9} "
          f"{'peak_MB/dev':>12} {'shrink':>7}")
    base_peak = None
    for ms in SHARD_SWEEP:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={FORCED_DEVICES}")
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            [REPO_ROOT, os.path.join(REPO_ROOT, "src"),
             env.get("PYTHONPATH", "")])
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--_forced-shards", str(ms), "--rounds", str(rounds)],
            env=env, capture_output=True, text=True,
            timeout=900)  # a wedged child names its shard count loudly
        if res.returncode != 0:
            raise RuntimeError(
                f"shard sweep child (model_shards={ms}) failed:\n"
                f"{res.stdout}\n{res.stderr}")
        row = next(json.loads(line[4:])
                   for line in res.stdout.splitlines()
                   if line.startswith("ROW "))
        rows.append(row)
        peak = row["peak_state_bytes_per_device"]
        base_peak = base_peak if base_peak is not None else peak
        print(f"{ms:>7} {row['mesh']:>7} {row['warmup_s']:9.2f} "
              f"{row['round_s']:9.3f} {peak/1e6:12.3f} "
              f"{base_peak/peak:6.2f}x")
    return rows


def parse_check(path: str) -> None:
    """Regression gate: all three shard counts present, sane timings, and
    peak per-device bytes shrinking >= MIN_SHRINK_PER_DOUBLING per shard
    doubling. Exits non-zero with a reason on any failure."""
    with open(path) as f:
        data = json.load(f)
    rows = data["rows"] if isinstance(data, dict) else data
    by_ms = {r.get("model_shards"): r for r in rows}
    if set(by_ms) != set(SHARD_SWEEP):
        raise SystemExit(
            f"{path}: expected model_shards rows {sorted(SHARD_SWEEP)}, "
            f"got {sorted(by_ms)}")
    for r in rows:
        if not (r.get("round_s", 0) > 0 and r.get("warmup_s", 0) > 0):
            raise SystemExit(f"{path}: non-positive timing in row {r}")
        if not 0.0 <= r.get("final_acc", -1.0) <= 1.0:
            raise SystemExit(f"{path}: final_acc out of [0, 1] in {r}")
        if r.get("peak_state_bytes_per_device", 0) <= 0:
            raise SystemExit(f"{path}: missing peak bytes in row {r}")
    peaks = [by_ms[ms]["peak_state_bytes_per_device"] for ms in SHARD_SWEEP]
    for (ms_a, a), (ms_b, b) in zip(zip(SHARD_SWEEP, peaks),
                                    zip(SHARD_SWEEP[1:], peaks[1:])):
        if b >= a:
            raise SystemExit(
                f"{path}: peak bytes/device did not shrink "
                f"(shards {ms_a}: {a} -> shards {ms_b}: {b})")
        if a / b < MIN_SHRINK_PER_DOUBLING:
            raise SystemExit(
                f"{path}: shard doubling {ms_a}->{ms_b} shed only "
                f"{a/b:.2f}x peak bytes (< {MIN_SHRINK_PER_DOUBLING}x)")
    print(f"{path}: {len(rows)} rows OK "
          f"(peak MB/dev {peaks[0]/1e6:.3f} -> {peaks[-1]/1e6:.3f}, "
          f"{peaks[0]/peaks[-1]:.2f}x at {SHARD_SWEEP[-1]} shards)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="1 timed round per row (CI bench-smoke scale)")
    ap.add_argument("--rounds", type=int, default=None,
                    help="timed rounds per row (after 1 warmup round); "
                         "default 1 with --quick else 3")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="output JSON path (default: repo-root "
                         "BENCH_fdx.json, like the other BENCH_* files)")
    ap.add_argument("--parse", default=None, metavar="FILE",
                    help="validate a previously written result file and "
                         "exit (CI regression gate)")
    ap.add_argument("--_forced-shards", type=int, default=None,
                    dest="forced_shards", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.parse:
        parse_check(args.parse)
        return []

    rounds = args.rounds if args.rounds is not None \
        else (1 if args.quick else 3)

    if args.forced_shards is not None:
        # sweep child: the forced host-device count is already in XLA_FLAGS
        row = bench_shards(args.forced_shards, rounds)
        print("ROW " + json.dumps(row))
        return [row]

    rows = shard_sweep(rounds)
    with open(args.out, "w") as f:
        json.dump({"benchmark": "fd_transformer_shard_sweep",
                   "forced_host_devices": FORCED_DEVICES,
                   "host_cpu_count": os.cpu_count(),
                   "note": "client axis held at 1 device row; peak bytes "
                           "= max over devices of summed addressable "
                           "shards across stacked params + Adam state "
                           "(replicated residue counted); CPU timings "
                           "validate wiring, bytes are exact",
                   "rows": rows}, f, indent=2)
    print(f"saved {args.out}")
    return rows


if __name__ == "__main__":
    main()
