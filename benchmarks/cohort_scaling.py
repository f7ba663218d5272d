"""Round wall-clock vs client count: loop engine vs cohort engine.

The loop engine pays a Python dispatch + host↔device transfer per client per
step (and a per-client jit compile at warmup); the cohort engine runs each
round phase as one vmapped call. This benchmark measures one federated round
(local train + proxy logits + filter + distill + eval) at C ∈ {8, 32, 128,
512} homogeneous MLP clients and reports the speedup.

    PYTHONPATH=src python benchmarks/cohort_scaling.py
    PYTHONPATH=src python benchmarks/cohort_scaling.py --clients 8 32 --rounds 2

Acceptance gate (ISSUE 1): cohort ≥ 5× lower per-round wall-clock at C=128.

Device-count sweep (ISSUE 2): ``--devices 1 2 4`` re-runs the cohort engine
at fixed C with the client axis mesh-sharded over N emulated host devices
(each count in a fresh subprocess — jax fixes the device count at init — via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and records the
sweep to ``BENCH_cohort_mesh.json`` at the repo root:

    PYTHONPATH=src python benchmarks/cohort_scaling.py --devices 1 2 4

Wall-clock decreases while the device count stays within the host's
physical cores; oversubscribed counts plateau.

Participation sweep (ISSUE 3): ``--fractions 0.25 0.5 1.0`` re-runs both
engines at fixed C with ``participation_fraction`` swept, recording the
result to ``BENCH_participation.json`` at the repo root. The loop engine's
per-round wall-clock drops roughly linearly with the fraction (it skips
sampled-out clients outright); the cohort engine's compiled phases stay
cached across fractions and rounds (sampled-out clients are ``_where_tree``
no-op lanes — same shapes, zero retraces — so its already-small round time
stays flat while per-round upload bytes shrink with the fraction):

    PYTHONPATH=src python benchmarks/cohort_scaling.py --fractions 0.25 0.5 1.0

``--parse FILE`` validates a previously written result file (rows present,
both engines, sane times/accuracies) and exits non-zero on regression —
CI's bench-smoke job runs the tiny benchmark and then this gate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import save_json
from repro.common.types import FedConfig
from repro.core.methods import get_method
from repro.core.protocol import run_round
from repro.fed import simulator

SAMPLES_PER_CLIENT = 64
# Table-I-scale edge models: the paper's clients are tiny (LeNet lineage);
# a small MLP keeps the benchmark in the dispatch-bound regime the cohort
# engine targets rather than saturating this host's matmul throughput.
MLP_HIDDEN = (64,)


def bench_engine(engine: str, num_clients: int, rounds: int,
                 seed: int = 0, num_devices: int = 0,
                 fraction: float = 1.0) -> dict:
    rounds = max(rounds, 1)  # at least one timed round after the warmup
    cfg = FedConfig(num_clients=num_clients, rounds=rounds, method="edgefd",
                    scenario="iid", proxy_batch=256, batch_size=32,
                    lr=1e-2, seed=seed, engine=engine,
                    num_devices=num_devices,
                    participation_fraction=fraction)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=SAMPLES_PER_CLIENT * num_clients,
        n_test=512, mlp_hidden=MLP_HIDDEN)
    eng = simulator.build_engine(clients, cfg)
    method = get_method(cfg.method)

    t0 = time.perf_counter()
    import jax
    eng.learn_dres(jax.random.PRNGKey(cfg.seed))
    # warm up at full participation so *every* client's steps compile now:
    # otherwise a swept fraction < 1 pays first-touch compiles for late
    # sampled clients inside the timed rounds (loop engine jits per client)
    warm_cfg = dataclasses.replace(cfg, participation_fraction=1.0)
    run_round(0, eng, server, method, warm_cfg, x_test, y_test)
    warm_s = time.perf_counter() - t0

    times = []
    logs = []
    up0 = server.bytes_received
    for r in range(1, rounds + 1):
        log = run_round(r, eng, server, method, cfg, x_test, y_test)
        times.append(log.wall_s)
        logs.append(log)
    # per-phase wall-clock breakdown (median across timed rounds) — the
    # scheduler produces it for free; it shows where each engine's round
    # time actually goes (RoundLog.phase_s)
    phase_keys = sorted(set().union(*(log.phase_s for log in logs)))
    phase_s = {k: float(np.median([log.phase_s.get(k, 0.0) for log in logs]))
               for k in phase_keys}
    # the sweep pins its children to the CPU: every row names its
    # platform, so no CPU row can pass for a chip measurement
    return {"engine": engine, "clients": num_clients,
            "platform": jax.devices()[0].platform,
            "devices": num_devices, "fraction": fraction,
            "warmup_s": warm_s, "round_s": float(np.median(times)),
            "phase_s": phase_s,
            "bytes_up_per_round": (server.bytes_received - up0) // rounds,
            "final_acc": log.mean_acc}


def device_sweep(devices, clients, rounds: int) -> list:
    """Re-run the mesh-sharded cohort engine once per (C, device count).

    Each device count runs in a fresh subprocess with
    ``--xla_force_host_platform_device_count`` set before jax init (the
    count is frozen at init, so one process cannot sweep it)."""
    bad = [d for d in devices if d < 1]
    if bad:
        raise SystemExit(
            f"--devices entries must be >= 1 (got {bad}); the sweep forces "
            "that many host devices per subprocess — devices=1 IS the "
            "unsharded-comparable baseline (a 1-device mesh)")
    rows = []
    print(f"{'C':>5} {'devices':>8} {'warmup_s':>9} {'round_s':>9} "
          f"{'speedup':>8}")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for c in clients:
        base_s = None
        for d in devices:
            env = dict(os.environ)
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={d}")
            env.setdefault("JAX_PLATFORMS", "cpu")
            env["PYTHONPATH"] = os.pathsep.join(
                [root, os.path.join(root, "src"), env.get("PYTHONPATH", "")])
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--_forced-devices", str(d), "--clients", str(c),
                 "--rounds", str(rounds)],
                env=env, capture_output=True, text=True,
                timeout=900)  # a wedged child names its (C, d) cell loudly
            if res.returncode != 0:
                raise RuntimeError(
                    f"device sweep child (C={c}, devices={d}) failed:\n"
                    f"{res.stdout}\n{res.stderr}")
            row = next(json.loads(line[4:])
                       for line in res.stdout.splitlines()
                       if line.startswith("ROW "))
            rows.append(row)
            base_s = base_s if base_s is not None else row["round_s"]
            speed = f"{base_s / row['round_s']:7.2f}x" if base_s else ""
            print(f"{c:>5} {d:>8} {row['warmup_s']:9.2f} "
                  f"{row['round_s']:9.3f} {speed:>8}")
    return rows


def participation_sweep(fractions, clients, rounds: int) -> list:
    """Both engines at fixed C, participation_fraction swept in-process
    (the fraction changes data, never shapes — the cohort engine's jitted
    phases compile once at the first fraction and stay cached)."""
    rows = []
    print(f"{'C':>5} {'engine':>7} {'fraction':>9} {'warmup_s':>9} "
          f"{'round_s':>9} {'MB_up/rd':>9}")
    for c in clients:
        for engine in ("loop", "cohort"):
            for f in fractions:
                row = bench_engine(engine, c, rounds, fraction=f)
                rows.append(row)
                print(f"{c:>5} {engine:>7} {f:>9.2f} {row['warmup_s']:9.2f} "
                      f"{row['round_s']:9.3f} "
                      f"{row['bytes_up_per_round'] / 1e6:9.2f}")
    return rows


def parse_check(path: str) -> None:
    """Regression gate over a result file written by any mode of this
    benchmark: crash-shaped output (no rows, missing engines, nonsense
    times or accuracies) exits non-zero with a reason."""
    with open(path) as f:
        data = json.load(f)
    rows = data["rows"] if isinstance(data, dict) else data
    if not rows:
        raise SystemExit(f"{path}: no benchmark rows")
    engines = {r.get("engine") for r in rows}
    if "cohort" not in engines:
        raise SystemExit(f"{path}: cohort engine missing (got {engines})")
    for r in rows:
        if not (r.get("round_s", 0) > 0 and r.get("warmup_s", 0) > 0):
            raise SystemExit(f"{path}: non-positive timing in row {r}")
        acc = r.get("final_acc", 0.0)
        if not 0.0 <= acc <= 1.0:
            raise SystemExit(f"{path}: final_acc {acc} out of [0, 1] in {r}")
    print(f"{path}: {len(rows)} rows OK (engines: {sorted(engines)})")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, nargs="+", default=None)
    ap.add_argument("--rounds", type=int, default=1,
                    help="timed rounds per configuration (after 1 warmup)")
    ap.add_argument("--skip-loop-above", type=int, default=10_000,
                    help="skip the loop engine beyond this client count "
                         "(it is the slow thing being measured)")
    ap.add_argument("--devices", type=int, nargs="+", default=None,
                    help="mesh-device sweep mode: cohort engine at fixed C "
                         "(default 128), one emulated-host-device count per "
                         "subprocess; writes BENCH_cohort_mesh.json")
    ap.add_argument("--fractions", type=float, nargs="+", default=None,
                    help="participation sweep mode: both engines at fixed C "
                         "(default 128), participation_fraction swept; "
                         "writes BENCH_participation.json")
    ap.add_argument("--out", default=None,
                    help="output path override (default: results dir, or "
                         "<repo>/BENCH_*.json for the sweep modes)")
    ap.add_argument("--parse", default=None, metavar="FILE",
                    help="validate a previously written result file and "
                         "exit (CI regression gate)")
    ap.add_argument("--_forced-devices", type=int, default=0,
                    dest="forced_devices", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.parse:
        parse_check(args.parse)
        return []

    if args.forced_devices:
        # device-sweep child: this process was launched with the forced
        # host-device count already in XLA_FLAGS
        clients = (args.clients or [128])[0]
        row = bench_engine("cohort", clients, max(args.rounds, 3),
                           num_devices=args.forced_devices)
        print("ROW " + json.dumps(row))
        return [row]

    if args.devices is not None:
        clients = args.clients or [128]
        rows = device_sweep(args.devices, clients, max(args.rounds, 3))
        out = args.out or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_cohort_mesh.json")
        with open(out, "w") as f:
            json.dump({"benchmark": "cohort_mesh_device_sweep",
                       "clients": clients,
                       "host_cpu_count": os.cpu_count(),
                       "note": "emulated host devices via XLA_FLAGS="
                               "--xla_force_host_platform_device_count; "
                               "wall-clock decreases while devices <= "
                               "physical cores",
                       "rows": rows}, f, indent=2)
        print(f"saved {out}")
        return rows

    if args.fractions is not None:
        clients = args.clients or [128]
        rows = participation_sweep(args.fractions, clients,
                                   max(args.rounds, 3))
        out = args.out or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_participation.json")
        with open(out, "w") as f:
            json.dump({"benchmark": "participation_fraction_sweep",
                       "clients": clients,
                       "host_cpu_count": os.cpu_count(),
                       "note": "loop round time scales with the sampled "
                               "fraction (skipped clients cost nothing); "
                               "cohort phases stay compiled across "
                               "fractions (no-op lanes), so its round "
                               "time is flat while upload bytes shrink",
                       "rows": rows}, f, indent=2)
        print(f"saved {out}")
        return rows

    args.clients = args.clients or [8, 32, 128, 512]
    rows = []
    print(f"{'C':>5} {'engine':>7} {'warmup_s':>9} {'round_s':>9} {'speedup':>8}")
    for c in args.clients:
        loop_s = None
        for engine in ("loop", "cohort"):
            if engine == "loop" and c > args.skip_loop_above:
                print(f"{c:>5} {engine:>7} {'skipped':>9}")
                continue
            row = bench_engine(engine, c, args.rounds)
            rows.append(row)
            if engine == "loop":
                loop_s = row["round_s"]
                speed = ""
            else:
                speed = (f"{loop_s / row['round_s']:7.1f}x"
                         if loop_s else "")
            print(f"{c:>5} {engine:>7} {row['warmup_s']:9.2f} "
                  f"{row['round_s']:9.3f} {speed:>8}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"benchmark": "cohort_scaling", "rows": rows}, f,
                      indent=2)
        path = args.out
    else:
        path = save_json("cohort_scaling.json", rows)
    print(f"saved {path}")
    return rows


if __name__ == "__main__":
    main()
