"""Fleet-scale rounds: wave streaming + two-tier aggregation at C=16k.

Cross-device FL surveys put real fleets at 10^4–10^6 clients, far beyond
what a device-resident client stack allows. This benchmark drives the two
scale mechanisms end to end and records that a **C=16384 cohort round
completes on a laptop-class host with peak memory bounded by the wave
size, not the client count**:

  * ``FedConfig.wave_size`` — the cohort engine streams the client axis
    through the device in fixed-size waves (``repro.fed.cohort``);
  * ``FedConfig.num_edge_aggregators`` — E edge aggregators reduce client
    shards locally and the root fuses E partials (``repro.fed.server``);
  * a heavy-traffic row exercises the trace-driven arrival machinery
    (bursty arrivals + churn + mid-round dropout, ``repro.fed.clock``)
    with partial participation and staleness reuse.

Each row runs in a fresh subprocess (clean peak-RSS accounting via
``resource.getrusage`` — Linux reports ru_maxrss in KB) and reports back
on stdout as ``ROW {json}``, the same protocol as
``benchmarks/cohort_scaling.py``'s device sweep.

    PYTHONPATH=src python benchmarks/scale.py             # full, C=16384
    PYTHONPATH=src python benchmarks/scale.py --quick     # CI smoke
    PYTHONPATH=src python benchmarks/scale.py --parse BENCH_scale.json

``--parse`` is the CI regression gate: rows present, a multi-wave row
completed, every peak RSS under the bound, sane times/accuracies.
Results land in ``BENCH_scale.json`` at the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time

import numpy as np

# tiny edge models: the paper's clients are LeNet-lineage; at fleet scale
# the interesting axis is the client count, not per-client FLOPs
SAMPLES_PER_CLIENT = 16
MLP_HIDDEN = (16,)
N_TEST = 256
DEFAULT_RSS_MB = 8192.0

# row configs: name, clients, wave_size, edges, then the traffic knobs.
# The headline rows run full participation — the StalenessBuffer only
# materializes on the subset path, and at C=16k a per-client proxy cache
# would dwarf every other allocation; staleness/hierarchy interplay is
# exercised at C=1024 where the per-edge buffers are small.
FULL_ROWS = [
    dict(name="headline_c16k_w1k", clients=16384, wave=1024, edges=8,
         rounds=1),
    dict(name="c16k_w4k", clients=16384, wave=4096, edges=8, rounds=1),
    dict(name="traffic_c1k", clients=1024, wave=256, edges=4, rounds=2,
         fraction=0.5, decay=0.5, arrival="bursty", spread=60.0,
         churn=0.05, dropout=0.05),
]
QUICK_ROWS = [
    dict(name="quick_c2k_w256", clients=2048, wave=256, edges=4, rounds=1),
    dict(name="quick_traffic_c256", clients=256, wave=64, edges=4, rounds=2,
         fraction=0.5, decay=0.5, arrival="bursty", spread=60.0,
         churn=0.05, dropout=0.05),
]


def run_row(row: dict) -> dict:
    """Run one scale configuration in-process and return its result row."""
    import jax

    from repro.common.types import FedConfig
    from repro.core.methods import get_method
    from repro.core.protocol import run_round
    from repro.fed import simulator

    cfg = FedConfig(
        num_clients=row["clients"], rounds=row["rounds"], method="edgefd",
        scenario="iid", proxy_batch=64, batch_size=16, lr=1e-2, seed=0,
        engine="cohort", wave_size=row["wave"],
        num_edge_aggregators=row["edges"],
        participation_fraction=row.get("fraction", 1.0),
        staleness_decay=row.get("decay", 0.0),
        arrival_process=row.get("arrival", "static"),
        arrival_spread=row.get("spread", 0.0),
        churn_prob=row.get("churn", 0.0),
        dropout_prob=row.get("dropout", 0.0),
    )
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=SAMPLES_PER_CLIENT * cfg.num_clients,
        n_test=N_TEST, mlp_hidden=MLP_HIDDEN)
    t0 = time.perf_counter()
    eng = simulator.build_engine(clients, cfg)
    method = get_method(cfg.method)
    eng.learn_dres(jax.random.PRNGKey(cfg.seed))
    warm_log = run_round(0, eng, server, method, cfg, x_test, y_test)
    warm_s = time.perf_counter() - t0

    times, log = [], warm_log
    for r in range(1, cfg.rounds):
        log = run_round(r, eng, server, method, cfg, x_test, y_test)
        times.append(log.wall_s)
    round_s = float(np.median(times)) if times else warm_log.wall_s
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    # the sweep pins its children to the CPU: every row names its
    # platform, so no CPU row can pass for a chip measurement
    return {
        "name": row["name"], "clients": cfg.num_clients,
        "platform": jax.devices()[0].platform,
        "wave_size": cfg.wave_size,
        "waves": -(-cfg.num_clients // max(cfg.wave_size, 1)),
        "edges": cfg.num_edge_aggregators,
        "fraction": cfg.participation_fraction,
        "staleness_decay": cfg.staleness_decay,
        "arrival": cfg.arrival_process,
        "warmup_s": warm_s, "round_s": round_s,
        "phase_s": {k: float(v) for k, v in log.phase_s.items()},
        "peak_rss_mb": peak_mb,
        "bytes_up": int(server.bytes_received),
        "bytes_down": int(server.bytes_broadcast),
        "mean_staleness": log.mean_staleness,
        "final_acc": log.mean_acc,
    }


def sweep(rows) -> list:
    """One subprocess per row: peak RSS is per-config, not cumulative."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = []
    print(f"{'row':>22} {'C':>6} {'wave':>5} {'E':>3} {'warm_s':>7} "
          f"{'round_s':>8} {'rss_mb':>8} {'acc':>6}")
    for row in rows:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            [root, os.path.join(root, "src"), env.get("PYTHONPATH", "")])
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--_child", json.dumps(row)],
            env=env, capture_output=True, text=True, timeout=3000)
        if res.returncode != 0:
            raise RuntimeError(f"scale child {row['name']} failed:\n"
                               f"{res.stdout}\n{res.stderr}")
        r = next(json.loads(line[4:]) for line in res.stdout.splitlines()
                 if line.startswith("ROW "))
        out.append(r)
        print(f"{r['name']:>22} {r['clients']:>6} {r['wave_size']:>5} "
              f"{r['edges']:>3} {r['warmup_s']:7.1f} {r['round_s']:8.2f} "
              f"{r['peak_rss_mb']:8.0f} {r['final_acc']:6.3f}")
    return out


def parse_check(path: str, rss_bound_mb: float) -> None:
    """CI regression gate: a crash-shaped result file exits non-zero."""
    with open(path) as f:
        data = json.load(f)
    rows = data["rows"] if isinstance(data, dict) else data
    if not rows:
        raise SystemExit(f"{path}: no benchmark rows")
    if not any(r.get("waves", 0) >= 2 for r in rows):
        raise SystemExit(f"{path}: no multi-wave row completed — wave "
                         "streaming was not exercised")
    for r in rows:
        if not r.get("round_s", 0) > 0:
            raise SystemExit(f"{path}: non-positive round_s in row {r}")
        if not 0.0 <= r.get("final_acc", -1.0) <= 1.0:
            raise SystemExit(f"{path}: final_acc out of [0, 1] in row {r}")
        if r.get("peak_rss_mb", float("inf")) > rss_bound_mb:
            raise SystemExit(
                f"{path}: peak RSS {r['peak_rss_mb']:.0f} MB exceeds the "
                f"{rss_bound_mb:.0f} MB bound in row {r['name']} — wave "
                "streaming is no longer bounding memory")
    biggest = max(r["clients"] for r in rows)
    print(f"{path}: {len(rows)} rows OK (max C={biggest}, all peak RSS <= "
          f"{rss_bound_mb:.0f} MB)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small CI-sized rows instead of the C=16384 run")
    ap.add_argument("--out", default=None,
                    help="output path (default <repo>/BENCH_scale.json)")
    ap.add_argument("--parse", default=None, metavar="FILE",
                    help="validate a previously written result file and "
                         "exit non-zero on regression (CI gate)")
    ap.add_argument("--rss-bound-mb", type=float, default=DEFAULT_RSS_MB,
                    help="--parse only: per-row peak RSS bound")
    ap.add_argument("--_child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.parse:
        parse_check(args.parse, args.rss_bound_mb)
        return []

    if args._child:
        row = run_row(json.loads(args._child))
        print("ROW " + json.dumps(row))
        return [row]

    rows = sweep(QUICK_ROWS if args.quick else FULL_ROWS)
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_scale.json")
    with open(out, "w") as f:
        json.dump({"benchmark": "scale",
                   "quick": bool(args.quick),
                   "host_cpu_count": os.cpu_count(),
                   "note": "wave streaming bounds peak device memory by "
                           "wave_size (not C); two-tier edge aggregation "
                           "bounds root work by num_edge_aggregators. "
                           "peak_rss_mb is per-subprocess ru_maxrss.",
                   "rows": rows}, f, indent=2)
    print(f"saved {out}")
    return rows


if __name__ == "__main__":
    main()
