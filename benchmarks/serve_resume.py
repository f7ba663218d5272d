"""Checkpoint overhead of the resumable federated service.

The service driver (``repro.launch.fed_serve``) snapshots the full
experiment — scheduler window + in-flight rounds, server buffers/pending
reports, engine params/opt-state, rng streams — every N rounds through
``repro.checkpoint.save_state`` (atomic write + fsync). This benchmark
measures what that durability costs:

  * per-round wall overhead of ``snapshot() + save_state`` (seconds and
    as a fraction of the round's compute);
  * checkpoint size on disk;
  * one restore (``restore_state + RoundScheduler.restore``) latency;
  * and it verifies the resumed run's remaining rounds are bit-for-bit
    identical to the uninterrupted ones (the service's headline
    guarantee — a benchmark that measured a broken checkpoint would be
    noise).

    PYTHONPATH=src:. python benchmarks/serve_resume.py            # C=32
    PYTHONPATH=src:. python benchmarks/serve_resume.py --quick    # CI

Writes ``BENCH_serve.json`` at the repo root per the BENCH convention;
``--parse FILE`` re-validates a result file and exits non-zero on
regression — CI's bench-smoke job runs the quick benchmark then this
gate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time

SAMPLES_PER_CLIENT = 64
MLP_HIDDEN = (64,)
# host-measured fields can never match across runs; everything else must
OVERHEAD_FRAC_MAX = 0.5  # ckpt time vs round compute, quick-scale gate
MEASURED_FIELDS = ("wall_s", "phase_s", "counters.compiles")

FIXED_COSTS = {"local_train": 1.0, "report": 0.1, "aggregate": 0.3,
               "distill": 1.0, "eval": 0.0}


def _build(cfg):
    import jax

    from repro.core.methods import get_method
    from repro.fed import simulator
    from repro.fed.scheduler import RoundScheduler
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=SAMPLES_PER_CLIENT * cfg.num_clients,
        n_test=512, mlp_hidden=MLP_HIDDEN)
    eng = simulator.build_engine(clients, cfg)
    eng.learn_dres(jax.random.PRNGKey(cfg.seed))
    return RoundScheduler(eng, server, get_method(cfg.method), cfg,
                          x_test, y_test, sim_phase_costs=FIXED_COSTS)


def _strip(logs):
    out = []
    for lg in logs:
        d = {k: v for k, v in dataclasses.asdict(lg).items()
             if k not in MEASURED_FIELDS}
        for name in MEASURED_FIELDS:   # "a.b": key b of dict field a
            field, _, key = name.partition(".")
            if key and field in d:
                d[field] = {k: v for k, v in d[field].items() if k != key}
        out.append(d)
    return out


def bench(*, clients: int, rounds: int, engine: str = "loop",
          seed: int = 0) -> dict:
    from repro.checkpoint import restore_state, save_state
    from repro.common.types import FedConfig
    cfg = FedConfig(num_clients=clients, rounds=rounds, method="edgefd",
                    scenario="iid", proxy_batch=256, batch_size=32,
                    lr=1e-2, seed=seed, engine=engine,
                    participation_fraction=0.5, staleness_decay=0.5,
                    round_mode="overlap", max_inflight=2)

    # uninterrupted run, no checkpointing: the compute baseline
    sched = _build(cfg)
    t0 = time.perf_counter()
    ref_logs = sched.run_rounds(0, rounds)
    compute_s = time.perf_counter() - t0

    # checkpointed service loop: snapshot + atomic save every round.
    # Alongside the full snapshot, also save the fed_serve production form
    # (``logs_tail=0`` — retired logs stream to the sidecar instead of the
    # checkpoint) to show its bytes stay flat as the service ages.
    with tempfile.TemporaryDirectory() as ckdir, \
            tempfile.TemporaryDirectory() as flatdir:
        sched2 = _build(cfg)
        sched2.begin(0, rounds)
        ckpt_s, ckpt_bytes, n_ckpts = 0.0, 0, 0
        flat_bytes = []
        mid_step = None
        while sched2.has_pending():
            _, _, log = sched2.step()
            if log is not None:
                t0 = time.perf_counter()
                path = save_state(ckdir, len(sched2.logs),
                                  sched2.snapshot().to_tree(), keep_last=3)
                ckpt_s += time.perf_counter() - t0
                ckpt_bytes = os.path.getsize(path)
                n_ckpts += 1
                flat_bytes.append(os.path.getsize(save_state(
                    flatdir, len(sched2.logs),
                    sched2.snapshot(logs_tail=0).to_tree(), keep_last=3)))
                if len(sched2.logs) == max(1, rounds // 2):
                    mid_step = len(sched2.logs)

        # one restore from mid-run, then drain: correctness + latency
        t0 = time.perf_counter()
        tree = restore_state(ckdir, mid_step)
        sched3 = _build(cfg)
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        sched3.restore(tree)
        restore_s = time.perf_counter() - t0
        sched3.drain()
        resume_bitexact = _strip(sched3.logs) == _strip(ref_logs)

    per_round = ckpt_s / max(n_ckpts, 1)
    return {"engine": engine, "clients": clients, "rounds": rounds,
            "compute_s": compute_s,
            "ckpt_total_s": ckpt_s,
            "ckpt_per_round_s": per_round,
            "ckpt_overhead_frac": ckpt_s / compute_s if compute_s else 0.0,
            "ckpt_bytes": ckpt_bytes,
            "ckpt_bytes_flat_first": flat_bytes[0],
            "ckpt_bytes_flat_last": flat_bytes[-1],
            "n_checkpoints": n_ckpts,
            "rebuild_s": build_s,
            "restore_s": restore_s,
            "resume_bitexact": resume_bitexact,
            "final_acc": ref_logs[-1].mean_acc}


def run_and_save(quick: bool = False, out: str | None = None,
                 clients: int | None = None,
                 rounds: int | None = None) -> list:
    clients = clients or (8 if quick else 32)
    rounds = rounds or (4 if quick else 8)
    row = bench(clients=clients, rounds=rounds)
    print(f"C={clients} rounds={rounds}: compute={row['compute_s']:.2f}s "
          f"ckpt={row['ckpt_per_round_s']*1e3:.1f}ms/round "
          f"({100*row['ckpt_overhead_frac']:.1f}% of compute, "
          f"{row['ckpt_bytes']/1e6:.2f}MB) "
          f"restore={row['restore_s']*1e3:.1f}ms "
          f"bitexact={row['resume_bitexact']}")
    out = out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_serve.json")
    with open(out, "w") as f:
        json.dump({"benchmark": "serve_resume_overhead",
                   "host_cpu_count": os.cpu_count(),
                   "overhead_frac_max": OVERHEAD_FRAC_MAX,
                   "note": "per-round cost of snapshot()+save_state "
                           "(atomic npz + fsync) in the fed_serve event "
                           "loop, plus one mid-run restore; "
                           "resume_bitexact asserts the restored run's "
                           "logs match the uninterrupted ones",
                   "rows": [row]}, f, indent=2)
    print(f"saved {out}")
    return [row]


def parse_check(path: str) -> None:
    """Regression gate: checkpoint round-trip intact and overhead sane."""
    with open(path) as f:
        data = json.load(f)
    rows = data.get("rows", [])
    if len(rows) != 1:
        raise SystemExit(f"{path}: expected exactly one row, got "
                         f"{len(rows)}")
    r = rows[0]
    if not r.get("resume_bitexact"):
        raise SystemExit(
            f"{path}: resumed run diverged from the uninterrupted one — "
            "the checkpoint round-trip is broken")
    if not (r["n_checkpoints"] == r["rounds"] and r["ckpt_bytes"] > 0):
        raise SystemExit(f"{path}: checkpointing did not run every round "
                         f"({r['n_checkpoints']}/{r['rounds']}, "
                         f"{r['ckpt_bytes']}B)")
    if not (r["compute_s"] > 0 and r["ckpt_per_round_s"] > 0
            and r["restore_s"] > 0):
        raise SystemExit(f"{path}: non-positive timing in {r}")
    first = r.get("ckpt_bytes_flat_first")
    last = r.get("ckpt_bytes_flat_last")
    # one-sided: in-flight overlap state makes individual snapshots vary
    # (and often shrink as rounds drain), but retired history must never
    # accumulate in the checkpoint
    if first is not None and last - first > 1024:
        raise SystemExit(
            f"{path}: logs_tail=0 checkpoint grew {first}B -> {last}B over "
            f"{r['rounds']} rounds — retired-log streaming is not keeping "
            "checkpoint size flat")
    frac_max = data.get("overhead_frac_max", OVERHEAD_FRAC_MAX)
    if r["ckpt_overhead_frac"] > frac_max:
        raise SystemExit(
            f"{path}: checkpointing costs {100*r['ckpt_overhead_frac']:.1f}%"
            f" of round compute (gate {100*frac_max:.0f}%)")
    print(f"{path}: OK — {r['ckpt_per_round_s']*1e3:.1f}ms/round "
          f"({100*r['ckpt_overhead_frac']:.1f}% of compute), "
          f"restore {r['restore_s']*1e3:.1f}ms, bit-exact resume")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI scale: C=8, 4 rounds (default C=32, 8)")
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="output path (default <repo>/BENCH_serve.json)")
    ap.add_argument("--parse", default=None, metavar="FILE",
                    help="validate a previously written result file and "
                         "exit (CI regression gate)")
    args = ap.parse_args(argv)
    if args.parse:
        parse_check(args.parse)
        return []
    return run_and_save(quick=args.quick, out=args.out,
                        clients=args.clients, rounds=args.rounds)


if __name__ == "__main__":
    main()
