"""Federated-distillation driver — the paper's main experiment entry point.

``python -m repro.launch.fed_train --method edgefd --scenario strong \
      --dataset mnist_feat --rounds 10``

The argparse → ``FedConfig`` mapping lives in ``add_config_args`` /
``config_from_args`` so other drivers (``fed_serve``, the resumable
service) expose the identical experiment surface.
"""
from __future__ import annotations

import argparse
import json

from repro.common.compile_cache import enable_compile_cache
from repro.common.types import FedConfig
from repro.core.methods import METHODS
from repro.fed import simulator

# short labels for the per-phase wall-clock breakdown (RoundLog.phase_s)
PHASE_ABBREV = {"local_train": "lt", "report": "rep", "aggregate": "agg",
                "server_distill": "sdist", "distill": "dist", "eval": "ev"}


def add_config_args(ap: argparse.ArgumentParser) -> None:
    """Install every experiment-defining flag (the ``FedConfig`` surface).

    Shared by ``fed_train`` and ``fed_serve`` so a service resumes the
    exact experiment a batch run would execute."""
    ap.add_argument("--method", default="edgefd", choices=sorted(METHODS))
    ap.add_argument("--scenario", default="strong",
                    choices=["strong", "weak", "iid"])
    ap.add_argument("--dataset", default="mnist_feat",
                    help="synthetic dataset (repro.data.synthetic.SPECS): "
                         "*_feat = flat features (MLP zoo), *_like = images "
                         "(CNN zoo), lm_tokens = int32 token sequences — "
                         "each client is a reduced granite transformer "
                         "(core/fd_trainer.py) distilling last-position "
                         "next-token logits, with flash-attention on the "
                         "hot path via --kernel-backend")
    ap.add_argument("--engine", default="loop", choices=["loop", "cohort"],
                    help="loop = per-client python loop; cohort = vmapped "
                         "homogeneous cohorts (fed/cohort.py)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard the cohort client axis over a 1-D device "
                         "mesh: 0 = unsharded, -1 = all jax devices, N = "
                         "exactly N (CPU hosts: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N). "
                         "Requires --engine cohort")
    ap.add_argument("--model-shards", type=int, default=0,
                    help="fold the --devices mesh into a 2-D "
                         "(clients, model) mesh: each stacked client's "
                         "weight matrices additionally shard M-way over "
                         "the model axis (heads/ff/vocab dims — "
                         "repro.fed.mesh), so cohort members bigger than "
                         "one device can be federated. --devices must be "
                         "divisible by M. 0 = the 1-D client mesh "
                         "bit-for-bit (REPRO_MODEL_SHARDS can fill in). "
                         "Requires --engine cohort")
    ap.add_argument("--wave-size", type=int, default=0,
                    help="stream the cohort client axis through the device "
                         "in fixed-size waves (fed/cohort.py): peak device "
                         "memory is bounded by the wave, not the client "
                         "count. 0 = whole axis device-resident (the "
                         "historical path, bit-for-bit). Requires "
                         "--engine cohort; composes with --devices")
    ap.add_argument("--edge-aggregators", type=int, default=1,
                    help="two-tier hierarchical server (fed/server.py): E "
                         "edge aggregators each reduce a contiguous client "
                         "shard (filter + staleness bookkeeping local) and "
                         "the root fuses E partial sums — root work scales "
                         "with E, not the client count. 1 = flat legacy "
                         "server")
    ap.add_argument("--arrival-process", default="static",
                    choices=["static", "poisson", "bursty"],
                    help="trace-driven client arrivals on the simulated "
                         "timeline (repro.fed.clock): static = everyone at "
                         "phase start (legacy); poisson = iid exponential "
                         "delays (mean --arrival-spread s); bursty = "
                         "clients cluster into --arrival-bursts spikes "
                         "over --arrival-spread s. Deterministic in "
                         "(seed, round, client); pure accounting")
    ap.add_argument("--arrival-spread", type=float, default=0.0,
                    help="arrival-trace time scale in simulated seconds "
                         "(0 disables the trace)")
    ap.add_argument("--arrival-bursts", type=int, default=4,
                    help="bursty arrivals only: number of arrival spikes "
                         "per round (a client's burst is stable in "
                         "(seed, client) — think timezone waves)")
    ap.add_argument("--churn", type=float, default=0.0,
                    help="per-round whole-round churn probability: an "
                         "offline client skips the round entirely and "
                         "drains through the staleness machinery")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="mid-round dropout probability: a client trains "
                         "but vanishes before reporting — its fresh report "
                         "never reaches the server")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled each round "
                         "(participation_fraction; 1.0 = every client "
                         "reports every round, the paper's setting)")
    ap.add_argument("--policy", default="uniform",
                    choices=["uniform", "weighted", "roundrobin"],
                    help="how the per-round participant subset is drawn "
                         "(seeded from (seed, round)): uniform without "
                         "replacement, weighted by private-set size, or a "
                         "deterministic rotating block")
    ap.add_argument("--staleness-decay", type=float, default=0.0,
                    help="non-participants keep their last-reported proxy "
                         "logits, down-weighted by decay**age: 0 = drop "
                         "them silently, 1 = FedBuff-style full reuse")
    ap.add_argument("--round-mode", default="auto",
                    choices=["auto", "sync", "overlap"],
                    help="round scheduler (repro.fed.scheduler): sync = "
                         "lockstep Algorithm-1 phase order (bit-for-bit "
                         "the legacy logs); overlap = pipeline up to "
                         "--max-inflight rounds (round r+1 trains/reports "
                         "while round r aggregates/distills through the "
                         "staleness buffer); auto = sync unless "
                         "REPRO_ROUND_MODE says otherwise")
    ap.add_argument("--max-inflight", type=int, default=2,
                    help="overlap only: rounds concurrently in flight "
                         "(1 = lockstep)")
    ap.add_argument("--max-pending-reports", type=int, default=0,
                    help="admission/backpressure cap on client reports the "
                         "server holds in flight across pending rounds; "
                         "reports are admitted in simulated-arrival order "
                         "and overflow clients drain through the staleness "
                         "buffer like dropouts. 0 = unbounded (legacy)")
    ap.add_argument("--straggler-factor", type=float, default=4.0,
                    help="simulated straggler clock spread "
                         "(repro.fed.clock): per-client slowdowns drawn "
                         "deterministically from (seed, client) in "
                         "[1, factor]; 1.0 = homogeneous fleet. Pure "
                         "accounting for the sim=... column, never "
                         "changes numerics")
    ap.add_argument("--server-distill-epochs", type=int, default=0,
                    help="server-student epochs per ensemble-distillation "
                         "round (method server_distill only): the FedDF "
                         "central student usually takes many more steps "
                         "than client KD. 0 = same as distill epochs")
    ap.add_argument("--zoo", default="auto",
                    choices=["auto", "shared", "mixed"],
                    help="feature-mode model zoo (repro.fed.simulator): "
                         "shared = one MLP architecture for every client "
                         "(the historical population); mixed = three width "
                         "variants cycled over clients, giving three "
                         "architecture cohorts; auto = shared unless "
                         "REPRO_ZOO says otherwise. Image datasets are "
                         "always the ten-slot heterogeneous zoo")
    ap.add_argument("--concurrent-cohorts", action="store_true",
                    help="schedule per-cohort phase nodes "
                         "(repro.fed.scheduler): each architecture cohort "
                         "advances through its round phases independently, "
                         "so a fast cohort's round r+1 training overlaps a "
                         "slow cohort's round r reporting. Identical "
                         "numerics to the serial graph; changes only the "
                         "simulated timeline. Works with either engine")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "pallas", "jnp"],
                    help="hot-path kernel dispatch (repro.kernels.dispatch): "
                         "auto = Pallas kernels on TPU, jnp reference "
                         "elsewhere (REPRO_KERNEL_BACKEND overrides); "
                         "pallas = force the kernels (interpret mode "
                         "off-TPU — validates the kernel path, not a CPU "
                         "speedup); jnp = force the reference code")
    ap.add_argument("--fault-mode", default="none",
                    choices=["none", "nan", "random_logits", "scaled",
                             "colluding_flip", "stale_replay"],
                    help="Byzantine/corruption fault trace "
                         "(repro.fed.faults): faulty clients train "
                         "honestly but corrupt the report they send — "
                         "deterministic in (seed, round, client), so every "
                         "engine injects identically. none = legacy "
                         "protocol, bit-for-bit")
    ap.add_argument("--fault-prob", type=float, default=0.0,
                    help="transient corruption: independent per-round coin "
                         "per client (flaky hardware, not an adversary)")
    ap.add_argument("--byzantine-frac", type=float, default=0.0,
                    help="fixed adversarial subset: round(frac*C) clients, "
                         "the same ones every round")
    ap.add_argument("--fault-start", type=int, default=0,
                    help="first round the fault trace is active")
    ap.add_argument("--fault-duration", type=int, default=0,
                    help="rounds the trace stays active (0 = unbounded); "
                         "start+duration stages a mid-run burst")
    ap.add_argument("--robust-aggregation", default="mean",
                    choices=["mean", "trimmed_mean", "median", "krum_row"],
                    help="teacher fusion over the client axis "
                         "(core/aggregation.py): mean = the paper's "
                         "staleness-weighted masked mean (legacy, "
                         "bit-for-bit); trimmed_mean/median/krum_row = "
                         "Byzantine-robust reducers (contributing clients "
                         "get one vote each; staleness weights act as a "
                         "contribute/exclude mask)")
    ap.add_argument("--trim-frac", type=float, default=0.2,
                    help="trimmed_mean only: fraction trimmed from each "
                         "tail of the per-position client distribution "
                         "(in [0, 0.5); beats f attackers when "
                         "floor(trim*n) >= f)")
    ap.add_argument("--no-sanitize", action="store_true",
                    help="disable the server's report sanitize pass "
                         "(non-finite rows scrubbed and accounted per "
                         "client before any fusion)")
    ap.add_argument("--quarantine-threshold", type=float, default=0.0,
                    help="EWMA trust score above which a client is "
                         "quarantined (sits out rounds, drains through the "
                         "staleness buffer; honest clients hover near 1). "
                         "0 = trust tracking off (legacy)")
    ap.add_argument("--quarantine-rounds", type=int, default=2,
                    help="base quarantine length; escalates linearly with "
                         "a client's strike count")
    ap.add_argument("--trust-ewma", type=float, default=0.5,
                    help="EWMA weight on the newest round's outlier "
                         "distance (in (0, 1]; 1 = no memory)")
    ap.add_argument("--watchdog", action="store_true",
                    help="divergence watchdog (repro.fed.scheduler): on a "
                         "sick RoundLog (non-finite metrics, accuracy "
                         "collapse, distill-loss spike) roll the experiment "
                         "back to the last healthy retirement and "
                         "quarantine the round's top outlier suspects "
                         "before the deterministic replay")
    ap.add_argument("--watchdog-acc-drop", type=float, default=0.2,
                    help="mean-accuracy drop vs the best healthy round "
                         "that trips the watchdog")
    ap.add_argument("--watchdog-loss-factor", type=float, default=10.0,
                    help="distill-loss multiple of the recent healthy "
                         "median that trips the watchdog")
    ap.add_argument("--watchdog-max-rollbacks", type=int, default=3,
                    help="rollback budget per run (spent budget = sick "
                         "rounds retire as-is)")
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--proxy-fraction", type=float, default=0.2)
    ap.add_argument("--proxy-batch", type=int, default=512)
    ap.add_argument("--threshold", type=float, default=-1.0,
                    help="<0 = per-client quantile calibration")
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--n-train", type=int, default=5000)
    ap.add_argument("--n-test", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=0)


def config_from_args(args: argparse.Namespace) -> FedConfig:
    """Build the ``FedConfig`` from ``add_config_args`` output."""
    return FedConfig(
        num_clients=args.clients,
        rounds=args.rounds,
        method=args.method,
        scenario=args.scenario,
        proxy_fraction=args.proxy_fraction,
        proxy_batch=args.proxy_batch,
        id_threshold=None if args.threshold < 0 else args.threshold,
        lr=args.lr,
        seed=args.seed,
        engine=args.engine,
        num_devices=args.devices,
        model_shards=args.model_shards,
        wave_size=args.wave_size,
        num_edge_aggregators=args.edge_aggregators,
        arrival_process=args.arrival_process,
        arrival_spread=args.arrival_spread,
        arrival_bursts=args.arrival_bursts,
        churn_prob=args.churn,
        dropout_prob=args.dropout,
        participation_fraction=args.participation,
        participation_policy=args.policy,
        staleness_decay=args.staleness_decay,
        round_mode=args.round_mode,
        max_inflight=args.max_inflight,
        max_pending_reports=args.max_pending_reports,
        straggler_factor=args.straggler_factor,
        kernel_backend=args.kernel_backend,
        server_distill_epochs=args.server_distill_epochs,
        zoo=args.zoo,
        concurrent_cohorts=args.concurrent_cohorts,
        fault_mode=args.fault_mode,
        fault_prob=args.fault_prob,
        byzantine_frac=args.byzantine_frac,
        fault_start=args.fault_start,
        fault_duration=args.fault_duration,
        robust_aggregation=args.robust_aggregation,
        trim_frac=args.trim_frac,
        sanitize_reports=not args.no_sanitize,
        quarantine_threshold=args.quarantine_threshold,
        trust_ewma=args.trust_ewma,
        quarantine_rounds=args.quarantine_rounds,
        watchdog=args.watchdog,
        watchdog_acc_drop=args.watchdog_acc_drop,
        watchdog_loss_factor=args.watchdog_loss_factor,
        watchdog_max_rollbacks=args.watchdog_max_rollbacks,
    )


def print_round(log, num_clients: int) -> None:
    """One progress line per retired round (shared with ``fed_serve``)."""
    extra = ""
    if log.server_student_acc is not None:
        extra += f"  student={log.server_student_acc:.4f}"
    if log.participants is not None:
        extra += (f"  part={len(log.participants)}/{num_clients}"
                  f"  stale={log.mean_staleness:.2f}")
    if log.phase_s:
        breakdown = " ".join(
            f"{PHASE_ABBREV.get(k, k)}={v:.2f}"
            for k, v in log.phase_s.items())
        extra += (f"  sim={log.sim_finish_s:.2f}s"
                  f"  age={log.served_model_age_s:.2f}s  [{breakdown}]")
    n = log.counters
    if n:
        extra += (f"  syncs={n.get('engine.syncs', 0)}"
                  f"+{n.get('server.syncs', 0)}")
        if n.get("compiles", 0):
            extra += f"  compiles={n['compiles']}"
    print(f"round {log.round:3d}  acc={log.mean_acc:.4f}  "
          f"id={log.id_fraction:.2f}  local={log.local_loss:.3f}  "
          f"distill={log.distill_loss:.3f}  "
          f"up={log.bytes_up/1e6:.1f}MB{extra}")


def main(argv=None, on_round=None):
    """Run one experiment; ``on_round(log)`` is called after each retired
    round is printed (in-process callers, e.g. ``chip_smoke.py``, time
    rounds with it)."""
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    ap.add_argument("--json", default="")
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = config_from_args(args)

    def progress(log):
        print_round(log, args.clients)
        if on_round is not None:
            on_round(log)

    res = simulator.run(cfg, args.dataset, n_train=args.n_train,
                        n_test=args.n_test, progress=progress)
    print(f"\n{args.method} / {args.scenario} / {args.dataset}: "
          f"final={res.final_acc:.4f} best={res.best_acc:.4f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"method": res.method, "scenario": res.scenario,
                       "final": res.final_acc, "best": res.best_acc,
                       "rounds": [vars(r) for r in res.rounds]}, f, indent=2)
    return res


if __name__ == "__main__":
    main()
