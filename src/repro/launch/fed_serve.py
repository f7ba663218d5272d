"""Resumable federated service driver: checkpoint, crash, resume.

Where ``fed_train`` is a batch run (build, run N rounds, exit),
``fed_serve`` treats the experiment as a long-running *service*: the
scheduler advances one phase node per event tick, the full experiment
state (scheduler window + in-flight rounds, server buffers and pending
reports, engine params/opt-state, every rng stream) is checkpointed every
``--ckpt-every`` rounds through ``repro.checkpoint`` (atomic write,
retention, corrupt-file fallback), and ``--resume`` picks up the latest
checkpoint after a crash.

The headline guarantee: kill the process at any phase boundary, resume
from the last checkpoint, and the completed round logs are bit-for-bit
identical to the uninterrupted run — on the loop, cohort and mesh-sharded
engines, in both sync and overlap round modes. (``--fixed-phase-costs``
additionally pins the simulated-timeline fields; without it they price at
measured wall-clock, which no checkpoint can replay.)

``--crash-after-phase NAME:K`` is the fault-injection hook the
kill-and-resume harness uses: the process SIGKILLs itself right after
executing node ``(NAME, K)`` — after any checkpoint due at that boundary
— so tests can place a crash at every phase boundary of a round::

    python -m repro.launch.fed_serve --rounds 2 --ckpt-dir /tmp/svc \
        --ckpt-every 1 --fixed-phase-costs --crash-after-phase aggregate:1
    python -m repro.launch.fed_serve --rounds 2 --ckpt-dir /tmp/svc \
        --ckpt-every 1 --fixed-phase-costs --resume --json svc.json

Each retired round logs ``served_model_age_s`` next to ``sim_finish_s``:
the simulated interval the *previous* model stayed the one a user query
would hit (the service's freshness metric; see ``core/protocol.RoundLog``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
from typing import List, Optional, Tuple

import jax

from repro.checkpoint import latest_step, restore_state, save_state
from repro.common.compile_cache import enable_compile_cache
from repro.core.methods import get_method
from repro.core.protocol import RoundLog
from repro.fed import participation, scheduler as sched_mod, simulator
from repro.fed.scheduler import RoundScheduler
from repro.kernels import dispatch
from repro.launch.fed_train import (add_config_args, config_from_args,
                                    print_round)

# deterministic per-phase base costs (simulated seconds) for
# --fixed-phase-costs; same constants as benchmarks/async_rounds.py, so
# served freshness numbers line up with the async benchmark's timeline
FIXED_COSTS = {"local_train": 1.0, "report": 0.1, "aggregate": 0.3,
               "distill": 1.0, "eval": 0.0}

# retired-round history sidecar, next to the checkpoints: each retired
# RoundLog is appended here as one JSON line *before* the checkpoint is
# written, and checkpoints are taken with ``snapshot(logs_tail=0)`` — so
# checkpoint size stays flat over a long service instead of growing with
# the log history
LOGS_SIDECAR = "logs.jsonl"


def _trim_sidecar(path: str, completed: int,
                  tail_len: int) -> List[RoundLog]:
    """Reconcile the sidecar with a restored checkpoint.

    The sidecar is appended before each checkpoint, so after a crash it
    may hold entries for rounds the restored state has not retired yet —
    those are replayed and re-appended, so the file is truncated to the
    first ``completed`` lines. Returns the history *head*: the retired
    rounds the checkpoint no longer carries (``completed - tail_len``
    entries; zero for pre-sidecar checkpoints that kept every log)."""
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    keep = lines[:completed]
    if len(keep) != len(lines):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write("".join(ln + "\n" for ln in keep))
        os.replace(tmp, path)
    head = keep[:max(completed - tail_len, 0)]
    return [RoundLog(**json.loads(ln)) for ln in head]


def parse_crash_spec(spec: str) -> Optional[Tuple[str, int]]:
    """``"aggregate:1"`` → ``("aggregate", 1)``; empty → ``None``."""
    if not spec:
        return None
    try:
        name, k = spec.rsplit(":", 1)
        return (name, int(k))
    except ValueError:
        raise SystemExit(
            f"--crash-after-phase wants NAME:ROUND (e.g. aggregate:1), "
            f"got {spec!r}")


def build_scheduler(cfg, dataset: str, n_train: int, n_test: int,
                    fixed_costs: bool) -> RoundScheduler:
    """Build the experiment exactly like ``simulator.run`` would.

    Resume relies on this being deterministic in ``cfg``: datasets,
    partitions, model inits and DRE fits are rebuilt from the config, and
    the checkpoint only overlays mutable state on top."""
    participation.validate_config(cfg)
    sched_mod.validate_config(cfg)
    dispatch.resolve(cfg.kernel_backend)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, dataset, n_train=n_train, n_test=n_test)
    engine = simulator.build_engine(clients, cfg)
    method = get_method(cfg.method)
    if method.client_filter != "none":
        engine.learn_dres(jax.random.PRNGKey(cfg.seed))
    return RoundScheduler(engine, server, method, cfg, x_test, y_test,
                          sim_phase_costs=FIXED_COSTS if fixed_costs
                          else None)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Resumable federated service: event-loop scheduling "
                    "with periodic experiment checkpoints")
    add_config_args(ap)
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory (empty = no checkpointing)")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoint every N retired rounds (0 disables; "
                         "requires --ckpt-dir)")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="retain only the newest K checkpoints "
                         "(0 = keep everything)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(falls back to a fresh start when none exists)")
    ap.add_argument("--crash-after-phase", default="",
                    help="fault injection: SIGKILL the process right after "
                         "executing phase node NAME:ROUND (after any "
                         "checkpoint due at that boundary) — the "
                         "kill-and-resume harness hook")
    ap.add_argument("--fixed-phase-costs", action="store_true",
                    help="price the simulated timeline with fixed per-phase "
                         "costs instead of measured wall-clock, making sim "
                         "fields (sim_finish_s, served_model_age_s) "
                         "deterministic and resume bit-for-bit complete")
    ap.add_argument("--json", default="",
                    help="write the full round-log history here on exit")
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = config_from_args(args)
    crash_at = parse_crash_spec(args.crash_after_phase)
    ckpt_on = bool(args.ckpt_dir) and args.ckpt_every > 0
    keep_last = args.keep_last if args.keep_last > 0 else None

    sched = build_scheduler(cfg, args.dataset, args.n_train, args.n_test,
                            args.fixed_phase_costs)

    sidecar = (os.path.join(args.ckpt_dir, LOGS_SIDECAR) if ckpt_on
               else None)
    if sidecar is not None:
        # the sidecar is appended before the first checkpoint is written,
        # so the directory must exist already
        os.makedirs(args.ckpt_dir, exist_ok=True)
    resumed_from = None
    history: List[RoundLog] = []
    if args.resume and args.ckpt_dir:
        step = latest_step(args.ckpt_dir)
        if step is not None:
            sched.restore(restore_state(args.ckpt_dir, step))
            resumed_from = step
            if sidecar is not None and os.path.exists(sidecar):
                history = _trim_sidecar(sidecar, sched.completed,
                                        len(sched.logs))
            print(f"resumed from checkpoint step {step} "
                  f"({sched.completed} rounds already retired)")
    if resumed_from is None:
        sched.begin(0, cfg.rounds)
        if sidecar is not None and os.path.exists(sidecar):
            os.remove(sidecar)  # stale history from a previous service

    while sched.has_pending():
        phase, r, log = sched.step()
        if log is not None:
            print_round(log, cfg.num_clients)
            if sidecar is not None:
                # appended BEFORE the checkpoint: on crash the sidecar can
                # only run ahead of the restored state, and _trim_sidecar
                # truncates the overhang on resume
                with open(sidecar, "a") as f:
                    f.write(json.dumps(dataclasses.asdict(log)) + "\n")
            if ckpt_on and sched.completed % args.ckpt_every == 0:
                try:
                    path = save_state(args.ckpt_dir, sched.completed,
                                      sched.snapshot(logs_tail=0).to_tree(),
                                      keep_last=keep_last)
                    print(f"  checkpoint -> {path}")
                except OSError as e:
                    # the writer already retried with backoff; a service
                    # should keep serving on a transient storage outage
                    # and try again at the next boundary
                    print(f"  checkpoint FAILED after retries ({e!r}); "
                          f"continuing without", flush=True)
        if crash_at is not None and (phase, r) == crash_at:
            print(f"crash hook: SIGKILL after ({phase}, {r})", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)

    logs = history + sched.logs
    if logs:
        mean_age = sum(l.served_model_age_s for l in logs) / len(logs)
        print(f"\nserved {len(logs)} rounds  final={logs[-1].mean_acc:.4f}"
              f"  best={max(l.mean_acc for l in logs):.4f}"
              f"  mean_model_age={mean_age:.2f}s")
    if args.json:
        with open(args.json, "w") as f:
            json.dump([dataclasses.asdict(l) for l in logs], f, indent=2)
    return logs


if __name__ == "__main__":
    main()
