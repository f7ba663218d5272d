"""Phase-graph round scheduler: lockstep (sync) and overlapping (overlap).

Algorithm 1's training iteration is not one monolithic step — it is five
phases with explicit data dependencies::

    local_train ──▶ report ──▶ aggregate ──▶ distill ──▶ eval

(``report``/``aggregate``/``distill`` are the proxy-logit exchange for the
distillation methods, the class-wise exchange for the data-free methods,
and absent for ``indlearn``.) This module makes that graph explicit: every
round contributes one node per phase, nodes declare their dependencies,
and a deterministic executor runs whatever is ready. ``FedConfig.
round_mode`` selects between two dependency sets:

``sync`` (the default)
    ``local_train(r)`` additionally depends on ``eval(r-1)`` — a full
    barrier between rounds. The executor then replays the exact legacy
    ``run_round`` phase order, bit-for-bit (golden-pinned in
    ``tests/test_scheduler.py``).

``overlap``
    ``local_train(r)`` depends on ``eval(r - max_inflight)`` instead, so
    up to ``max_inflight`` rounds are in flight at once: round ``r+1``
    trains and reports while round ``r`` aggregates and distills, with
    non-participant knowledge draining through the server's existing
    ``StalenessBuffer`` (reports are ingested in round order, so buffer
    ages never go negative). Numerically this is a *different protocol* —
    round ``r+1`` trains on models that have not yet seen round ``r``'s
    teacher — which is exactly the asynchrony edge deployments pay for
    overlap; final accuracy stays within tolerance of lockstep
    (``benchmarks/async_rounds.py``).

The executor's ready-node policy is what creates the pipeline: client-side
*front* phases (``local_train``, ``report``) run before server-side
*drain* phases (``aggregate``, ``distill``, ``eval``), oldest round first
within each class. Under ``sync`` only one node is ever ready, so the
policy degenerates to the lockstep order; under ``overlap`` it interleaves
rounds like a software pipeline. The policy is engine-independent, so loop
== cohort == mesh-sharded round logs still match under ``overlap``.

Every node execution is timed (``RoundLog.phase_s``, from its
``phase.<name>`` span; ``repro.common.tracing``), its sync and compile
counts are booked on its round (``RoundLog.counters``), and it is priced
onto the simulated straggler timeline (``repro.fed.clock``): clients run in
parallel at deterministic per-client speeds, the server is one serial
resource, and ``RoundLog.sim_finish_s`` records when the round retires on
that timeline. That is the axis on which overlap measurably beats sync on
a single host (``BENCH_async.json``).

``REPRO_ROUND_MODE`` (env) fills in for ``round_mode="auto"`` the way
``REPRO_KERNEL_BACKEND`` does for the kernel dispatch layer — a CI
vehicle for running the whole test suite through the overlap scheduler.
Explicit ``sync``/``overlap`` always win over the env var.

Two extensions ride the same graph:

**Concurrent cohorts** (``FedConfig.concurrent_cohorts=True``): client-side
phase nodes (``local_train``/``report``/``distill``) are keyed per cohort —
``(phase, round, cohort)`` — so a heterogeneous zoo's cohorts pipeline
independently: cohort A distills round ``r`` while cohort B already trains
round ``r+1`` on the simulated timeline (admission is per cohort:
``local_train(r, c)`` waits on ``distill(r - max_inflight, c)`` of *its own*
cohort, with host-order edges keeping execution deterministic and
bit-for-bit the serial schedule when only one cohort exists). Aggregation
stays a global barrier — the protocol needs every cohort's report — so the
win is cross-round desynchronization, measurable with per-cohort phase
costs (``sim_phase_costs["phase@cohort"]``, ``benchmarks/hetero_zoo.py``).
Both graphs run the same node bodies: a client-phase body loops over the
cohorts its node covers (every cohort for a serial node, one for a
concurrent one) and calls the engine's per-cohort entry point for each.

**FedDF ensemble server** (``method="server_distill"``): a ``server_distill``
phase node between ``aggregate`` and ``distill`` trains the server's central
student against the masked/weighted ensemble teacher
(``Server.ensemble_distill``), priced on the serial server lane and
checkpointable like every other phase.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.common import tracing
from repro.common.types import FedConfig
from repro.core.protocol import RoundLog
from repro.fed.clock import (ARRIVAL_PROCESSES, SimTimeline, arrival_offsets,
                             client_speeds, dropout_mask, online_mask)
from repro.fed.faults import FaultInjector, validate_fault_config
from repro.fed.participation import sample_participants

ROUND_MODES = ("sync", "overlap")
# the five phase names, in intra-round dependency order
PHASE_ORDER = ("local_train", "report", "aggregate", "distill", "eval")
# client-side phases that admit new rounds into the pipeline; the rest
# drain old ones ("eval" is bookkeeping but retires the round, so it
# drains too)
FRONT_PHASES = frozenset({"local_train", "report"})
# phases priced on client lanes of the simulated timeline ("aggregate" is
# the serial server resource, "eval" is free measurement)
CLIENT_PHASES = frozenset({"local_train", "report", "distill"})


def resolve_round_mode(mode: Optional[str]) -> str:
    """``auto`` → the ``REPRO_ROUND_MODE`` env var if set, else ``sync``.

    Explicit ``sync``/``overlap`` always win — the env var exists so CI can
    run the whole suite through the overlap scheduler without touching
    every config (mirroring ``REPRO_KERNEL_BACKEND``)."""
    if mode in (None, "auto"):
        env = os.environ.get("REPRO_ROUND_MODE")
        # an empty or "auto" env value means "no opinion" (the CI matrix
        # exports the literal matrix cell, which is "auto" off the
        # overlap entry)
        mode = env if env not in (None, "", "auto") else "sync"
    if mode not in ROUND_MODES:
        raise ValueError(f"unknown round_mode {mode!r}; known: auto, "
                         + ", ".join(ROUND_MODES))
    return mode


def validate_config(cfg: FedConfig) -> None:
    """Fail fast on an inconsistent scheduler config."""
    resolve_round_mode(cfg.round_mode)
    if cfg.max_inflight < 1:
        raise ValueError(
            f"max_inflight must be >= 1 (1 = lockstep), got "
            f"{cfg.max_inflight!r}")
    if cfg.straggler_factor < 1.0:
        raise ValueError(
            f"straggler_factor must be >= 1.0 (1.0 = homogeneous fleet), "
            f"got {cfg.straggler_factor!r}")
    f = cfg.participation_fraction
    if not 0.0 < f <= 1.0:
        raise ValueError(
            f"participation_fraction must be in (0, 1], got {f!r}")
    if cfg.arrival_process not in ARRIVAL_PROCESSES:
        raise ValueError(
            f"unknown arrival_process {cfg.arrival_process!r}; known: "
            + ", ".join(ARRIVAL_PROCESSES))
    if cfg.arrival_spread < 0.0:
        raise ValueError(
            f"arrival_spread must be >= 0, got {cfg.arrival_spread!r}")
    if cfg.arrival_bursts < 1:
        raise ValueError(
            f"arrival_bursts must be >= 1, got {cfg.arrival_bursts!r}")
    for knob, v in (("churn_prob", cfg.churn_prob),
                    ("dropout_prob", cfg.dropout_prob)):
        if not 0.0 <= v < 1.0:
            raise ValueError(f"{knob} must be in [0, 1), got {v!r}")
    if cfg.max_pending_reports < 0:
        raise ValueError(
            f"max_pending_reports must be >= 0 (0 = unbounded), got "
            f"{cfg.max_pending_reports!r}")
    validate_fault_config(cfg.fault_mode, cfg.fault_prob,
                          cfg.byzantine_frac, cfg.fault_start,
                          cfg.fault_duration)
    # robust_aggregation / trust knobs are validated where they land (the
    # Server constructor); the watchdog knobs live here with the scheduler
    if cfg.watchdog_max_rollbacks < 0:
        raise ValueError(
            f"watchdog_max_rollbacks must be >= 0, got "
            f"{cfg.watchdog_max_rollbacks!r}")
    if cfg.watchdog_acc_drop <= 0.0:
        raise ValueError(
            f"watchdog_acc_drop must be > 0, got "
            f"{cfg.watchdog_acc_drop!r}")
    if cfg.watchdog_loss_factor <= 1.0:
        raise ValueError(
            f"watchdog_loss_factor must be > 1, got "
            f"{cfg.watchdog_loss_factor!r}")


def round_phases(method) -> Tuple[str, ...]:
    """The phase nodes one round of ``method`` contributes to the graph."""
    if method.name == "indlearn":  # no collaboration: train, then measure
        return ("local_train", "eval")
    if method.server_distill:
        # FedDF: the server student trains on the fused teacher before the
        # clients distill — a serial server-lane node riding the graph
        return ("local_train", "report", "aggregate", "server_distill",
                "distill", "eval")
    return PHASE_ORDER


def _node_meta(key: Tuple) -> Dict[str, int]:
    """Span metadata of a node: its round, and its cohort if it has one."""
    if len(key) > 2:
        return {"round": key[1], "cohort": key[2]}
    return {"round": key[1]}


class _RoundState:
    """Mutable state threaded between one round's phase nodes."""

    __slots__ = ("r", "part", "idx", "px", "powner", "means_counts",
                 "teacher", "valid", "teacher_by_class", "valid_by_class",
                 "local_losses", "distill_losses", "id_frac", "id_fracs",
                 "mean_staleness", "accs", "phase_s", "counters",
                 "sim_finish_s", "rpart", "sampled", "reports_pending",
                 "report_logits", "report_masks", "report_arrival",
                 "server_distill_loss", "server_student_acc")

    def __init__(self, r: int):
        self.r = r
        self.part = None            # training mask (None = everyone)
        self.idx = None             # proxy indices / batch / owners
        self.px = None
        self.powner = None
        self.means_counts = None    # data-free report payload
        self.teacher = None         # aggregation outputs
        self.valid = None
        self.teacher_by_class = None
        self.valid_by_class = None
        self.local_losses: List[float] = []
        self.distill_losses: List[float] = []
        self.id_frac = 1.0
        self.id_fracs = None        # per client (0.0 where it sent nothing)
        self.mean_staleness = 0.0
        self.accs = None
        self.phase_s: Dict[str, float] = {}
        self.counters: Dict[str, int] = {}
        self.sim_finish_s = 0.0
        # The round's *reporting* participants: training participation
        # (st.part) minus mid-round dropout and admission overflow. Kept
        # apart from st.part because, with per-cohort nodes, a later
        # cohort's local_train may still need the pre-dropout mask.
        # None = same as st.part.
        self.rpart = None
        self.sampled = False        # participation drawn for this round?
        # report accumulation: report nodes fill their cohorts' rows here
        # and count down reports_pending; ingestion fires at zero. With
        # per-cohort nodes these live across phase boundaries, so they are
        # checkpointed.
        self.reports_pending = None
        self.report_logits = None
        self.report_masks = None
        # per-client simulated report-arrival times, captured when each
        # report node is priced (later nodes may advance the lanes before
        # the round ingests, so arrival order must be pinned at pricing)
        self.report_arrival = None
        # FedDF ensemble server (method="server_distill")
        self.server_distill_loss = 0.0
        self.server_student_acc = None

    def state_dict(self) -> Dict:
        """Mutable payload of a partially-executed (in-flight) round.

        ``px``/``powner`` are derived fields (recomputed from ``idx`` on
        restore), so they are not captured. Losses and
        accuracies are plain python floats end-to-end, which the JSON
        manifest round-trips exactly (``repr`` round-trip)."""
        from repro.fed.state import opt_array
        return {
            "r": int(self.r),
            "part": opt_array(self.part, bool),
            "idx": opt_array(self.idx),
            "means_counts": (None if self.means_counts is None
                             else [[np.asarray(m), np.asarray(c)]
                                   for m, c in self.means_counts]),
            "teacher": opt_array(self.teacher),
            "valid": opt_array(self.valid),
            "teacher_by_class": opt_array(self.teacher_by_class),
            "valid_by_class": opt_array(self.valid_by_class),
            "local_losses": [float(v) for v in self.local_losses],
            "distill_losses": [float(v) for v in self.distill_losses],
            "id_frac": float(self.id_frac),
            "id_fracs": self.id_fracs,
            "mean_staleness": float(self.mean_staleness),
            "accs": (None if self.accs is None
                     else [float(a) for a in self.accs]),
            "phase_s": {k: float(v) for k, v in self.phase_s.items()},
            "counters": {k: int(v) for k, v in self.counters.items()},
            "sim_finish_s": float(self.sim_finish_s),
            "rpart": opt_array(self.rpart, bool),
            "sampled": bool(self.sampled),
            "reports_pending": (None if self.reports_pending is None
                                else int(self.reports_pending)),
            "report_logits": opt_array(self.report_logits),
            "report_masks": opt_array(self.report_masks, bool),
            "report_arrival": opt_array(self.report_arrival),
            "server_distill_loss": float(self.server_distill_loss),
            "server_student_acc": (None if self.server_student_acc is None
                                   else float(self.server_student_acc)),
        }

    def load_state_dict(self, sd: Dict, scheduler) -> None:
        from repro.fed.state import opt_array
        self.part = opt_array(sd["part"], bool)
        self.idx = opt_array(sd["idx"])
        if self.idx is not None:
            self.px = scheduler.server.proxy.x[self.idx]
            self.powner = scheduler.server.proxy.owner[self.idx]
        mc = sd["means_counts"]
        self.means_counts = (None if mc is None
                             else [(np.asarray(m), np.asarray(c))
                                   for m, c in mc])
        self.teacher = opt_array(sd["teacher"])
        self.valid = opt_array(sd["valid"])
        self.teacher_by_class = opt_array(sd["teacher_by_class"])
        self.valid_by_class = opt_array(sd["valid_by_class"])
        self.local_losses = [float(v) for v in sd["local_losses"]]
        self.distill_losses = [float(v) for v in sd["distill_losses"]]
        self.id_frac = float(sd["id_frac"])
        self.id_fracs = sd["id_fracs"]
        self.mean_staleness = float(sd["mean_staleness"])
        accs = sd["accs"]
        self.accs = None if accs is None else [float(a) for a in accs]
        self.phase_s = {k: float(v) for k, v in sd["phase_s"].items()}
        self.counters = {k: int(v)
                         for k, v in sd.get("counters", {}).items()}
        self.sim_finish_s = float(sd["sim_finish_s"])
        # concurrent-cohort / ensemble-server fields (``.get``: absent from
        # checkpoints written before these features existed). Serial
        # checkpoints of older trees narrowed ``part`` in place and left
        # ``rpart`` None, which ``_report_part`` reads as "same as part".
        self.rpart = opt_array(sd.get("rpart"), bool)
        self.sampled = bool(sd.get("sampled", False))
        rp = sd.get("reports_pending")
        self.reports_pending = None if rp is None else int(rp)
        self.report_logits = opt_array(sd.get("report_logits"))
        self.report_masks = opt_array(sd.get("report_masks"), bool)
        self.report_arrival = opt_array(sd.get("report_arrival"))
        self.server_distill_loss = float(sd.get("server_distill_loss", 0.0))
        acc = sd.get("server_student_acc")
        self.server_student_acc = None if acc is None else float(acc)


class RoundScheduler:
    """Executes the round phase graph over an engine/server pair.

    One scheduler instance owns one contiguous run of rounds: the straggler
    timeline, the execution trace and the server's in-flight report records
    all live here. ``run_round``/``run_experiment`` are thin drivers over
    this class.

    ``sim_phase_costs`` (tests/benchmark harnesses) replaces the measured
    per-phase host seconds with fixed base costs, making the simulated
    timeline fully deterministic; ``None`` (the default) prices phases at
    their measured wall-clock.
    """

    def __init__(self, engine, server, method, cfg, x_test, y_test, *,
                 sim_phase_costs: Optional[Dict[str, float]] = None):
        validate_config(cfg)
        self.engine = engine
        self.server = server
        self.method = method
        self.cfg = cfg
        self.x_test = x_test
        self.y_test = y_test
        self.mode = resolve_round_mode(cfg.round_mode)
        # sync IS the overlap graph at pipeline depth 1
        self.max_inflight = cfg.max_inflight if self.mode == "overlap" else 1
        self.phases = round_phases(method)
        self.sim_phase_costs = sim_phase_costs
        self.timeline = SimTimeline(client_speeds(
            engine.num_clients, seed=cfg.seed,
            straggler_factor=cfg.straggler_factor))
        # concurrent-cohort mode: client-side phase nodes are keyed
        # (phase, round, cohort) and each cohort pipelines independently;
        # a serial client node covers every cohort in order
        self._concurrent = cfg.concurrent_cohorts
        self._cohort_pos = [np.asarray(p, int)
                            for p in engine.cohort_positions()]
        # node keys in host execution order — (phase, round) for global
        # nodes, (phase, round, cohort) for per-cohort client nodes; the
        # determinism tests pin this, and it is the record of what the
        # pipeline actually did
        self.trace: List[Tuple] = []
        self._sim_end: Dict[Tuple, float] = {}
        # event-loop state (begin()/step()/drain()); a fresh scheduler has
        # no window open
        self._order = {p: i for i, p in enumerate(self.phases)}
        self._window: Optional[Tuple[int, int]] = None
        self._states: Dict[int, _RoundState] = {}
        self._nodes: Dict[Tuple[str, int], List] = {}
        self._pending: set = set()
        self._done: set = set()
        self.logs: List[RoundLog] = []
        # monotone count of rounds retired in the open window. Equal to
        # ``len(self.logs)`` unless ``snapshot(logs_tail=...)`` truncated
        # the retained history (the fed_serve sidecar streams retired logs
        # out of the checkpoint) — restore then trusts this counter, not
        # the tail length.
        self.completed = 0
        # sim time of the last round retirement — the served-model
        # freshness reference (service start = 0.0)
        self._last_retire_s = 0.0
        # Byzantine / corruption fault trace: built only when enabled, so
        # the default path never constructs one (bit-for-bit legacy)
        self.faults: Optional[FaultInjector] = None
        if cfg.fault_mode != "none":
            self.faults = FaultInjector(
                engine.num_clients, mode=cfg.fault_mode, seed=cfg.seed,
                fault_prob=cfg.fault_prob,
                byzantine_frac=cfg.byzantine_frac,
                fault_start=cfg.fault_start,
                fault_duration=cfg.fault_duration)
        # divergence watchdog: rollback-to-last-healthy-retire on a sick
        # RoundLog. ``_wd_tree`` is the in-memory restore point (a plain
        # nested tree — asdict deep-copies, so later mutation can't alias
        # into it); it is NOT checkpointed and rebuilds at the next
        # healthy retire (or on restore()).
        self._watchdog = cfg.watchdog
        self.rollbacks = 0
        self._wd_best_acc = 0.0
        self._wd_loss_hist: List[float] = []
        self._wd_tree = None

    # ------------------------------------------------------------ the graph
    def _build_deps(self, rounds) -> Dict[Tuple[str, int], List]:
        """Nodes + declared dependencies for a contiguous round window.

        Each dep is ``(phase, round, kind)``: ``data`` deps gate both host
        execution and the simulated timeline; ``order`` deps (same phase,
        previous round) pin host execution order — server rng draws, report
        ingestion and log assembly must happen in round order — but cost
        nothing on the timeline (disjoint clients of different rounds
        genuinely run concurrently; shared clients are serialized by their
        timeline lanes instead).

        Concurrent-cohort mode keys client-side nodes per cohort — deps are
        then ``(phase, round, cohort, kind)``. Data flows stay within a
        cohort until the global aggregate barrier (which needs every
        cohort's report), and admission pipelines per cohort: cohort c's
        ``local_train(r)`` waits on *its own* ``distill(r - max_inflight)``
        on the timeline, with an order-only edge to ``eval(r -
        max_inflight)`` pinning the host order (so a single-cohort zoo
        replays the serial schedule — and its sim times — exactly)."""
        window = set(rounds)
        nodes: Dict[Tuple, List] = {}
        if not self._concurrent:
            for r in rounds:
                for i, p in enumerate(self.phases):
                    deps = []
                    if i > 0:  # intra-round chain: the actual data flow
                        deps.append((self.phases[i - 1], r, "data"))
                    if (r - 1) in window:  # host-order edge
                        deps.append((p, r - 1, "order"))
                    if i == 0 and (r - self.max_inflight) in window:
                        # admission: round r enters the pipeline only once
                        # round r - max_inflight has fully retired
                        deps.append((self.phases[-1], r - self.max_inflight,
                                     "data"))
                    nodes[(p, r)] = deps
            return nodes
        ncoh = len(self._cohort_pos)
        client = [p for p in self.phases if p in CLIENT_PHASES]
        last_client = client[-1]  # the cohort's slowest-retiring phase
        for r in rounds:
            for i, p in enumerate(self.phases):
                prev = self.phases[i - 1] if i > 0 else None
                if p not in CLIENT_PHASES:  # global: aggregate/sdist/eval
                    deps = []
                    if prev is not None:
                        if prev in CLIENT_PHASES:  # barrier on every cohort
                            deps += [(prev, r, cj, "data")
                                     for cj in range(ncoh)]
                        else:
                            deps.append((prev, r, "data"))
                    if (r - 1) in window:
                        deps.append((p, r - 1, "order"))
                    nodes[(p, r)] = deps
                    continue
                for ci in range(ncoh):
                    deps = []
                    if prev is not None:
                        # a client phase's input is its own cohort's
                        # previous client phase, or the global teacher
                        deps.append((prev, r, ci, "data")
                                    if prev in CLIENT_PHASES
                                    else (prev, r, "data"))
                    if (r - 1) in window:
                        deps.append((p, r - 1, ci, "order"))
                        if p == "report":
                            # every cohort of round r-1 reports before any
                            # cohort of round r: the server's proxy-batch
                            # rng draw and report ingestion stay
                            # round-ordered under any interleaving
                            deps += [(p, r - 1, cj, "order")
                                     for cj in range(ncoh) if cj != ci]
                    if p == client[0] and (r - self.max_inflight) in window:
                        q = r - self.max_inflight
                        # per-cohort admission: this cohort's lanes free up
                        # when ITS round-q distill retires — cross-round
                        # pipelining per cohort is the concurrency win...
                        deps.append((last_client, q, ci, "data"))
                        # ...while the host still runs eval(q) first (order
                        # only: free on the timeline), keeping execution
                        # deterministic and serial-equivalent numerics
                        deps.append((self.phases[-1], q, "order"))
                    nodes[(p, r, ci)] = deps
        return nodes

    # ------------------------------------------------------- the event loop
    def begin(self, start: int, count: int) -> None:
        """Open the round window ``[start, start + count)``.

        Builds the node graph and resets per-window bookkeeping; the
        simulated timeline, trace and node finish times carry over from any
        previous window on this scheduler (that is how sequential windows
        chain). ``step()`` then executes one node at a time."""
        if self._pending:
            raise RuntimeError(
                f"cannot begin a new round window: {len(self._pending)} "
                "nodes of the current window are still pending")
        rounds = range(start, start + count)
        self._window = (start, count)
        self._states = {r: _RoundState(r) for r in rounds}
        self._nodes = self._build_deps(rounds)
        self._pending = set(self._nodes)
        self._done = set()
        self.logs = []
        self.completed = 0
        if self._watchdog:
            # arm the rollback point at the window start too — a round-0
            # attack must be as recoverable as a mid-run one
            self._wd_tree = self.snapshot().to_tree()

    def has_pending(self) -> bool:
        """True while the open window still has nodes to execute."""
        return bool(self._pending)

    def step(self) -> Tuple[str, int, Optional[RoundLog]]:
        """Execute the single next ready node; the scheduler's event tick.

        Returns ``(phase, round, log)`` where ``log`` is the finished
        ``RoundLog`` when this node retired its round, else ``None``. Every
        return is a phase boundary — a consistent point to ``snapshot()``
        (or crash at: the kill-and-resume harness keys off these)."""
        if not self._pending:
            raise RuntimeError("no pending nodes — call begin() first")
        with tracing.mark("sched.step") as sp:
            ready = [
                k for k in self._pending
                if all(d[1] not in self._states or d[:-1] in self._done
                       for d in self._nodes[k])
            ]
            # deterministic pipeline policy: front (client-side) phases
            # before drain phases, oldest round first, intra-round order
            # next, cohort index last — under sync with one cohort exactly one
            # node is ever ready, so this replays the legacy lockstep order
            key = min(ready, key=lambda k: (k[0] not in FRONT_PHASES, k[1],
                                            self._order[k[0]],
                                            k[2] if len(k) > 2 else -1))
            phase, r = key[0], key[1]
            sp.set_metadata(**_node_meta(key))
            self._run_node(key, self._states[r], self._nodes[key])
            self._pending.remove(key)
            self._done.add(key)
            log = None
            if phase == self.phases[-1]:
                log = self._finish_round(self._states[r])
                if self._watchdog and self._wd_unhealthy(log) \
                        and self._wd_rollback(r):
                    # the round was replayed from the last healthy retire; the
                    # sick log is discarded and the caller sees no retirement
                    return phase, r, None
                self.logs.append(log)
                self.completed += 1
                self._retire(r)
                if self._watchdog:
                    self._wd_note_healthy(log)
            return phase, r, log

    def drain(self, progress: Optional[Callable[[RoundLog], None]] = None
              ) -> List[RoundLog]:
        """Run the open window to completion."""
        while self._pending:
            _, _, log = self.step()
            if log is not None and progress:
                progress(log)
        return self.logs

    def run_rounds(self, start: int, count: int,
                   progress: Optional[Callable[[RoundLog], None]] = None
                   ) -> List[RoundLog]:
        """Execute rounds ``[start, start + count)`` through the graph."""
        self.begin(start, count)
        return self.drain(progress)

    def _retire(self, r: int) -> None:
        """Drop a retired round's bookkeeping so memory stays bounded over
        a long-running service (rounds retire in round order — the eval
        nodes chain through same-phase order deps).

        The ready check treats rounds absent from ``_states`` as
        satisfied, so pruning is transparent to dependents. Simulated
        finish times survive a little longer: ``(eval, q)`` is the
        admission dep of ``local_train(q + max_inflight)``, so entries are
        only dropped once they are ``max_inflight`` rounds stale."""
        del self._states[r]
        # drop the round's suspect scores (the watchdog consumed them on
        # rollback already)
        self.server.pop_round_outlier(r)
        self._done -= {k for k in self._done if k[1] == r}
        horizon = r - self.max_inflight
        for key in [k for k in self._sim_end if k[1] <= horizon]:
            del self._sim_end[key]

    # --------------------------------------------------- snapshot / restore
    def snapshot(self, *, logs_tail: Optional[int] = None):
        """Capture the full experiment at the current phase boundary.

        Returns an ``ExperimentState`` assembling this scheduler's node
        bookkeeping and in-flight round payloads with the ``state_dict()``
        of the timeline, the server (pending reports, staleness buffers,
        byte ledger, rng) and the engine (per-client params/opt-state/rng).
        Call only between ``step()``s — mid-node state is not capturable.

        ``logs_tail`` caps how many retired ``RoundLog``s ride the state
        (``None`` = all of them, the legacy layout). A caller that streams
        retired logs to durable storage of its own (the fed_serve
        ``logs.jsonl`` sidecar) passes ``logs_tail=0`` so checkpoint size
        stays flat over a long service; ``sched["completed"]`` still
        records the true retired count."""
        from repro.fed.state import STATE_VERSION, ExperimentState
        if self._window is None:
            raise RuntimeError("nothing to snapshot — call begin() first")
        inflight = sorted(
            r for r in self._states
            if any(k[1] == r for k in self._done))

        def as_list(key):
            # (phase, round) → [p, r]; (phase, round, cohort) → [p, r, ci]
            # — length discriminates on restore
            return [key[0]] + [int(v) for v in key[1:]]

        sched = {
            "window": [int(self._window[0]), int(self._window[1])],
            "completed": int(self.completed),
            "done": sorted(as_list(k) for k in self._done),
            "trace": [as_list(k) for k in self.trace],
            "sim_end": sorted(as_list(k) + [float(t)]
                              for k, t in self._sim_end.items()),
            "last_retire_s": float(self._last_retire_s),
            "states": [self._states[r].state_dict() for r in inflight],
            "rollbacks": int(self.rollbacks),
            "wd_best_acc": float(self._wd_best_acc),
            "wd_loss_hist": [float(v) for v in self._wd_loss_hist],
        }
        if self.faults is not None:
            sched["faults"] = self.faults.state_dict()
        logs = (self.logs if logs_tail is None
                else self.logs[max(len(self.logs) - int(logs_tail), 0):])
        import dataclasses as _dc
        return ExperimentState(
            version=STATE_VERSION,
            round_mode=self.mode,
            scheduler=sched,
            timeline=self.timeline.state_dict(),
            server=self.server.state_dict(),
            engine=self.engine.state_dict(),
            logs=[_dc.asdict(lg) for lg in logs],
        )

    def restore(self, state) -> None:
        """Rebuild the event loop from a ``snapshot()`` (or its tree form).

        The scheduler must be freshly constructed from the *same*
        ``FedConfig`` (datasets, method, engine layout and rng seeds are
        rebuilt, not checkpointed); this overlays every piece of mutable
        state, after which ``drain()`` continues the run with logs
        bit-for-bit identical to the uninterrupted one."""
        from repro.fed.state import ExperimentState
        if not isinstance(state, ExperimentState):
            state = ExperimentState.from_tree(state)
        if state.round_mode != self.mode:
            raise ValueError(
                f"checkpoint was written in round_mode={state.round_mode!r} "
                f"but this scheduler runs {self.mode!r}")
        sched = state.scheduler
        start, count = (int(v) for v in sched["window"])
        rounds = range(start, start + count)
        self._window = (start, count)
        self._nodes = self._build_deps(rounds)
        completed = int(sched["completed"])
        # rounds retire in order, so the retired set is a prefix
        retired = set(range(start, start + completed))
        def as_key(e):
            # [p, r] → (phase, round); [p, r, ci] → (phase, round, cohort)
            return (e[0],) + tuple(int(v) for v in e[1:])

        self._done = {as_key(e) for e in sched["done"]}
        self._states = {r: _RoundState(r) for r in rounds
                        if r not in retired}
        for st_sd in sched["states"]:
            self._states[int(st_sd["r"])].load_state_dict(st_sd, self)
        self._pending = {k for k in self._nodes
                         if k[1] not in retired and k not in self._done}
        self.trace = [as_key(e) for e in sched["trace"]]
        self._sim_end = {as_key(e[:-1]): float(e[-1])
                         for e in sched["sim_end"]}
        self._last_retire_s = float(sched["last_retire_s"])
        self.timeline.load_state_dict(state.timeline)
        self.server.load_state_dict(state.server)
        self.engine.load_state_dict(state.engine)
        # a tail-truncated snapshot (fed_serve sidecar) carries fewer logs
        # than ``completed``; the counter is authoritative either way
        self.logs = [RoundLog(**lg) for lg in state.logs]
        self.completed = completed
        # robustness state (``.get``: absent from checkpoints written
        # before the fault/watchdog machinery existed)
        if self.faults is not None:
            self.faults.load_state_dict(sched.get("faults", {}))
        self.rollbacks = int(sched.get("rollbacks", 0))
        self._wd_best_acc = float(sched.get("wd_best_acc", 0.0))
        self._wd_loss_hist = [float(v)
                              for v in sched.get("wd_loss_hist", [])]
        if self._watchdog:
            # the restored boundary is (by construction) a healthy one —
            # re-arm the in-memory rollback point here so a fault right
            # after resume can still be rolled back
            self._wd_tree = self.snapshot().to_tree()

    # -------------------------------------------------- divergence watchdog
    def _wd_unhealthy(self, log: RoundLog) -> bool:
        """Health guard over a freshly assembled ``RoundLog``: non-finite
        metrics, an accuracy collapse vs the best healthy round, or a
        distill-loss spike vs the recent healthy median."""
        cfg = self.cfg
        vals = (log.mean_acc, log.local_loss, log.distill_loss)
        if not all(np.isfinite(v) for v in vals):
            return True
        if self._wd_best_acc > 0.0 and \
                log.mean_acc < self._wd_best_acc - cfg.watchdog_acc_drop:
            return True
        if self._wd_loss_hist and log.distill_loss > 0.0:
            ref = float(np.median(self._wd_loss_hist))
            if ref > 0.0 and log.distill_loss > cfg.watchdog_loss_factor * ref:
                return True
        return False

    def _wd_suspects(self, r: int) -> List[int]:
        """Top-suspect clients for round ``r`` from the server's normalized
        outlier scores (median ≈ 1 for honest clients): everyone past 3×
        the honest scale, else the single worst scorer."""
        dist = self.server.pop_round_outlier(r)
        if dist is None or dist.size == 0:
            return []
        bad = np.flatnonzero(~np.isfinite(dist) | (dist > 3.0))
        if bad.size == 0 and float(np.max(dist)) > 0.0:
            bad = np.asarray([int(np.argmax(dist))], int)
        return [int(i) for i in bad]

    def _wd_rollback(self, r: int) -> bool:
        """Roll the experiment back to the last healthy retirement and
        quarantine the round's top outlier suspects so the deterministic
        replay of round ``r`` runs without them. Returns False (caller
        retires the sick round as-is) when no restore point exists yet or
        the rollback budget is spent."""
        if self._wd_tree is None or \
                self.rollbacks >= self.cfg.watchdog_max_rollbacks:
            return False
        # capture BEFORE restore: the suspect scores live in server state
        # and the rollback counter rides the sched snapshot, both about to
        # be overwritten
        suspects = self._wd_suspects(r)
        prev = self.rollbacks
        from repro.fed.state import ExperimentState
        self.restore(ExperimentState.from_tree(self._wd_tree))
        self.rollbacks = prev + 1
        if suspects:
            # from round r (not r+1): the replay re-runs r itself, and the
            # fault trace is deterministic — without the quarantine the
            # same clients would poison the same round again
            self.server._ensure_fleet(self.engine.num_clients)
            self.server.quarantine(suspects, r, event_round=r)
        # re-take the restore point so it carries the quarantine and the
        # bumped rollback counter (restore() armed a pre-quarantine one)
        self._wd_tree = self.snapshot().to_tree()
        return True

    def _wd_note_healthy(self, log: RoundLog) -> None:
        """A round retired healthy: refresh the health references and
        re-take the in-memory restore point."""
        self._wd_best_acc = max(self._wd_best_acc, float(log.mean_acc))
        if log.distill_loss > 0.0:
            self._wd_loss_hist.append(float(log.distill_loss))
            del self._wd_loss_hist[:-8]
        self._wd_tree = self.snapshot().to_tree()

    # ------------------------------------------------------- node execution
    def _run_node(self, key: Tuple, st: _RoundState, deps) -> None:
        phase = key[0]
        self.trace.append(key)
        meta = _node_meta(key)
        before = tracing.counts()
        with tracing.span("phase." + phase, **meta) as sp:
            body = getattr(self, "_phase_" + phase)
            if phase in CLIENT_PHASES:
                # a concurrent node covers its own cohort, a serial node
                # every cohort in order
                body(st, key[2:] or range(len(self._cohort_pos)))
            else:
                body(st)
        st.phase_s[phase] = st.phase_s.get(phase, 0.0) + sp.s
        self._account(key, st, deps, sp.s)
        if phase == "report":
            # ingestion is an *event* driven by the arrival-trace clock: it
            # runs after the node is priced so each report's simulated
            # arrival time (the client's report-lane finish) is known, and
            # admission can replay them in arrival order. _ingest_reports
            # no-ops until the round's LAST report node has accumulated and
            # priced its cohorts' rows.
            with tracing.span("server.ingest", **meta) as sp:
                self._ingest_reports(st)
            st.phase_s[phase] += sp.s
        tracing.book(st.counters, before)

    def _report_part(self, st: _RoundState):
        """The round's *reporting* participants: ``st.rpart``, the training
        mask ``st.part`` narrowed by dropout and admission (None = same as
        ``st.part``)."""
        return st.rpart if st.rpart is not None else st.part

    def _per_client_cost(self, phase: str, epart) -> Optional[np.ndarray]:
        """Per-client base costs for a serial (engine-wide) client node
        when ``sim_phase_costs`` prices cohorts individually
        (``"phase@cohort"`` keys) — the serial baseline of the hetero-zoo
        benchmark must charge each architecture its own cost or the
        comparison against concurrent mode would be apples to oranges."""
        costs = self.sim_phase_costs
        if costs is None or not any("@" in k for k in costs):
            return None
        per = np.zeros((self.engine.num_clients,), float)
        for ci, pos in enumerate(self._cohort_pos):
            c = costs.get(f"{phase}@{ci}", costs.get(phase, 0.0))
            n = len(pos) if epart is None else int(epart[pos].sum())
            per[pos] = c / max(n, 1)
        return per

    def _account(self, key: Tuple, st: _RoundState, deps,
                 measured_s: float) -> None:
        """Price the node onto the simulated straggler timeline."""
        phase = key[0]
        ready_s = max((self._sim_end.get(d[:-1], 0.0)
                       for d in deps if d[-1] == "data"),
                      default=0.0)
        costs = self.sim_phase_costs
        if costs is None:
            base = measured_s
        elif len(key) > 2:
            # per-cohort nodes read "phase@cohort" (heterogeneous phase
            # costs), falling back to the shared per-phase cost
            base = costs.get(f"{phase}@{key[2]}", costs.get(phase, 0.0))
        else:
            base = costs.get(phase, 0.0)
        if phase in CLIENT_PHASES:
            epart = (st.part if phase == "local_train"
                     else self._report_part(st))
            if len(key) > 2:  # this node covers one cohort's lanes only
                pos = self._cohort_pos[key[2]]
                lane_part = np.zeros((self.engine.num_clients,), bool)
                lane_part[pos] = True if epart is None else epart[pos]
                n = int(lane_part.sum())
                per_client = base / max(n, 1)
            else:
                lane_part = epart
                n = (self.engine.num_clients if epart is None
                     else int(np.asarray(epart, bool).sum()))
                per_client = self._per_client_cost(phase, epart)
                if per_client is None:
                    per_client = base / max(n, 1)
            # measured host seconds cover every participant back-to-back;
            # deployed clients run in parallel, each paying its own share
            # scaled by its straggler speed. The arrival trace delays when
            # each client shows up for the round — it gates local_train
            # (the round's entry point); later phases inherit the skew
            # through the per-client lane occupancy.
            offsets = None
            if phase == "local_train":
                offsets = arrival_offsets(
                    self.engine.num_clients, st.r, seed=self.cfg.seed,
                    process=self.cfg.arrival_process,
                    spread=self.cfg.arrival_spread,
                    bursts=self.cfg.arrival_bursts)
            end = self.timeline.client_phase(lane_part, per_client,
                                             ready_s, offsets=offsets)
            if phase == "report":
                # pin simulated arrival times NOW: by the time the round
                # ingests (its last report node), other rounds' nodes may
                # already have advanced these lanes
                if st.report_arrival is None:
                    st.report_arrival = np.zeros(
                        (self.engine.num_clients,), float)
                ids = (np.arange(self.engine.num_clients)
                       if lane_part is None else np.flatnonzero(lane_part))
                st.report_arrival[ids] = self.timeline.client_free[ids]
        elif phase in ("aggregate", "server_distill"):
            end = self.timeline.server_phase(base, ready_s)
        else:  # eval: simulation-side measurement, free on the timeline
            end = ready_s
        end = float(end)  # np.float64 would poison RoundLog JSON dumps
        self._sim_end[key] = end
        st.sim_finish_s = end

    # --------------------------------------------------------- phase bodies
    def _draw_participants(self, st: _RoundState) -> None:
        """Participation sampling + churn for one round (deterministic in
        (seed, round) — drawn once whichever node runs first)."""
        cfg = self.cfg
        st.sampled = True
        if cfg.participation_fraction < 1.0:
            sizes = None
            if cfg.participation_policy == "weighted":
                sizes = np.asarray([len(c.y) for c in self.engine.clients],
                                   np.int64)
            st.part = sample_participants(
                st.r, self.engine.num_clients, cfg.participation_fraction,
                cfg.participation_policy, seed=cfg.seed, data_sizes=sizes)
        # per-round churn: an offline client is removed from the round
        # entirely — no training, no report — and drains through the
        # staleness machinery exactly like a sampled-out client
        online = online_mask(self.engine.num_clients, st.r, seed=cfg.seed,
                             churn=cfg.churn_prob)
        if online is not None:
            st.part = online if st.part is None else (st.part & online)
        # quarantined clients sit the round out like sampled-out ones,
        # draining through the staleness buffer — unless that would empty
        # the round entirely (the protocol needs at least one report)
        q = self.server.quarantine_mask(st.r)
        if q is not None:
            keep = ~q if st.part is None else (st.part & ~q)
            if keep.any():
                st.part = keep

    def _fill(self, fleet: List, ci: int, values) -> None:
        """Scatter cohort ``ci``'s values into a fleet-length list."""
        for p, v in zip(self._cohort_pos[ci], values):
            fleet[p] = v

    def _phase_local_train(self, st: _RoundState, cohorts) -> None:
        cfg = self.cfg
        if not st.sampled:  # round-level draw, at the round's first node
            self._draw_participants(st)
        if not st.local_losses:
            st.local_losses = [0.0] * self.engine.num_clients
        for ci in cohorts:
            self._fill(st.local_losses, ci, self.engine.cohort_local_train(
                ci, cfg.local_epochs, cfg.batch_size, participants=st.part))

    def _phase_report(self, st: _RoundState, cohorts) -> None:
        cfg = self.cfg
        num = self.engine.num_clients
        if st.reports_pending is None:  # round-level setup, first node
            st.reports_pending = len(self._cohort_pos)
            # mid-round dropout: these clients trained (local_train already
            # priced their lanes) but vanish before reporting — their fresh
            # report never reaches the server and they sit out the rest of
            # the round, riding the staleness buffer like any
            # non-participant. Drawn once per round; the reduced mask lives
            # in st.rpart so cohorts that have not trained yet still see
            # the full training mask in st.part
            dropped = dropout_mask(num, st.r, seed=cfg.seed,
                                   dropout=cfg.dropout_prob)
            if dropped is not None:
                st.rpart = (~dropped if st.part is None
                            else (st.part & ~dropped))
            if not self.method.data_free:
                # the round's shared proxy batch: one draw, round-ordered by
                # the cross-round report order deps, so the server rng
                # stream is the same under either graph
                st.idx = self.server.select_indices(cfg.proxy_batch)
                st.px = self.server.proxy.x[st.idx]
                st.powner = self.server.proxy.owner[st.idx]
        part = self._report_part(st)
        for ci in cohorts:
            pos = self._cohort_pos[ci]
            if self.method.data_free:  # FKD/PLS upload class-wise means
                mc = self.engine.cohort_classwise_report(ci,
                                                         participants=part)
                if st.means_counts is None:
                    k = self.engine.clients[0].num_classes
                    zero = (np.zeros((k, k), np.float32),
                            np.zeros((k,), np.float32))
                    st.means_counts = [zero] * num
                self._fill(st.means_counts, ci, mc)
                continue
            # computed here (the client-side work) but ingested
            # post-pricing in _ingest_reports, once simulated arrival times
            # exist
            lg, mk = self.engine.cohort_report(ci, st.px, st.powner,
                                               participants=part)
            if st.report_logits is None:
                t, k = lg.shape[1], lg.shape[2]
                st.report_logits = np.zeros((num, t, k), np.float32)
                st.report_masks = np.zeros((num, t), bool)
            st.report_logits[pos] = lg
            st.report_masks[pos] = mk
        st.reports_pending -= len(cohorts)

    def _ingest_reports(self, st: _RoundState) -> None:
        """Server-side report ingestion, as an arrival-ordered event.

        Runs right after the report node is priced onto the timeline. With
        ``max_pending_reports > 0`` the server admits reports in simulated
        arrival order (each client's report-lane finish time, ties broken
        by client id) until the in-flight budget is full; overflow clients
        are demoted to non-participants for the rest of the round and drain
        through the staleness machinery exactly like dropouts — their
        buffer entries keep aging forward, so ages never go negative. With
        the cap at 0 (default) admission is the identity and the legacy
        lockstep byte stream is preserved bit-for-bit.

        The round's rows accumulate across its report nodes
        (``st.report_logits``/``st.report_masks``) and ingestion fires
        once, at the round's last report node — arrival times were pinned
        per node at pricing time (``st.report_arrival``), so admission
        order is independent of how cohorts interleaved."""
        if self.method.data_free or st.reports_pending:
            return  # nothing to ingest, or cohorts still reporting
        logits, masks = st.report_logits, st.report_masks
        st.report_logits = st.report_masks = None
        cfg = self.cfg
        part = self._report_part(st)
        if self.faults is not None:
            # the fault trace corrupts what faulty clients *send* — after
            # training, before the server sees anything. Deterministic in
            # (seed, round, client), so every engine injects identically.
            logits, masks = self.faults.corrupt_reports(
                st.r, logits, masks, part)
        if self.server.max_pending_reports > 0:
            ids = (np.arange(self.engine.num_clients)
                   if part is None else np.flatnonzero(part))
            arrival = st.report_arrival[ids]
            # primary key: simulated arrival; secondary: client id
            ordered = ids[np.lexsort((ids, arrival))]
            admitted_ids = self.server.admit_reports(st.r, ordered)
            if admitted_ids.size < ids.size:
                admitted = np.zeros((self.engine.num_clients,), bool)
                admitted[admitted_ids] = True
                part = st.rpart = admitted
        # ID fraction over the clients that actually reported; stale rows
        # merged at aggregation additionally carry reuse
        st.id_frac = (float(masks.mean()) if part is None
                      else (float(masks[part].mean())
                            if part.any() else 0.0))
        st.id_fracs = [float(v) for v in masks.mean(axis=1)]
        self.server.ingest_reports(st.r, part, st.idx, logits, masks,
                                   decay=cfg.staleness_decay,
                                   entropy_filter=self.method.server_filter)

    def _phase_aggregate(self, st: _RoundState) -> None:
        if self.method.data_free:
            if self.faults is not None:
                # classwise payloads are untouched between report and
                # aggregate, so injecting here is payload-equivalent to
                # injecting at report time — and single-sited across the
                # serial and concurrent-cohort report paths
                st.means_counts = self.faults.corrupt_classwise(
                    st.r, st.means_counts, self._report_part(st))
            st.teacher_by_class, st.valid_by_class = \
                self.server.aggregate_classwise(
                    st.means_counts, count_weighted=self.method.count_weighted,
                    uploaded_rows=self._report_part(st),
                    round_idx=st.r)
            st.means_counts = None
            return
        st.teacher, st.valid, st.mean_staleness = self.server.aggregate_round(
            st.r, sharpen=self.method.sharpen,
            entropy_filter=self.method.server_filter)

    def _phase_server_distill(self, st: _RoundState) -> None:
        """FedDF: train the server's central student on the round's proxy
        batch against the fused ensemble teacher (the same teacher/validity
        the clients are about to distill from)."""
        cfg = self.cfg
        epochs = cfg.server_distill_epochs or cfg.distill_epochs
        st.server_distill_loss = self.server.ensemble_distill(
            st.px, st.teacher, st.valid, epochs=epochs,
            batch_size=cfg.batch_size)

    def _phase_distill(self, st: _RoundState, cohorts) -> None:
        cfg = self.cfg
        part = self._report_part(st)
        if not st.distill_losses:
            st.distill_losses = [0.0] * self.engine.num_clients
        w = None if self.method.data_free else st.valid.astype(np.float32)
        for ci in cohorts:
            if self.method.data_free:
                losses = self.engine.cohort_distill_private(
                    ci, st.teacher_by_class, st.valid_by_class,
                    cfg.distill_epochs, cfg.batch_size, participants=part)
            else:
                losses = self.engine.cohort_distill(
                    ci, st.px, st.teacher, w, cfg.distill_epochs,
                    cfg.batch_size, participants=part)
            self._fill(st.distill_losses, ci, losses)

    def _phase_eval(self, st: _RoundState) -> None:
        st.accs = self.engine.evaluate(self.x_test, self.y_test)
        if self.server.student is not None:
            st.server_student_acc = self.server.evaluate_student(
                self.x_test, self.y_test)

    def _finish_round(self, st: _RoundState) -> RoundLog:
        # served-model freshness: how long the model this round replaces
        # was the one a user query would hit (sim seconds since the last
        # retirement; round 0 measures from service start). Overlap rounds
        # retire in round order on the host but may finish out of order on
        # the sim timeline — the interval clamps at 0 there, and the
        # reference only moves forward.
        age = max(0.0, st.sim_finish_s - self._last_retire_s)
        self._last_retire_s = max(self._last_retire_s, st.sim_finish_s)
        part = self._report_part(st)
        scrubbed = int(self.server.pop_scrubbed(st.r))
        newly_q = self.server.pop_quarantined(st.r)
        return RoundLog(
            round=st.r,
            mean_acc=float(np.mean(st.accs)),
            accs=st.accs,
            local_loss=float(np.mean(st.local_losses)),
            distill_loss=(float(np.mean(st.distill_losses))
                          if st.distill_losses else 0.0),
            id_fraction=st.id_frac,
            client_id_fractions=st.id_fracs,
            bytes_up=self.server.bytes_received,
            bytes_down=self.server.bytes_broadcast,
            wall_s=sum(st.phase_s.values()),
            participants=(None if part is None
                          else [int(i) for i in np.flatnonzero(part)]),
            mean_staleness=st.mean_staleness,
            phase_s=dict(st.phase_s),
            counters=dict(st.counters),
            sim_finish_s=st.sim_finish_s,
            served_model_age_s=age,
            server_distill_loss=st.server_distill_loss,
            server_student_acc=st.server_student_acc,
            scrubbed_rows=scrubbed,
            quarantined=(newly_q if newly_q else None),
            rollbacks=self.rollbacks,
        )
