"""Algorithm 1 — the EdgeFD round protocol, generic over Method and engine.

``run_round`` executes one training-phase iteration (lines 12–17);
``run_experiment`` wires data → clients → rounds → evaluation and returns
a result record (accuracy history per client + communication accounting).

Both are thin drivers over the *round phase graph* in
``repro.fed.scheduler``: a round decomposes into named phase nodes
(``local_train → report → aggregate → distill → eval``) with declared
data dependencies, and ``FedConfig.round_mode`` selects how the graph is
executed — ``sync`` replays the lockstep Algorithm-1 order bit-for-bit,
``overlap`` pipelines up to ``max_inflight`` rounds (round r+1 trains
while round r aggregates through the staleness buffer).

The phase bodies are written against a small *client engine* interface so
the same graph drives two execution strategies:

  * ``LoopEngine`` (here) — iterate a ``List[Client]`` one at a time.
    Always correct, required for heterogeneous architectures, slow: one
    host↔device round-trip per client per step.
  * ``CohortEngine`` (``repro.fed.cohort``) — stack homogeneous clients
    into leading-axis pytrees and run every per-client op under ``vmap``
    (one compiled call per round phase for the whole cohort).

Both engines expose one contract: ``cohort_positions()`` and one call per
client phase and cohort (``cohort_local_train``, ``cohort_report``,
``cohort_classwise_report``, ``cohort_distill``,
``cohort_distill_private``), one fleet-wide ``evaluate``, ``learn_dres``
and the ``state_dict``/``load_state_dict``/``sync_to_clients`` service
hooks. Both produce identical ``RoundLog`` streams for the same seed (see
``tests/test_cohort_parity.py``); ``FedConfig.engine`` selects one.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.common.types import FedConfig
from repro.core.methods import Method, get_method

if TYPE_CHECKING:  # avoid core <-> fed import cycle at runtime
    from repro.fed.client import Client
    from repro.fed.server import Server


@dataclasses.dataclass
class RoundLog:
    round: int
    mean_acc: float
    accs: List[float]
    local_loss: float
    distill_loss: float
    id_fraction: float          # fraction of (client, sample) pairs kept ID
    bytes_up: int
    bytes_down: int
    wall_s: float
    # partial participation (repro.fed.participation): the client ids that
    # trained/reported this round (None = every client, the legacy setting)
    # and the mean age of the aggregated reports in rounds (0.0 = all fresh)
    participants: Optional[List[int]] = None
    mean_staleness: float = 0.0
    # per-phase host wall-clock breakdown (repro.fed.scheduler phase nodes;
    # wall_s is their sum)
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    # what the round's phase nodes did beyond their time
    # (repro.common.tracing): device->host reads of the engine
    # ("engine.syncs") and of the server ("server.syncs"), the client-size
    # groups the cohort engine's plans packed ("engine.plan_groups"), and
    # programs JAX built ("compiles")
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    # when this round retired on the simulated straggler timeline
    # (repro.fed.clock) — the axis on which round_mode="overlap" beats
    # "sync"; see benchmarks/async_rounds.py
    sim_finish_s: float = 0.0
    # served-model freshness: a user query served between model refreshes
    # hits the *last retired* round's model, so when round r retires at
    # sim_finish_s the model being replaced has been serving since the
    # previous retirement — this field is that serving interval in
    # simulated seconds (the maximum sim-time age a query could have hit;
    # round 0 measures from service start, i.e. the init model's tenure).
    # Overlap mode retires rounds faster than lockstep, so this is the
    # serving-facing win of the pipelined scheduler (launch/fed_serve.py).
    served_model_age_s: float = 0.0
    # FedDF-style ensemble server (method="server_distill"): the server
    # student's mean KD loss on the proxy batch this round, and its test
    # accuracy measured at the eval phase (None = no student attached)
    server_distill_loss: float = 0.0
    server_student_acc: Optional[float] = None
    # defense stack (repro.fed.server / repro.fed.scheduler): report rows
    # the sanitize pass scrubbed this round, clients quarantined on this
    # round's evidence (None = trust tracking off), and the cumulative
    # watchdog rollback count as of this round's retirement
    scrubbed_rows: int = 0
    quarantined: Optional[List[int]] = None
    rollbacks: int = 0
    # per-client share of the proxy batch its filter kept ID (None = the
    # engine reported no masks)
    client_id_fractions: Optional[List[float]] = None


@dataclasses.dataclass
class ExperimentResult:
    method: str
    scenario: str
    rounds: List[RoundLog]

    @property
    def final_acc(self) -> float:
        return self.rounds[-1].mean_acc if self.rounds else 0.0

    @property
    def best_acc(self) -> float:
        return max(r.mean_acc for r in self.rounds) if self.rounds else 0.0


# ---------------------------------------------------------------------------
# Client engines
# ---------------------------------------------------------------------------

class LoopEngine:
    """Reference engine: drives clients one by one (heterogeneous-safe).

    This is the seed implementation of the round phases factored behind the
    engine interface (one behavioral delta: clients with fewer samples than
    the batch size now train one short batch per epoch instead of silently
    skipping local training — see ``repro.fed.batching``); ``CohortEngine``
    must match its outputs up to float tolerance.

    It groups clients into cohorts by ``arch_key`` exactly like
    ``CohortEngine``, so loop == cohort round-log parity holds node for
    node; every ``cohort_*`` call returns values aligned to that cohort's
    client positions and the scheduler scatters them back into
    fleet-length structures.
    """

    def __init__(self, clients: Sequence["Client"]):
        self.clients = list(clients)
        # client positions per cohort, grouped by ``arch_key`` in first-
        # appearance order (clients without an arch_key are singletons)
        groups: Dict = {}
        for pos, c in enumerate(self.clients):
            key = c.arch_key if c.arch_key is not None else ("solo", pos)
            groups.setdefault(key, []).append(pos)
        self._cohort_pos = [np.asarray(p, int) for p in groups.values()]

    @property
    def num_clients(self) -> int:
        return len(self.clients)

    def _part(self, participants) -> np.ndarray:
        """Normalize a participation mask (None = every client).

        A sampled-out client is skipped entirely: no local training, no
        proxy logits, no filter mask, and — critically for loop↔cohort
        parity — no consumption of its private rng stream.
        """
        if participants is None:
            return np.ones((len(self.clients),), bool)
        part = np.asarray(participants, bool)
        if part.shape != (len(self.clients),):
            raise ValueError(
                f"participation mask shape {part.shape} != "
                f"({len(self.clients)},)")
        return part

    def learn_dres(self, key) -> None:
        for i, c in enumerate(self.clients):
            c.learn_dre(jax.random.fold_in(key, i))

    # ------------------------------------------------ per-cohort entry points
    def cohort_positions(self) -> List[np.ndarray]:
        return self._cohort_pos

    def cohort_local_train(self, ci: int, epochs: int, batch_size: int,
                           participants=None) -> List[float]:
        part = self._part(participants)
        return [self.clients[p].local_train(epochs, batch_size)
                if part[p] else 0.0
                for p in self.cohort_positions()[ci]]

    def cohort_classwise_report(self, ci: int, participants=None):
        part = self._part(participants)
        k = self.clients[0].num_classes
        skipped = (np.zeros((k, k), np.float32), np.zeros((k,), np.float32))
        return [self.clients[p].classwise_means() if part[p] else skipped
                for p in self.cohort_positions()[ci]]

    def cohort_report(self, ci: int, px, powner, participants=None):
        """Returns (logits (m, t, K), masks (m, t)) for cohort ``ci``'s m
        clients; sampled-out clients get zero logits and all-False masks
        (the staleness buffer replaces those rows with their last
        report)."""
        part = self._part(participants)
        pos = self.cohort_positions()[ci]
        t = len(px)
        k = self.clients[0].num_classes
        logits = np.zeros((len(pos), t, k), np.float32)
        masks = np.zeros((len(pos), t), bool)
        for j, p in enumerate(pos):
            if not part[p]:
                continue
            c = self.clients[p]
            logits[j] = np.asarray(c.proxy_logits(px))
            masks[j] = np.asarray(c.filter_mask(px, powner).mask)
        return logits, masks

    def cohort_distill(self, ci: int, px, teacher, weight, epochs: int,
                       batch_size: int, participants=None) -> List[float]:
        part = self._part(participants)
        return [self.clients[p].distill(px, teacher, weight, epochs,
                                        batch_size)
                if part[p] else 0.0
                for p in self.cohort_positions()[ci]]

    def cohort_distill_private(self, ci: int, teacher_by_class,
                               valid_by_class, epochs: int, batch_size: int,
                               participants=None) -> List[float]:
        part = self._part(participants)
        out = []
        for p in self.cohort_positions()[ci]:
            c = self.clients[p]
            if not part[p]:
                out.append(0.0)
                continue
            teacher = teacher_by_class[c.y]                # (n, K)
            w = valid_by_class[c.y].astype(np.float32)
            out.append(c.distill(c.x, teacher, w, epochs, batch_size))
        return out

    def evaluate(self, x_test, y_test) -> List[float]:
        return [c.evaluate(x_test, y_test) for c in self.clients]

    # ------------------------------------------------- resumable service
    def state_dict(self) -> Dict:
        """Per-client mutable state (params, opt-state, rng) in the shared
        engine checkpoint format (``repro.fed.state``) — portable across
        loop/cohort/mesh engines."""
        from repro.fed.state import clients_state_dict
        return clients_state_dict(self.clients)

    def load_state_dict(self, sd: Dict) -> None:
        from repro.fed.state import load_clients_state_dict
        load_clients_state_dict(self.clients, sd)

    def sync_to_clients(self) -> None:
        """No-op: the loop engine trains the ``Client`` objects in place."""


def as_engine(clients_or_engine, engine: str = "loop", *,
              num_devices: int = 0, mesh_axis: str = "clients",
              wave_size: int = 0, model_shards: int = 0):
    """Coerce a plain client list (the historical API) into an engine.

    ``num_devices``/``mesh_axis`` build the cohort engine's client mesh
    (``repro.fed.mesh``): 0 = unsharded, -1 = all devices, N > 0 = exactly N.
    ``model_shards`` > 0 folds those same devices into a 2-D
    ``(clients, model)`` mesh so each stacked client's weight matrices are
    model-sharded too; 0 keeps the 1-D mesh bit-for-bit.
    ``wave_size`` streams the cohort client axis through the device in
    fixed-size waves (``repro.fed.cohort``); 0 = whole axis resident.
    """
    if hasattr(clients_or_engine, "cohort_positions"):
        if wave_size and not getattr(clients_or_engine, "wave_size", 0):
            warnings.warn(
                f"wave_size={wave_size} requested but a pre-built engine "
                "without wave streaming was supplied; it will run as "
                "constructed — build it via simulator.build_engine(...) "
                "or pass the raw client list to honor the config")
        if num_devices and getattr(clients_or_engine, "mesh", None) is None:
            # a pre-built engine runs as constructed; say so instead of
            # letting the config silently promise a mesh that isn't there
            warnings.warn(
                f"num_devices={num_devices} requested but a pre-built "
                "engine without a device mesh was supplied; it will run "
                "as constructed — build it via simulator.build_engine(...) "
                "or pass the raw client list to honor the config")
        return clients_or_engine
    if engine == "cohort":
        # lazy imports: core must not import fed at load time
        from repro.fed.cohort import CohortEngine
        from repro.fed.mesh import build_client_mesh
        mesh = build_client_mesh(num_devices, mesh_axis,
                                 model_shards=model_shards)
        return CohortEngine(clients_or_engine, mesh=mesh, mesh_axis=mesh_axis,
                            wave_size=wave_size)
    if engine != "loop":
        raise ValueError(f"unknown engine {engine!r}; known: loop, cohort")
    if num_devices:
        raise ValueError("num_devices requires engine='cohort' (the loop "
                         "engine drives one client at a time)")
    if wave_size:
        raise ValueError("wave_size requires engine='cohort' (the loop "
                         "engine never stacks a client axis to stream)")
    if model_shards:
        raise ValueError("model_shards requires engine='cohort' (the loop "
                         "engine holds each client's params on one device)")
    return LoopEngine(clients_or_engine)


def engine_from_config(clients_or_engine, cfg: FedConfig):
    """``as_engine`` with every engine-relevant ``FedConfig`` field applied.

    The single cfg→engine mapping — ``run_round``, ``run_experiment`` and
    ``simulator.build_engine`` all route through here so a new
    engine-relevant config field cannot be wired into one and not the
    others."""
    return as_engine(clients_or_engine, cfg.engine,
                     num_devices=cfg.num_devices, mesh_axis=cfg.mesh_axis,
                     wave_size=cfg.wave_size,
                     model_shards=cfg.model_shards)


# ---------------------------------------------------------------------------
# Protocol — thin drivers over the phase-graph scheduler
# ---------------------------------------------------------------------------

def _scheduler(engine, server: "Server", method: Method, cfg: FedConfig,
               x_test, y_test):
    # lazy import, like as_engine: core must not import fed at load time
    from repro.fed.scheduler import RoundScheduler
    return RoundScheduler(engine, server, method, cfg, x_test, y_test)


def run_round(r: int, clients, server: "Server", method: Method,
              cfg: FedConfig, x_test, y_test) -> RoundLog:
    """One round through the phase graph.

    A single round cannot overlap with anything, so ``round_mode="overlap"``
    degenerates to the sync phase order here — multi-round callers who want
    pipelining should go through ``run_experiment`` (one scheduler instance
    spanning all rounds). The scheduler validates the config on every entry
    path, so a direct caller cannot slip a zero/negative/overful
    ``participation_fraction`` past the protocol.

    NOTE: a raw client list must honor ``cfg.engine`` — an engine built
    here dies with this call, so its state must flow back to the Client
    objects below. That also means a raw list re-stacks and re-jits the
    cohort phases every round — multi-round callers should build the
    engine once (``simulator.build_engine`` / ``run_experiment``) and pass
    it in.
    """
    engine = engine_from_config(clients, cfg)
    transient = engine is not clients
    log = _scheduler(engine, server, method, cfg, x_test, y_test
                     ).run_rounds(r, 1)[0]
    if transient:
        # engines that train on stacked device state (CohortEngine) must
        # write params/opt-state back before being discarded, or raw-list
        # callers would silently lose every round's training
        engine.sync_to_clients()
    return log


def run_experiment(clients, server: "Server", method_name: str,
                   cfg: FedConfig, x_test, y_test,
                   progress: Optional[Callable[[RoundLog], None]] = None
                   ) -> ExperimentResult:
    engine = engine_from_config(clients, cfg)
    method = get_method(method_name)
    key = jax.random.PRNGKey(cfg.seed)
    if method.client_filter != "none":                     # Initialization
        engine.learn_dres(key)
    logs = _scheduler(engine, server, method, cfg, x_test, y_test
                      ).run_rounds(0, cfg.rounds, progress=progress)
    if engine is not clients:
        # raw-list callers hold only the Client objects — an engine built
        # here must write its trained stacked state back before vanishing
        engine.sync_to_clients()
    return ExperimentResult(method=method_name, scenario=cfg.scenario,
                            rounds=logs)
