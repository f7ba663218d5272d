"""Heterogeneous model zoos: mixed populations, concurrent-cohort
scheduling and the FedDF ensemble server.

Gates, in order of importance:

* a single-cohort population under ``concurrent_cohorts=True`` replays
  the serial phase graph **bit-for-bit** (pinned against
  ``tests/data/golden_rounds.json``, the same goldens the scheduler and
  kernel-dispatch layers certify against);
* on the mixed three-width zoo, serial and concurrent sync runs are
  numerically identical (only the simulated timeline moves), and the
  loop and cohort engines agree within the engine tolerance under both
  sync and overlap;
* the interleaved trace is deterministic in the seed, and under overlap
  a cohort's round r+1 training genuinely overlaps round r's server
  phases;
* the simulated makespan of the concurrent graph beats the serial graph
  under anti-correlated per-cohort costs;
* ``method="server_distill"`` trains the server's central student every
  round and reports its accuracy.
"""

import json
import pathlib

import jax
import numpy as np
import pytest

from repro.common.types import FedConfig
from repro.core.methods import get_method
from repro.fed import simulator
from repro.fed.scheduler import RoundScheduler, round_phases
from repro.fed.simulator import resolve_zoo

GOLDEN_PATH = pathlib.Path(__file__).parent / "data" / "golden_rounds.json"
TOL = dict(rtol=0.0, atol=1e-5)


def _cfg(**kw):
    base = dict(
        num_clients=6,
        rounds=2,
        method="fedmd",
        scenario="strong",
        proxy_batch=64,
        batch_size=32,
        lr=1e-2,
        seed=0,
        engine="cohort",
        zoo="mixed",
        round_mode="sync",
    )
    base.update(kw)
    return FedConfig(**base)


def _build_sched(cfg, **sched_kw):
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=600, n_test=200, mlp_hidden=(16,)
    )
    engine = simulator.build_engine(clients, cfg)
    method = get_method(cfg.method)
    if method.client_filter != "none":
        engine.learn_dres(jax.random.PRNGKey(cfg.seed))
    return RoundScheduler(engine, server, method, cfg, x_test, y_test, **sched_kw)


def _rows(res):
    return [
        (r.accs, r.mean_acc, r.local_loss, r.distill_loss, r.id_fraction)
        for r in res.rounds
    ]


# -------------------------------------------------------- zoo resolution


def test_resolve_zoo(monkeypatch):
    monkeypatch.delenv("REPRO_ZOO", raising=False)
    assert resolve_zoo("auto") == "shared"
    assert resolve_zoo("shared") == "shared"
    assert resolve_zoo("mixed") == "mixed"
    monkeypatch.setenv("REPRO_ZOO", "mixed")
    assert resolve_zoo("auto") == "mixed"
    assert resolve_zoo("shared") == "shared"  # explicit config wins
    monkeypatch.setenv("REPRO_ZOO", "auto")
    assert resolve_zoo("auto") == "shared"
    with pytest.raises(ValueError):
        resolve_zoo("nonsense")
    monkeypatch.setenv("REPRO_ZOO", "nonsense")
    with pytest.raises(ValueError):
        resolve_zoo("auto")


def test_mixed_zoo_builds_three_cohorts():
    cfg = _cfg()
    clients, _, _, _ = simulator.build_experiment(
        cfg, "mnist_feat", n_train=600, n_test=200, mlp_hidden=(16,)
    )
    keys = {c.arch_key for c in clients}
    assert len(keys) == 3  # three width variants, cycled by cid % 3
    engine = simulator.build_engine(clients, cfg)
    assert len(engine.cohort_positions()) == 3


def test_run_rejects_bad_zoo():
    with pytest.raises(ValueError):
        simulator.run(_cfg(zoo="bogus"), "mnist_feat", n_train=64, n_test=32)


# -------------------------------------------- golden (single-cohort sync)


def test_single_cohort_concurrent_matches_golden_bit_for_bit():
    """With one architecture cohort the concurrent graph must degenerate
    to exactly the serial schedule: same goldens as the lockstep tree,
    bit for bit. round_mode/kernel_backend/zoo are pinned so the test
    also holds under the overlap/pallas/mixed CI matrix entries."""
    golden = json.loads(GOLDEN_PATH.read_text())["edgefd_cohort"]
    cfg = FedConfig(
        num_clients=4,
        rounds=2,
        method="edgefd",
        scenario="strong",
        proxy_batch=128,
        batch_size=32,
        seed=0,
        engine="cohort",
        zoo="shared",
        round_mode="sync",
        kernel_backend="jnp",
        concurrent_cohorts=True,
    )
    res = simulator.run(cfg, "mnist_feat", n_train=600, n_test=200)
    assert len(res.rounds) == len(golden)
    for g, n in zip(golden, res.rounds):
        assert g["accs"] == n.accs
        assert g["mean_acc"] == n.mean_acc
        assert g["local_loss"] == n.local_loss
        assert g["distill_loss"] == n.distill_loss
        assert g["id_fraction"] == n.id_fraction
        assert g["bytes_up"] == n.bytes_up
        assert g["bytes_down"] == n.bytes_down


# ----------------------------------------------------- numerics parity


@pytest.mark.parametrize(
    "part_kw",
    [
        dict(),
        dict(
            participation_fraction=0.75,
            staleness_decay=0.5,
            dropout_prob=0.2,
            max_pending_reports=3,
        ),
    ],
    ids=["full", "dropout_admission"],
)
def test_sync_concurrent_is_bitwise_serial_on_mixed_zoo(part_kw):
    """Sync mode: the concurrent graph reorders nothing the numerics can
    see (order deps pin the host order), so serial and concurrent runs
    of the same mixed-zoo experiment are bit-identical — also when
    sampling and mid-round dropout shrink the round's reporting
    participants. The admission cap runs too, but admits every report in
    these two rounds: a cap that cuts follows simulated arrival order,
    which the two graphs price differently."""
    a = simulator.run(_cfg(**part_kw), "mnist_feat", n_train=600, n_test=200)
    b = simulator.run(
        _cfg(concurrent_cohorts=True, **part_kw),
        "mnist_feat",
        n_train=600,
        n_test=200,
    )
    assert _rows(a) == _rows(b)
    assert [r.participants for r in a.rounds] == [r.participants for r in b.rounds]


@pytest.mark.parametrize(
    "mode_kw",
    [
        dict(round_mode="sync"),
        dict(
            round_mode="overlap",
            max_inflight=2,
            participation_fraction=0.75,
            staleness_decay=0.5,
        ),
    ],
    ids=["sync", "overlap"],
)
def test_loop_cohort_parity_on_mixed_zoo_concurrent(mode_kw):
    """The engines must agree on the mixed zoo with concurrent cohorts —
    the loop engine groups clients by arch_key into the same cohorts the
    cohort engine stacks."""
    a = simulator.run(
        _cfg(engine="loop", concurrent_cohorts=True, **mode_kw),
        "mnist_feat",
        n_train=600,
        n_test=200,
    )
    b = simulator.run(
        _cfg(engine="cohort", concurrent_cohorts=True, **mode_kw),
        "mnist_feat",
        n_train=600,
        n_test=200,
    )
    for ra, rb in zip(a.rounds, b.rounds):
        np.testing.assert_allclose(ra.accs, rb.accs, **TOL)
        np.testing.assert_allclose(ra.local_loss, rb.local_loss, **TOL)
        np.testing.assert_allclose(ra.distill_loss, rb.distill_loss, **TOL)
        assert ra.id_fraction == rb.id_fraction
        assert ra.participants == rb.participants


# ------------------------------------------------------ trace properties


def _overlap_cfg(**kw):
    return _cfg(
        rounds=3,
        round_mode="overlap",
        max_inflight=2,
        participation_fraction=0.75,
        staleness_decay=0.5,
        concurrent_cohorts=True,
        **kw,
    )


def test_interleaved_trace_is_seed_deterministic():
    traces = []
    for _ in range(2):
        sched = _build_sched(_overlap_cfg())
        sched.run_rounds(0, 3)
        traces.append(list(sched.trace))
    assert traces[0] == traces[1]
    # per-cohort nodes actually exist in the trace
    assert any(len(k) == 3 for k in traces[0])


def test_overlap_interleaves_cohort_rounds():
    """Under overlap a cohort's round-1 training must run before round
    0's aggregate — per-cohort admission is the whole point."""
    sched = _build_sched(_overlap_cfg())
    sched.run_rounds(0, 3)
    t = sched.trace
    agg0 = t.index(("aggregate", 0))
    assert any(
        t.index(("local_train", 1, ci)) < agg0
        for ci in range(3)
        if ("local_train", 1, ci) in t
    )


def test_concurrent_beats_serial_on_sim_clock():
    """Anti-correlated per-cohort costs: the serial graph pays
    sum-over-phases of the slowest cohort, concurrent pays roughly the
    slowest chain — its makespan must be strictly smaller."""
    costs = {
        "local_train@0": 2.0,
        "local_train@1": 0.5,
        "report": 0.1,
        "aggregate": 0.2,
        "distill@0": 0.5,
        "distill@1": 2.0,
        "eval": 0.0,
    }
    spans = {}
    for concurrent in (False, True):
        cfg = _cfg(concurrent_cohorts=concurrent, straggler_factor=1.0)
        sched = _build_sched(cfg, sim_phase_costs=costs)
        logs = sched.run_rounds(0, cfg.rounds)
        spans[concurrent] = max(lg.sim_finish_s for lg in logs)
    assert spans[True] < spans[False]


# ------------------------------------------------- FedDF ensemble server


def test_server_distill_trains_a_student():
    cfg = _cfg(
        method="server_distill",
        rounds=2,
        scenario="iid",
        server_distill_epochs=8,
    )
    assert "server_distill" in round_phases(get_method("server_distill"))
    res = simulator.run(cfg, "mnist_feat", n_train=600, n_test=200)
    for lg in res.rounds:
        assert lg.server_distill_loss > 0.0
        assert 0.0 <= lg.server_student_acc <= 1.0
    # the student must actually learn from the ensemble: round-1 accuracy
    # above chance on the 10-class problem
    assert res.rounds[-1].server_student_acc > 0.15


def test_server_distill_concurrent_matches_serial():
    kw = dict(method="server_distill", rounds=2, server_distill_epochs=2)
    a = simulator.run(_cfg(**kw), "mnist_feat", n_train=600, n_test=200)
    b = simulator.run(
        _cfg(concurrent_cohorts=True, **kw),
        "mnist_feat",
        n_train=600,
        n_test=200,
    )
    assert _rows(a) == _rows(b)
    for ra, rb in zip(a.rounds, b.rounds):
        assert ra.server_distill_loss == rb.server_distill_loss
        assert ra.server_student_acc == rb.server_student_acc
