"""Round phase-graph scheduler (repro.fed.scheduler) + straggler clock.

Gates, in order of importance:

  * ``round_mode="sync"`` (the default) reproduces the pre-scheduler round
    logs **bit-for-bit** (pinned against ``tests/data/golden_rounds.json``,
    the same goldens the kernel-dispatch layer certifies against);
  * under ``round_mode="overlap"`` the loop and cohort engines (and the
    mesh-sharded cohort engine, via the forced-device harness) produce
    identical round logs — the pipeline schedule is engine-independent;
  * the overlap schedule is deterministic in the seed: same seed ⇒ same
    execution trace, same logs, same straggler speeds;
  * the simulated straggler timeline prices overlap strictly below sync
    for the same per-phase costs;
  * ``run_round`` rejects a zero/negative/overful participation fraction
    on every entry path.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.common.types import FedConfig
from repro.core.methods import get_method
from repro.core.protocol import run_round
from repro.fed import simulator
from repro.fed.clock import SimTimeline, client_speeds
from repro.fed.scheduler import (RoundScheduler, resolve_round_mode,
                                 round_phases, validate_config)

GOLDEN_PATH = pathlib.Path(__file__).parent / "data" / "golden_rounds.json"
TOL = dict(rtol=0.0, atol=1e-5)


def _cfg(engine="loop", **kw):
    base = dict(num_clients=5, rounds=3, method="edgefd", scenario="strong",
                proxy_batch=120, batch_size=32, lr=1e-2, seed=0,
                engine=engine)
    base.update(kw)
    return FedConfig(**base)


def _overlap_cfg(engine="loop", **kw):
    base = dict(round_mode="overlap", max_inflight=2,
                participation_fraction=0.6, staleness_decay=0.5)
    base.update(kw)
    return _cfg(engine, **base)


def _build_scheduler(cfg, **sched_kw):
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    engine = simulator.build_engine(clients, cfg)
    engine.learn_dres(jax.random.PRNGKey(cfg.seed))
    return RoundScheduler(engine, server, get_method(cfg.method), cfg,
                          x_test, y_test, **sched_kw)


# ----------------------------------------------------------- golden (sync)

def test_sync_mode_reproduces_golden_logs_bit_for_bit():
    """The scheduler's sync path must replay the lockstep Algorithm-1
    order exactly: same goldens as the pre-scheduler tree, bit for bit.
    round_mode/kernel_backend/zoo are pinned so the test also holds under
    the REPRO_ROUND_MODE=overlap / REPRO_KERNEL_BACKEND=pallas /
    REPRO_ZOO=mixed CI entries — on a clean CPU host these pins ARE the
    defaults."""
    golden = json.loads(GOLDEN_PATH.read_text())
    for name, method, engine in [("edgefd_loop", "edgefd", "loop"),
                                 ("edgefd_cohort", "edgefd", "cohort")]:
        cfg = FedConfig(num_clients=4, rounds=2, method=method,
                        scenario="strong", proxy_batch=128, batch_size=32,
                        seed=0, engine=engine, round_mode="sync",
                        kernel_backend="jnp", zoo="shared")
        res = simulator.run(cfg, "mnist_feat", n_train=600, n_test=200)
        assert len(res.rounds) == len(golden[name])
        for g, n in zip(golden[name], res.rounds):
            assert g["accs"] == n.accs, (name, n.round)
            assert g["mean_acc"] == n.mean_acc
            assert g["local_loss"] == n.local_loss
            assert g["distill_loss"] == n.distill_loss
            assert g["id_fraction"] == n.id_fraction
            assert g["bytes_up"] == n.bytes_up
            assert g["bytes_down"] == n.bytes_down


def test_sync_trace_is_lockstep():
    cfg = _cfg(rounds=2, round_mode="sync")
    sched = _build_scheduler(cfg)
    sched.run_rounds(0, cfg.rounds)
    expected = [(p, r) for r in range(2)
                for p in round_phases(get_method(cfg.method))]
    assert sched.trace == expected


# ------------------------------------------------------------ overlap mode

def test_overlap_pipeline_reorders_phases():
    """max_inflight=2 must run round 1's local_train/report BEFORE round
    0's aggregate — that reordering IS the overlap."""
    cfg = _overlap_cfg(rounds=3)
    sched = _build_scheduler(cfg)
    sched.run_rounds(0, cfg.rounds)
    t = sched.trace
    assert t.index(("local_train", 1)) < t.index(("aggregate", 0))
    assert t.index(("report", 1)) < t.index(("aggregate", 0))
    # admission control: round 2 must NOT start before round 0 retired
    assert t.index(("local_train", 2)) > t.index(("eval", 0))
    # drains stay in round order (server rng / buffer / log assembly)
    assert t.index(("aggregate", 0)) < t.index(("aggregate", 1))
    assert t.index(("eval", 0)) < t.index(("eval", 1))


def test_overlap_schedule_deterministic_in_seed():
    """Same seed ⇒ identical execution trace, identical round logs (bit
    for bit) and identical straggler speeds across two fresh builds."""
    runs = []
    for _ in range(2):
        cfg = _overlap_cfg(rounds=3)
        sched = _build_scheduler(cfg)
        logs = sched.run_rounds(0, cfg.rounds)
        runs.append((sched.trace, logs, sched.timeline.speeds.copy()))
    (t0, l0, s0), (t1, l1, s1) = runs
    assert t0 == t1
    np.testing.assert_array_equal(s0, s1)
    for a, b in zip(l0, l1):
        assert a.accs == b.accs
        assert a.local_loss == b.local_loss
        assert a.distill_loss == b.distill_loss
        assert a.participants == b.participants


@pytest.mark.parametrize("method", ["edgefd", "fkd", "indlearn"])
def test_overlap_loop_cohort_parity(method):
    """The pipeline schedule is engine-independent: loop and cohort logs
    must match under overlap — across the proxy-distillation, data-free
    and no-collaboration phase graphs."""
    results = {}
    for engine in ("loop", "cohort"):
        cfg = _overlap_cfg(engine, method=method)
        results[engine] = simulator.run(cfg, "mnist_feat",
                                        n_train=800, n_test=300)
    for rl, rc in zip(results["loop"].rounds, results["cohort"].rounds):
        assert rl.participants == rc.participants
        np.testing.assert_allclose(rl.accs, rc.accs, **TOL)
        np.testing.assert_allclose(rl.local_loss, rc.local_loss, **TOL)
        np.testing.assert_allclose(rl.distill_loss, rc.distill_loss, **TOL)
        np.testing.assert_allclose(rl.id_fraction, rc.id_fraction, **TOL)
        np.testing.assert_allclose(rl.mean_staleness, rc.mean_staleness,
                                   **TOL)
        assert rl.bytes_up == rc.bytes_up


def test_overlap_mesh_sharded_parity():
    """loop == cohort == mesh@4 under round_mode="overlap" (forced-device
    harness like tests/test_cohort_parity.py); C=5 on 4 devices exercises
    a padded cohort inside the pipeline."""
    if jax.device_count() >= 4:
        import _mesh_parity_prog
        _mesh_parity_prog.check_parity(5, 4, participation_fraction=0.5,
                                       staleness_decay=0.5,
                                       round_mode="overlap", rounds=3)
        return
    here = os.path.dirname(os.path.abspath(__file__))
    prog = os.path.join(here, "_mesh_parity_prog.py")
    src = os.path.abspath(os.path.join(here, "..", "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, prog, "--devices", "4", "--clients", "5",
         "--participation", "0.5", "--staleness-decay", "0.5",
         "--round-mode", "overlap", "--rounds", "3"],
        env=env, capture_output=True, text=True, timeout=480)
    assert res.returncode == 0, (
        f"overlap mesh parity subprocess failed:\n"
        f"{res.stdout}\n{res.stderr}")
    assert res.stdout.count("PARITY-OK") == 1, res.stdout


def test_run_round_single_call_accepts_overlap():
    """A single run_round call cannot overlap with anything: overlap mode
    must degenerate to the sync order, not crash."""
    cfg = _overlap_cfg(rounds=1)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    engine = simulator.build_engine(clients, cfg)
    engine.learn_dres(jax.random.PRNGKey(cfg.seed))
    log = run_round(0, engine, server, get_method(cfg.method), cfg,
                    x_test, y_test)
    assert log.round == 0 and log.accs


# ------------------------------------------------------------- accounting

def test_phase_wall_clock_breakdown_recorded():
    cfg = _cfg(rounds=2, round_mode="sync")
    res = simulator.run(cfg, "mnist_feat", n_train=800, n_test=300)
    for log in res.rounds:
        assert set(log.phase_s) == set(round_phases(get_method(cfg.method)))
        assert all(v >= 0.0 for v in log.phase_s.values())
        assert log.wall_s == pytest.approx(sum(log.phase_s.values()))
        assert isinstance(log.sim_finish_s, float)
    # rounds retire in order on the simulated timeline
    finishes = [log.sim_finish_s for log in res.rounds]
    assert finishes == sorted(finishes) and finishes[0] > 0.0


def test_per_client_id_fractions_recorded():
    """Each round log carries every client's filter keep-rate; at full
    participation their mean is the round's ID fraction."""
    cfg = _cfg(rounds=2, round_mode="sync", engine="cohort")
    res = simulator.run(cfg, "mnist_feat", n_train=800, n_test=300)
    for log in res.rounds:
        fracs = log.client_id_fractions
        assert len(fracs) == cfg.num_clients
        assert all(0.0 <= v <= 1.0 for v in fracs)
        assert np.mean(fracs) == pytest.approx(log.id_fraction, abs=1e-6)


def test_client_speeds_deterministic_and_bounded():
    a = client_speeds(8, seed=3, straggler_factor=4.0)
    b = client_speeds(8, seed=3, straggler_factor=4.0)
    np.testing.assert_array_equal(a, b)
    assert np.all((1.0 <= a) & (a <= 4.0))
    assert not np.array_equal(a, client_speeds(8, seed=4,
                                               straggler_factor=4.0))
    # per-client draws: client c keeps its speed when the fleet grows
    np.testing.assert_array_equal(a[:4], client_speeds(4, seed=3,
                                                       straggler_factor=4.0))
    np.testing.assert_array_equal(client_speeds(5, straggler_factor=1.0),
                                  np.ones(5))
    with pytest.raises(ValueError, match="straggler_factor"):
        client_speeds(4, straggler_factor=0.5)


def test_sim_timeline_overlap_beats_sync_within_acc_tolerance():
    """Fixed per-phase costs through the scheduler's own graphs: the
    overlap pipeline must retire the same rounds strictly earlier on the
    simulated straggler timeline than lockstep does — while landing
    within accuracy tolerance of the lockstep trajectory (overlap is a
    different protocol, not a broken one)."""
    costs = {"local_train": 1.0, "report": 0.1, "aggregate": 0.5,
             "distill": 1.0, "eval": 0.0}
    finish, final_acc = {}, {}
    for mode in ("sync", "overlap"):
        cfg = _overlap_cfg(rounds=4, round_mode=mode)
        sched = _build_scheduler(cfg, sim_phase_costs=costs)
        logs = sched.run_rounds(0, cfg.rounds)
        finish[mode] = logs[-1].sim_finish_s
        final_acc[mode] = logs[-1].mean_acc
    assert finish["overlap"] < finish["sync"], finish
    assert abs(final_acc["overlap"] - final_acc["sync"]) < 0.1, final_acc


def test_sim_timeline_primitives():
    tl = SimTimeline(np.array([1.0, 2.0]))
    # both clients start at 0; the 2x straggler gates the barrier
    assert tl.client_phase(None, 1.0) == pytest.approx(2.0)
    # server waits for its input, then runs serially
    assert tl.server_phase(0.5, ready_s=2.0) == pytest.approx(2.5)
    assert tl.server_phase(0.5, ready_s=0.0) == pytest.approx(3.0)
    # a busy lane defers the next phase for that client only: client 0's
    # lane is occupied until 1.0, so its next 1.0 s phase ends at 2.0
    end = tl.client_phase(np.array([True, False]), 1.0, ready_s=0.0)
    assert end == pytest.approx(2.0)
    # participants=[] completes at ready_s
    assert tl.client_phase(np.array([False, False]), 5.0,
                           ready_s=7.0) == pytest.approx(7.0)


# ------------------------------------------------------------- validation

def test_run_round_rejects_bad_participation_fraction():
    """Satellite: 0 and negative fractions must fail loudly at the
    run_round entry path (only > 1 was rejected before)."""
    cfg = _cfg(rounds=1)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=400, n_test=200)
    method = get_method(cfg.method)
    for bad in (0.0, -0.25, 1.5):
        bad_cfg = _cfg(rounds=1, participation_fraction=bad)
        with pytest.raises(ValueError, match="participation_fraction"):
            run_round(0, clients, server, method, bad_cfg, x_test, y_test)


def test_round_mode_resolution_and_validation():
    assert resolve_round_mode("sync") == "sync"
    assert resolve_round_mode("overlap") == "overlap"
    env_backup = os.environ.pop("REPRO_ROUND_MODE", None)
    try:
        assert resolve_round_mode("auto") == "sync"
        os.environ["REPRO_ROUND_MODE"] = "overlap"
        assert resolve_round_mode("auto") == "overlap"
        # explicit modes beat the env var
        assert resolve_round_mode("sync") == "sync"
    finally:
        if env_backup is None:
            os.environ.pop("REPRO_ROUND_MODE", None)
        else:
            os.environ["REPRO_ROUND_MODE"] = env_backup
    with pytest.raises(ValueError, match="round_mode"):
        resolve_round_mode("eager")
    with pytest.raises(ValueError, match="round_mode"):
        validate_config(_cfg(round_mode="pipelined"))
    with pytest.raises(ValueError, match="max_inflight"):
        validate_config(_cfg(max_inflight=0))
    with pytest.raises(ValueError, match="straggler_factor"):
        validate_config(_cfg(straggler_factor=0.0))


def test_staleness_buffer_rejects_out_of_order_merge():
    from repro.fed.participation import StalenessBuffer
    buf = StalenessBuffer(2, 4, 2)
    idx = np.array([0, 1])
    logits = np.ones((2, 2, 2), np.float32)
    masks = np.ones((2, 2), bool)
    buf.merge(3, [True, False], idx, logits, masks, decay=0.5)
    buf.merge(3, [True, False], idx, logits, masks, decay=0.5)  # same: OK
    with pytest.raises(ValueError, match="round order"):
        buf.merge(2, [True, False], idx, logits, masks, decay=0.5)
