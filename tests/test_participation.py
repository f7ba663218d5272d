"""Partial participation & staleness (repro.fed.participation).

Gates, in order of importance:

  * ``participation_fraction=1.0, staleness_decay=0`` reproduces the
    pre-participation round logs **bit-for-bit** (the machinery must be
    invisible when disabled);
  * under ``participation_fraction < 1`` the loop and cohort engines (and
    the mesh-sharded cohort engine, via the forced-device harness) produce
    identical round logs — sampling, rng-stream skipping and staleness
    reuse are engine-independent;
  * every sampling policy is deterministic in ``(seed, round)``;
  * sampling a different subset each round changes only data, never
    shapes: no cohort phase retraces.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.common.types import FedConfig
from repro.core.methods import get_method
from repro.core.protocol import run_round
from repro.fed import simulator
from repro.fed.cohort import CohortEngine
from repro.fed.participation import (StalenessBuffer, cohort_size,
                                     sample_participants, validate_config)

TOL = dict(rtol=0.0, atol=1e-5)


def _cfg(engine, **kw):
    base = dict(num_clients=5, rounds=3, method="edgefd", scenario="strong",
                proxy_batch=120, batch_size=32, lr=1e-2, seed=0,
                engine=engine)
    base.update(kw)
    return FedConfig(**base)


# ---------------------------------------------------------------- sampling

@pytest.mark.parametrize("policy", ["uniform", "weighted", "roundrobin"])
def test_policy_deterministic_in_seed_and_round(policy):
    sizes = np.array([10, 20, 30, 40, 50, 60])
    for r in range(4):
        a = sample_participants(r, 6, 0.5, policy, seed=3, data_sizes=sizes)
        b = sample_participants(r, 6, 0.5, policy, seed=3, data_sizes=sizes)
        np.testing.assert_array_equal(a, b)
        assert a.sum() == cohort_size(6, 0.5) == 3


def test_uniform_varies_across_rounds():
    draws = {tuple(np.flatnonzero(
        sample_participants(r, 20, 0.25, "uniform", seed=0)))
        for r in range(12)}
    assert len(draws) > 1, "uniform sampling must not freeze one subset"


def test_roundrobin_covers_everyone_each_cycle():
    c, frac = 7, 0.3                       # k = 2, cycle = ceil(7/2) = 4
    k = cohort_size(c, frac)
    seen = set()
    for r in range(-(-c // k)):
        mask = sample_participants(r, c, frac, "roundrobin")
        assert mask.sum() == k
        seen |= set(np.flatnonzero(mask))
    assert seen == set(range(c))


def test_weighted_prefers_large_shards():
    sizes = np.array([1000, 1, 1, 1, 1, 1, 1, 1])
    hits = np.zeros(8)
    for r in range(40):
        hits += sample_participants(r, 8, 0.25, "weighted", seed=0,
                                    data_sizes=sizes)
    assert hits[0] == max(hits) and hits[0] >= 35, hits


def test_fraction_one_is_everyone():
    for policy in ("uniform", "weighted", "roundrobin"):
        mask = sample_participants(5, 9, 1.0, policy,
                                   data_sizes=np.ones(9))
        assert mask.all()


def test_sampling_validation():
    with pytest.raises(ValueError, match="policy"):
        sample_participants(0, 4, 0.5, "fifo")
    with pytest.raises(ValueError, match="fraction"):
        sample_participants(0, 4, 0.0, "uniform")
    with pytest.raises(ValueError, match="data_sizes"):
        sample_participants(0, 4, 0.5, "weighted")
    with pytest.raises(ValueError, match="clients with data"):
        sample_participants(0, 4, 0.75, "weighted",
                            data_sizes=np.array([1, 0, 0, 0]))
    with pytest.raises(ValueError, match="participation_policy"):
        validate_config(_cfg("loop", participation_policy="fifo"))
    with pytest.raises(ValueError, match="participation_fraction"):
        validate_config(_cfg("loop", participation_fraction=1.5))
    with pytest.raises(ValueError, match="staleness_decay"):
        validate_config(_cfg("loop", staleness_decay=-0.1))


# ---------------------------------------------------------------- staleness

def test_staleness_buffer_ages_and_weights():
    buf = StalenessBuffer(num_clients=3, proxy_size=6, num_classes=2)
    idx0 = np.array([0, 1, 2])
    logits = np.arange(3 * 3 * 2, dtype=np.float32).reshape(3, 3, 2)
    masks = np.ones((3, 3), bool)
    # round 0: clients 0, 1 report
    m0 = buf.merge(0, [True, True, False], idx0, logits, masks, decay=0.5)
    np.testing.assert_array_equal(m0.client_weights, [1.0, 1.0, 0.0])
    assert not m0.masks[2].any(), "never-reported client contributes nothing"
    # round 2 (client 1 skipped two rounds): only client 2 fresh
    idx2 = np.array([1, 2, 3])
    m2 = buf.merge(2, [False, False, True], idx2, logits, masks, decay=0.5)
    np.testing.assert_allclose(m2.client_weights, [0.25, 0.25, 1.0])
    # stale rows come from the cache at *this* round's indices: client 0
    # reported positions {0,1,2}, so position 3 is unknown for it
    np.testing.assert_array_equal(m2.masks[0], [True, True, False])
    np.testing.assert_allclose(m2.logits[0, 0], logits[0, 1])
    assert m2.mean_staleness == pytest.approx((2 + 2 + 0) / 3)


def test_staleness_decay_zero_drops_stale():
    buf = StalenessBuffer(2, 4, 2)
    idx = np.array([0, 1])
    logits = np.ones((2, 2, 2), np.float32)
    masks = np.ones((2, 2), bool)
    buf.merge(0, [True, True], idx, logits, masks, decay=0.0)
    m = buf.merge(1, [True, False], idx, logits, masks, decay=0.0)
    np.testing.assert_array_equal(m.client_weights, [1.0, 0.0])


def test_staleness_decay_one_full_reuse():
    buf = StalenessBuffer(2, 4, 2)
    idx = np.array([0, 1])
    logits = np.ones((2, 2, 2), np.float32)
    masks = np.ones((2, 2), bool)
    buf.merge(0, [True, True], idx, logits, masks, decay=1.0)
    m = buf.merge(5, [True, False], idx, logits, masks, decay=1.0)
    np.testing.assert_array_equal(m.client_weights, [1.0, 1.0])
    np.testing.assert_array_equal(m.masks, masks)


# ------------------------------------------------------------- regressions

@pytest.mark.parametrize("engine", ["loop", "cohort"])
def test_defaults_reproduce_legacy_logs_bit_for_bit(engine):
    """participation_fraction=1.0, staleness_decay=0 (the defaults) must
    leave the round logs *bit-for-bit* identical to the pre-participation
    protocol — replicated here as the exact legacy call sequence (explicit
    per-cohort engine calls without a mask, scattered back into client
    order, and aggregation without client weights).

    round_mode is pinned to "sync": the legacy sequence IS the lockstep
    order, so the comparison must not follow the REPRO_ROUND_MODE=overlap
    CI matrix entry (sync stays the FedConfig default either way)."""
    cfg = _cfg(engine, rounds=2, round_mode="sync")
    new = simulator.run(cfg, "mnist_feat", n_train=800, n_test=300)

    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    eng = simulator.build_engine(clients, cfg)
    method = get_method(cfg.method)
    key = jax.random.PRNGKey(cfg.seed)
    eng.learn_dres(key)
    cohorts = range(len(eng.cohort_positions()))
    order = np.argsort(np.concatenate(eng.cohort_positions()))

    def fleet(per_cohort):          # cohort-major rows -> client order
        return np.concatenate(per_cohort)[order]

    for r, log in enumerate(new.rounds):
        local_losses = fleet([eng.cohort_local_train(ci, cfg.local_epochs,
                                                     cfg.batch_size)
                              for ci in cohorts])
        idx = server.select_indices(cfg.proxy_batch)
        px, powner = server.proxy.x[idx], server.proxy.owner[idx]
        reports = [eng.cohort_report(ci, px, powner) for ci in cohorts]
        logits = fleet([lg for lg, _ in reports])
        masks = fleet([mk for _, mk in reports])
        teacher, valid = server.aggregate(logits, masks,
                                          sharpen=method.sharpen,
                                          entropy_filter=method.server_filter)
        distill_losses = fleet([
            eng.cohort_distill(ci, px, teacher, valid.astype(np.float32),
                               cfg.distill_epochs, cfg.batch_size)
            for ci in cohorts])
        accs = eng.evaluate(x_test, y_test)
        assert log.accs == accs                               # bit-for-bit
        assert log.mean_acc == float(np.mean(accs))
        assert log.local_loss == float(np.mean(local_losses))
        assert log.distill_loss == float(np.mean(distill_losses))
        assert log.id_fraction == float(masks.mean())
        assert log.bytes_up == server.bytes_received
        assert log.bytes_down == server.bytes_broadcast
        assert log.participants is None and log.mean_staleness == 0.0


@pytest.mark.parametrize("policy,decay", [("uniform", 0.0),
                                          ("roundrobin", 0.5),
                                          ("weighted", 1.0)])
def test_loop_cohort_parity_partial_participation(policy, decay):
    """fraction < 1: loop and cohort logs must still match — the sampled
    subset, the skipped rng streams and the staleness reuse are all
    engine-independent."""
    results = {}
    for engine in ("loop", "cohort"):
        cfg = _cfg(engine, participation_fraction=0.5,
                   participation_policy=policy, staleness_decay=decay)
        results[engine] = simulator.run(cfg, "mnist_feat",
                                        n_train=800, n_test=300)
    for rl, rc in zip(results["loop"].rounds, results["cohort"].rounds):
        assert rl.participants == rc.participants
        assert len(rl.participants) == cohort_size(5, 0.5)
        np.testing.assert_allclose(rl.accs, rc.accs, **TOL)
        np.testing.assert_allclose(rl.local_loss, rc.local_loss, **TOL)
        np.testing.assert_allclose(rl.distill_loss, rc.distill_loss, **TOL)
        np.testing.assert_allclose(rl.id_fraction, rc.id_fraction, **TOL)
        np.testing.assert_allclose(rl.mean_staleness, rc.mean_staleness,
                                   **TOL)
        assert rl.bytes_up == rc.bytes_up
        assert rl.bytes_down == rc.bytes_down


def test_mesh_sharded_parity_partial_participation():
    """loop == cohort == mesh@4 under fraction < 1 (forced-device harness,
    like tests/test_cohort_parity.py): the participation mask must compose
    with the mesh's dummy-client padding. C=5 on 4 devices exercises a
    padded cohort with sampled-out real clients."""
    if jax.device_count() >= 4:
        import _mesh_parity_prog
        _mesh_parity_prog.check_parity(5, 4, participation_fraction=0.5,
                                       staleness_decay=0.5)
        return
    here = os.path.dirname(os.path.abspath(__file__))
    prog = os.path.join(here, "_mesh_parity_prog.py")
    src = os.path.abspath(os.path.join(here, "..", "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, prog, "--devices", "4", "--clients", "5",
         "--participation", "0.5", "--staleness-decay", "0.5"],
        env=env, capture_output=True, text=True, timeout=480)
    assert res.returncode == 0, (
        f"mesh participation parity subprocess failed:\n"
        f"{res.stdout}\n{res.stderr}")
    assert res.stdout.count("PARITY-OK") == 1, res.stdout


@pytest.mark.parametrize("method", ["edgefd", "fkd"])
def test_participation_reduces_upload_bytes(method):
    """Only participants upload — on the proxy-logit path (mask-compressed
    logits) and the data-free classwise path (per-class mean matrices)
    alike: at fraction 0.5 the per-round upload must be strictly below the
    full-participation run's."""
    full = simulator.run(_cfg("loop", rounds=2, method=method), "mnist_feat",
                         n_train=800, n_test=300)
    half = simulator.run(_cfg("loop", rounds=2, method=method,
                              participation_fraction=0.5),
                         "mnist_feat", n_train=800, n_test=300)
    assert half.rounds[-1].bytes_up < full.rounds[-1].bytes_up


def test_changing_subset_does_not_retrace_cohort_phases():
    """The participation mask changes plan *data*, never shapes: running
    rounds over different sampled subsets must reuse every compiled cohort
    phase (one trace per phase, total)."""
    from repro.fed.client import Client
    from repro.models.cnn import MLPClassifier
    from repro.optim.optimizers import sgd

    mlp = MLPClassifier(d_in=8, hidden=(16,), num_classes=4)
    traces = []

    def counting_apply(params, x, train):
        traces.append(tuple(x.shape))    # one entry per (re)trace
        return mlp.apply(params, x, train)

    rng = np.random.default_rng(0)
    opt = sgd(1e-2)
    key = jax.random.PRNGKey(0)
    clients = []
    for cid in range(4):
        key, sub = jax.random.split(key)
        clients.append(Client(
            cid, counting_apply, mlp.init(sub), opt,
            rng.normal(size=(64, 8)).astype(np.float32),
            rng.integers(0, 4, size=64), num_classes=4, arch_key="mlp",
            seed=0))
    engine = CohortEngine(clients)
    px = rng.normal(size=(32, 8)).astype(np.float32)
    teacher = rng.normal(size=(32, 4)).astype(np.float32)
    w = np.ones((32,), np.float32)
    masks = [np.array([True, True, False, False]),
             np.array([False, False, True, True]),
             np.array([True, False, True, False])]
    engine.cohort_local_train(0, 1, 32, participants=masks[0])
    engine.cohort_distill(0, px, teacher, w, 1, 32,
                          participants=masks[0])
    first = len(traces)
    for m in masks[1:]:
        engine.cohort_local_train(0, 1, 32, participants=m)
        engine.cohort_distill(0, px, teacher, w, 1, 32, participants=m)
    assert len(traces) == first, (
        f"sampling a different subset retraced a phase: "
        f"{first} -> {len(traces)} traces ({traces})")


def test_run_round_records_participants_and_staleness():
    cfg = _cfg("loop", participation_fraction=0.6, staleness_decay=0.5)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    engine = simulator.build_engine(clients, cfg)
    engine.learn_dres(jax.random.PRNGKey(cfg.seed))
    method = get_method(cfg.method)
    logs = [run_round(r, engine, server, method, cfg, x_test, y_test)
            for r in range(3)]
    k = cohort_size(cfg.num_clients, cfg.participation_fraction)
    assert all(len(log.participants) == k for log in logs)
    assert logs[0].mean_staleness == 0.0
    assert any(log.mean_staleness > 0.0 for log in logs[1:]), (
        "with fraction < 1 some aggregated knowledge must age")
