"""Loop ↔ cohort engine parity: same seed ⇒ same round logs.

The cohort engine (``repro.fed.cohort``) is only admissible if it is a pure
execution-strategy change: stacked vmapped clients must reproduce the
per-client loop's round logs — per-client accuracies, losses, ID fractions
and byte accounting — within float tolerance (acceptance gate: 1e-5).

Scenarios cover the three partition regimes (strong/weak non-IID, IID — the
IID case has uniform per-client sizes and exercises the *vmapped* KMeans-DRE
learn path) and the method axes: filtered (edgefd), unfiltered ensemble
(fedmd), no collaboration (indlearn), data-free (fkd), and the KuLSIF-filter
baseline (selective-fd).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.common.types import FedConfig
from repro.fed import simulator
from repro.fed.cohort import CohortEngine

TOL = dict(rtol=0.0, atol=1e-5)


def _cfg(method, scenario, engine, **kw):
    base = dict(num_clients=5, rounds=2, method=method, scenario=scenario,
                proxy_batch=120, batch_size=32, lr=1e-2, seed=0, engine=engine)
    base.update(kw)
    return FedConfig(**base)


def _pair(method, scenario, **kw):
    res = {}
    for engine in ("loop", "cohort"):
        res[engine] = simulator.run(_cfg(method, scenario, engine, **kw),
                                    "mnist_feat", n_train=800, n_test=300)
    return res["loop"], res["cohort"]


def _assert_logs_match(loop, cohort):
    assert len(loop.rounds) == len(cohort.rounds)
    for rl, rc in zip(loop.rounds, cohort.rounds):
        np.testing.assert_allclose(rl.accs, rc.accs, **TOL)
        np.testing.assert_allclose(rl.mean_acc, rc.mean_acc, **TOL)
        np.testing.assert_allclose(rl.local_loss, rc.local_loss, **TOL)
        np.testing.assert_allclose(rl.distill_loss, rc.distill_loss, **TOL)
        np.testing.assert_allclose(rl.id_fraction, rc.id_fraction, **TOL)
        assert rl.bytes_up == rc.bytes_up
        assert rl.bytes_down == rc.bytes_down


@pytest.mark.parametrize("scenario", ["strong", "weak", "iid"])
def test_edgefd_parity_across_scenarios(scenario):
    _assert_logs_match(*_pair("edgefd", scenario))


@pytest.mark.parametrize("method", ["fedmd", "indlearn", "fkd"])
def test_method_parity_strong_noniid(method):
    _assert_logs_match(*_pair(method, "strong"))


def test_kulsif_filter_parity():
    """selective-fd: batched KuLSIF estimate (far-sentinel padding) must
    reproduce the per-client ratio filter."""
    _assert_logs_match(*_pair("selective-fd", "strong"))


def test_parity_with_ragged_client_sizes():
    """Weak non-IID with few labels per client yields very unequal private
    set sizes — the padded/masked step machinery is what's under test."""
    _assert_logs_match(*_pair("edgefd", "weak", labels_per_client=1))


def test_parity_short_proxy_batch():
    """Proxy batch smaller than the train batch: the single short-batch rule
    (fed/batching.py) must behave identically in both engines."""
    _assert_logs_match(*_pair("edgefd", "strong", proxy_batch=20,
                              batch_size=64))


def test_cohort_groups_homogeneous_clients():
    # zoo pinned: this test certifies the single-cohort structure of the
    # shared population (the REPRO_ZOO=mixed CI entry builds three)
    cfg = _cfg("edgefd", "strong", "cohort", zoo="shared")
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    engine = CohortEngine(clients)
    # feature mode: all clients share the MLP arch -> exactly one cohort
    assert len(engine.cohorts) == 1
    assert engine.cohorts[0].positions == list(range(cfg.num_clients))


def test_mesh_sharded_parity_forced_devices():
    """Same-seed parity for the mesh-sharded cohort engine on 4 forced host
    devices: C=4 (divisible) and C=5 (exercises client-axis padding with
    validity-gated dummy clients). jax fixes the device count at first init,
    so on single-device hosts the check re-runs in a subprocess that forces
    XLA_FLAGS=--xla_force_host_platform_device_count=4 before importing jax;
    the multi-device CI job runs it in-process."""
    if jax.device_count() >= 4:
        import _mesh_parity_prog
        for c in (4, 5):
            _mesh_parity_prog.check_parity(c, 4)
        return
    here = os.path.dirname(os.path.abspath(__file__))
    prog = os.path.join(here, "_mesh_parity_prog.py")
    src = os.path.abspath(os.path.join(here, "..", "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    # < CI's per-test --timeout=600 (pytest-timeout), so a wedged child is
    # reported by this assert instead of a blunt test kill
    res = subprocess.run(
        [sys.executable, prog, "--devices", "4", "--clients", "4", "5"],
        env=env, capture_output=True, text=True, timeout=480)
    assert res.returncode == 0, (
        f"mesh parity subprocess failed:\n{res.stdout}\n{res.stderr}")
    assert res.stdout.count("PARITY-OK") == 2, res.stdout


def test_mesh_sharded_parity_pallas_kernels():
    """The same parity with every engine on the Pallas kernels: on the mesh
    each kernel runs under ``shard_map`` over the client axis (a
    ``pallas_call`` has no partitioning rule), C=5 on 4 devices pads the
    tail with validity-gated dummy clients."""
    if jax.device_count() >= 4:
        import _mesh_parity_prog
        _mesh_parity_prog.check_parity(5, 4, kernel_backend="pallas")
        return
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.abspath(os.path.join(here, "..", "src"))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(here, "_mesh_parity_prog.py"),
         "--devices", "4", "--clients", "5", "--kernel-backend", "pallas"],
        env=env, capture_output=True, text=True, timeout=480)
    assert res.returncode == 0, (
        f"mesh parity subprocess failed:\n{res.stdout}\n{res.stderr}")
    assert "PARITY-OK" in res.stdout, res.stdout


def test_run_round_honors_cfg_engine(monkeypatch):
    """Regression: run_round built its engine with as_engine(clients) —
    dropping cfg.engine — so a raw client list under engine='cohort'
    silently ran the slow loop engine."""
    import repro.fed.cohort as cohort_mod
    from repro.core import protocol
    from repro.core.methods import get_method

    cfg = _cfg("fedmd", "strong", "cohort", rounds=1)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    created = []

    class SpyEngine(CohortEngine):
        def __init__(self, cs, **kw):
            created.append(len(cs))
            super().__init__(cs, **kw)

    monkeypatch.setattr(cohort_mod, "CohortEngine", SpyEngine)
    protocol.run_round(0, clients, server, get_method(cfg.method), cfg,
                       x_test, y_test)
    assert created == [cfg.num_clients], (
        "run_round must build the engine cfg.engine selects when handed a "
        "raw client list")


def test_run_round_raw_list_trains_across_rounds():
    """A per-call cohort engine is transient: unless run_round syncs its
    stacked params back onto the Client objects — and unless a fresh engine
    adopts the clients' already-learned DRE filters — successive raw-list
    calls restart from the initial weights (or silently stop filtering)
    every round. Multi-round raw-list logs must match the loop engine's
    exactly for the filtered method."""
    from repro.core import protocol
    from repro.core.methods import get_method

    logs = {}
    for engine in ("loop", "cohort"):
        cfg = _cfg("edgefd", "strong", engine, rounds=3)
        clients, server, x_test, y_test = simulator.build_experiment(
            cfg, "mnist_feat", n_train=800, n_test=300)
        method = get_method(cfg.method)
        key = jax.random.PRNGKey(cfg.seed)
        for i, c in enumerate(clients):   # what run_experiment's init does
            c.learn_dre(jax.random.fold_in(key, i))
        logs[engine] = [protocol.run_round(r, clients, server, method, cfg,
                                           x_test, y_test)
                        for r in range(cfg.rounds)]
    for rl, rc in zip(logs["loop"], logs["cohort"]):
        np.testing.assert_allclose(rl.accs, rc.accs, **TOL)
        np.testing.assert_allclose(rl.local_loss, rc.local_loss, **TOL)
        np.testing.assert_allclose(rl.distill_loss, rc.distill_loss, **TOL)
        np.testing.assert_allclose(rl.id_fraction, rc.id_fraction, **TOL)
    assert logs["cohort"][-1].mean_acc > logs["cohort"][0].mean_acc, (
        "accuracy must improve across raw-list rounds (state persisted)")


def test_evaluate_pads_tail_batch_single_compile():
    """Regression: _Cohort.evaluate sliced x_test into a ragged final batch,
    silently recompiling the eval fn for every distinct tail shape. With
    the padded+masked tail, the model traces exactly once per test-set
    shape — and the accuracies still match the per-client reference."""
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
    for cid in range(3):
        key, sub = jax.random.split(key)
        clients.append(Client(
            cid, counting_apply, mlp.init(sub), opt,
            rng.normal(size=(40, 8)).astype(np.float32),
            rng.integers(0, 4, size=40), num_classes=4, arch_key="mlp",
            seed=0))
    engine = CohortEngine(clients)
    # 700 % 512 != 0: the old path compiled (512, 8) AND the (188, 8) tail
    x_test = rng.normal(size=(700, 8)).astype(np.float32)
    y_test = np.asarray(rng.integers(0, 4, size=700))
    accs = engine.evaluate(x_test, y_test)
    assert len(traces) == 1, (
        f"eval traced {len(traces)} times for one test-set shape "
        f"(shapes: {traces}); the tail batch must be padded, not ragged")
    engine.evaluate(x_test, y_test)
    assert len(traces) == 1, "second eval of the same shape must hit the cache"
    ref = [c.evaluate(x_test, y_test) for c in clients]
    np.testing.assert_allclose(accs, ref, **TOL)


def test_transient_engine_adopts_custom_dre_via_loop_fallback():
    """A cohort built from clients carrying an unknown (non-KMeans/KuLSIF)
    estimator must take the per-client mask fallback — not silently stop
    filtering with all-True masks — matching the loop engine exactly."""
    import dataclasses as dc

    from repro.fed.client import Client
    from repro.models.cnn import MLPClassifier
    from repro.optim.optimizers import sgd

    @dc.dataclass
    class NormDRE:                         # distances + threshold interface
        threshold: float = 2.0

        def distances(self, t):
            import jax.numpy as jnp
            return jnp.linalg.norm(t, axis=1)

    mlp = MLPClassifier(d_in=6, hidden=(8,), num_classes=3)
    rng = np.random.default_rng(0)
    opt = sgd(1e-2)
    key = jax.random.PRNGKey(0)
    clients = []
    for cid in range(2):
        key, sub = jax.random.split(key)
        clients.append(Client(
            cid, mlp.apply, mlp.init(sub), opt,
            rng.normal(size=(20, 6)).astype(np.float32),
            rng.integers(0, 3, size=20), dre=NormDRE(),
            num_classes=3, arch_key="mlp", seed=0))
    px = np.concatenate([np.zeros((5, 6), np.float32),          # ID (d=0)
                         np.full((5, 6), 9.0, np.float32)])     # OOD (d>>thr)
    powner = np.full((10,), -1, np.int32)   # no sample owned by either client
    engine = CohortEngine(clients)
    _, masks = engine.cohort_report(0, px, powner)
    ref = np.stack([np.asarray(c.filter_mask(px, powner).mask)
                    for c in clients])
    np.testing.assert_array_equal(masks, ref)
    assert not masks.all(), "OOD proxy samples must be filtered out"
    assert masks[:, :5].all(), "ID proxy samples must be kept"


def test_mixed_dre_cohort_matches_loop():
    """A cohort where only some members carry a (learned) DRE must use the
    per-client mask fallback — not return all-True for everyone because
    member 0 happens to be filterless."""
    from repro.core.dre import KMeansDRE
    from repro.fed.client import Client
    from repro.models.cnn import MLPClassifier
    from repro.optim.optimizers import sgd

    mlp = MLPClassifier(d_in=6, hidden=(8,), num_classes=3)
    rng = np.random.default_rng(0)
    opt = sgd(1e-2)
    key = jax.random.PRNGKey(0)
    clients = []
    for cid in range(2):
        key, sub = jax.random.split(key)
        x = rng.normal(size=(20, 6)).astype(np.float32) * 0.1
        dre = None
        if cid == 1:
            import jax.numpy as jnp
            dre = KMeansDRE(num_centroids=1, threshold=2.0).learn(
                jax.random.fold_in(key, cid), jnp.asarray(x))
        clients.append(Client(cid, mlp.apply, mlp.init(sub), opt, x,
                              rng.integers(0, 3, size=20), dre=dre,
                              num_classes=3, arch_key="mlp", seed=0))
    px = np.concatenate([np.zeros((5, 6), np.float32),          # ID
                         np.full((5, 6), 9.0, np.float32)])     # OOD
    powner = np.full((10,), -1, np.int32)
    engine = CohortEngine(clients)
    _, masks = engine.cohort_report(0, px, powner)
    ref = np.stack([np.asarray(c.filter_mask(px, powner).mask)
                    for c in clients])
    np.testing.assert_array_equal(masks, ref)
    assert masks[0].all(), "filterless member keeps every proxy sample"
    assert not masks[1, 5:].any(), "filtered member drops OOD samples"


def test_transient_engine_unlearned_dre_fails_like_loop():
    """Filter masks requested from a cohort whose clients carry *unlearned*
    DREs must fail exactly like the loop engine (KMeansDRE.distances
    asserts 'call learn() first'), not silently return all-True masks."""
    cfg = _cfg("edgefd", "strong", "cohort", rounds=1)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    engine = CohortEngine(clients)      # learn_dres deliberately not called
    px = np.asarray(server.proxy.x[:10])
    powner = np.asarray(server.proxy.owner[:10])
    with pytest.raises(AssertionError, match="learn"):
        engine.cohort_report(0, px, powner)


def test_nonuniform_calibration_q_matches_loop():
    """The vmapped KMeans-DRE fit bakes one (calibration_q, max_iter) into
    the whole batch; members differing in either must take the per-client
    path and calibrate exactly like the loop engine."""
    from repro.core.dre import KMeansDRE
    from repro.core.protocol import LoopEngine
    from repro.fed.client import Client
    from repro.models.cnn import MLPClassifier
    from repro.optim.optimizers import sgd

    mlp = MLPClassifier(d_in=6, hidden=(8,), num_classes=3)
    opt = sgd(1e-2)

    def make_clients():
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(0)
        out = []
        for cid, q in enumerate((0.5, 0.99)):
            key, sub = jax.random.split(key)
            out.append(Client(
                cid, mlp.apply, mlp.init(sub), opt,
                rng.normal(size=(20, 6)).astype(np.float32),
                rng.integers(0, 3, size=20),
                dre=KMeansDRE(num_centroids=1, threshold=None,
                              calibration_q=q),
                num_classes=3, arch_key="mlp", seed=0))
        return out

    key = jax.random.PRNGKey(7)
    loop_clients, cohort_clients = make_clients(), make_clients()
    LoopEngine(loop_clients).learn_dres(key)
    CohortEngine(cohort_clients).learn_dres(key)
    for cl, cc in zip(loop_clients, cohort_clients):
        np.testing.assert_allclose(cc.dre.threshold, cl.dre.threshold, **TOL)
    assert loop_clients[0].dre.threshold < loop_clients[1].dre.threshold, (
        "distinct calibration quantiles must yield distinct thresholds")


def test_run_experiment_raw_list_syncs_cohort_state():
    """run_experiment over a raw client list with engine='cohort' builds an
    internal engine; its trained params must land back on the Client
    objects before it is discarded (the loop engine mutates in place, so
    raw-list callers rightly expect trained clients either way)."""
    from repro.core.protocol import run_experiment

    cfg = _cfg("edgefd", "strong", "cohort", rounds=1)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    before = [np.asarray(c.params[0]["w"]).copy() for c in clients]
    run_experiment(clients, server, cfg.method, cfg, x_test, y_test)
    for c, b in zip(clients, before):
        assert not np.allclose(np.asarray(c.params[0]["w"]), b), (
            "client params must reflect the training run_experiment did")


def test_cohort_sync_to_clients():
    cfg = _cfg("edgefd", "strong", "cohort", rounds=1)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=800, n_test=300)
    before = [np.asarray(c.params[0]["w"]).copy() for c in clients]
    engine = simulator.build_engine(clients, cfg)
    from repro.core.protocol import run_experiment
    run_experiment(engine, server, cfg.method, cfg, x_test, y_test)
    engine.sync_to_clients()
    for c, b in zip(clients, before):
        assert not np.allclose(np.asarray(c.params[0]["w"]), b)
