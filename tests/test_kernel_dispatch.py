"""The kernel backend-dispatch layer (``repro.kernels.dispatch``).

Four contracts:

  * resolution — ``kernel_backend ∈ {auto, pallas, jnp}``: explicit beats
    context beats ``REPRO_KERNEL_BACKEND`` beats the platform rule, and
    interpret-mode Pallas is never an ``auto`` choice off-TPU;
  * parity — pallas(interpret) ≡ jnp for the fused Lloyd step, the
    ``distill_kl`` forward *and gradient* (custom-VJP backward kernel),
    and the KuLSIF gram matrices;
  * stability — backend selection is baked in at trace time: flipping the
    ambient backend never retraces a compiled round phase;
  * regression — same-seed end-to-end round logs: loop == cohort == mesh
    under ``kernel_backend="pallas"`` and ≈ the jnp backend; the default
    backend reproduces the pre-dispatch golden logs bit-for-bit
    (``tests/data/golden_rounds.json``, regenerate via
    ``tests/_golden_gen.py`` only for intentional numeric changes).
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import FedConfig
from repro.core.distill import kd_kl_loss
from repro.core.dre import KMeansDRE, KuLSIFDRE
from repro.core.kmeans import kmeans_fit, kmeans_fit_batched
from repro.fed import simulator
from repro.kernels import dispatch
from repro.kernels.kmeans_dist import ops as kd_ops

GOLDEN_PATH = pathlib.Path(__file__).parent / "data" / "golden_rounds.json"


# ------------------------------------------------------------------ resolve

def test_resolve_explicit_wins():
    assert dispatch.resolve("pallas") == "pallas"
    assert dispatch.resolve("jnp") == "jnp"


def test_resolve_auto_is_jnp_off_tpu(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    assert jax.default_backend() != "tpu"   # the CI/test platform
    assert dispatch.resolve("auto") == "jnp"
    assert dispatch.resolve(None) == "jnp"


def test_resolve_env_overrides_auto(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    assert dispatch.resolve("auto") == "pallas"
    assert dispatch.resolve(None) == "pallas"
    assert dispatch.resolve("jnp") == "jnp"       # explicit still wins


def test_context_manager_overrides_env(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "jnp")
    with dispatch.kernel_backend("pallas"):
        assert dispatch.resolve(None) == "pallas"
        assert dispatch.resolve("jnp") == "jnp"   # explicit still wins
        with dispatch.kernel_backend("jnp"):      # innermost context wins
            assert dispatch.resolve(None) == "jnp"
        assert dispatch.resolve(None) == "pallas"
    assert dispatch.resolve(None) == "jnp"


def test_unknown_backend_rejected(monkeypatch):
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.resolve("mosaic")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        with dispatch.kernel_backend("cuda"):
            pass
    monkeypatch.setenv(dispatch.ENV_VAR, "nope")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.resolve(None)


def test_simulator_rejects_bad_backend():
    cfg = FedConfig(num_clients=2, rounds=1, kernel_backend="fast")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        simulator.run(cfg, "mnist_feat", n_train=200, n_test=50)


# ------------------------------------------------------- fused Lloyd parity

@pytest.mark.parametrize("n,d,k", [(64, 8, 1), (300, 17, 5), (257, 50, 10)])
def test_lloyd_step_pallas_matches_jnp(n, d, k):
    key = jax.random.PRNGKey(n + d + k)
    x = jax.random.normal(key, (n, d))
    cents = jax.random.normal(jax.random.fold_in(key, 1), (k, d)) * 2
    a_p, m_p, s_p, c_p = dispatch.lloyd_step(x, cents, backend="pallas")
    a_j, m_j, s_j, c_j = dispatch.lloyd_step(x, cents, backend="jnp")
    np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_j))
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_j),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_j),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_j))


def test_lloyd_step_batched_matches_per_slice():
    key = jax.random.PRNGKey(0)
    xb = jax.random.normal(key, (3, 130, 9))
    cb = jax.random.normal(jax.random.fold_in(key, 1), (3, 4, 9))
    a_b, m_b, s_b, c_b = dispatch.lloyd_step(xb, cb, backend="pallas")
    for i in range(3):
        a1, m1, s1, c1 = kd_ops.lloyd_step(xb[i], cb[i])
        np.testing.assert_array_equal(np.asarray(a_b[i]), np.asarray(a1))
        np.testing.assert_allclose(np.asarray(s_b[i]), np.asarray(s1),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(c_b[i]), np.asarray(c1))


def test_lloyd_padding_excluded_from_sums():
    """ops.py pads n up to the block size; padded rows must not leak into
    the per-centroid sums/counts (the fit would drift toward zero)."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (70, 5)) + 10.0   # far from the pad zeros
    cents = jax.random.normal(jax.random.fold_in(key, 1), (2, 5)) + 10.0
    _, _, sums, counts = dispatch.lloyd_step(x, cents, backend="pallas")
    assert float(jnp.sum(counts)) == x.shape[0]
    np.testing.assert_allclose(np.asarray(jnp.sum(sums, axis=0)),
                               np.asarray(jnp.sum(x, axis=0)), rtol=1e-5)


@pytest.mark.parametrize("batched", [False, True])
def test_kmeans_fit_backend_parity(batched):
    key = jax.random.PRNGKey(7)
    if batched:
        keys = jax.random.split(key, 3)
        xs = jax.random.normal(jax.random.fold_in(key, 9), (3, 120, 6)) * 2
        r_j = kmeans_fit_batched(keys, xs, 3, 25, backend="jnp")
        r_p = kmeans_fit_batched(keys, xs, 3, 25, backend="pallas")
    else:
        x = jax.random.normal(jax.random.fold_in(key, 9), (150, 6)) * 2
        r_j = kmeans_fit(key, x, 3, 25, backend="jnp")
        r_p = kmeans_fit(key, x, 3, 25, backend="pallas")
    np.testing.assert_allclose(np.asarray(r_j.centroids),
                               np.asarray(r_p.centroids),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(r_j.assignments),
                                  np.asarray(r_p.assignments))
    np.testing.assert_allclose(np.asarray(r_j.inertia),
                               np.asarray(r_p.inertia), rtol=1e-3)
    np.testing.assert_array_equal(np.asarray(r_j.n_iter),
                                  np.asarray(r_p.n_iter))


# ------------------------------------------------- distill_kl fwd + gradient

def _kl_inputs(n=300, k=10, seed=0):
    key = jax.random.PRNGKey(seed)
    s = jax.random.normal(key, (n, k)) * 3
    t = jax.random.normal(jax.random.fold_in(key, 1), (n, k)) * 3
    w = (jax.random.uniform(jax.random.fold_in(key, 2), (n,)) > 0.3
         ).astype(jnp.float32)
    return s, t, w


@pytest.mark.parametrize("temp", [1.0, 3.0])
def test_distill_kl_forward_backend_parity(temp):
    s, t, w = _kl_inputs()
    l_j = kd_kl_loss(s, t, temp, w, backend="jnp")
    l_p = kd_kl_loss(s, t, temp, w, backend="pallas")
    np.testing.assert_allclose(float(l_j), float(l_p), rtol=1e-5)


@pytest.mark.parametrize("wrt", ["student", "teacher"])
def test_distill_kl_gradient_backend_parity(wrt):
    """No gradient test existed for the kernel before the custom-VJP: the
    fused Pallas backward must match jax.grad through the jnp loss."""
    s, t, w = _kl_inputs()

    def loss(backend):
        if wrt == "student":
            return lambda a: kd_kl_loss(a, t, 3.0, w, backend=backend)
        return lambda a: kd_kl_loss(s, a, 3.0, w, backend=backend)

    primal = s if wrt == "student" else t
    g_j = jax.grad(loss("jnp"))(primal)
    g_p = jax.grad(loss("pallas"))(primal)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_j),
                               rtol=1e-4, atol=1e-6)


def test_distill_kl_gradient_parity_under_vmap_jit():
    """The cohort engine differentiates the loss inside jit(vmap(...)) —
    the Pallas custom-VJP must batch through the kernel grid."""
    key = jax.random.PRNGKey(5)
    sb = jax.random.normal(key, (4, 64, 10))
    tb = jax.random.normal(jax.random.fold_in(key, 1), (4, 64, 10))

    def g(backend):
        return jax.jit(jax.vmap(lambda a, b: jax.grad(
            lambda aa: kd_kl_loss(aa, b, 3.0, backend=backend))(a)))(sb, tb)

    np.testing.assert_allclose(np.asarray(g("pallas")), np.asarray(g("jnp")),
                               rtol=1e-4, atol=1e-6)


# ------------------------------------------------------- KuLSIF gram parity

def test_rbf_matrix_backend_parity():
    key = jax.random.PRNGKey(11)
    a = jax.random.normal(key, (300, 12))
    b = jax.random.normal(jax.random.fold_in(key, 1), (170, 12))
    o_p = dispatch.rbf_matrix(a, b, 2.5, backend="pallas")
    o_j = dispatch.rbf_matrix(a, b, 2.5, backend="jnp")
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_j),
                               rtol=1e-5, atol=1e-6)


def test_kulsif_learn_estimate_backend_parity():
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(key, (200, 12))
    tst = jax.random.normal(jax.random.fold_in(key, 1), (50, 12))
    d_j = KuLSIFDRE(sigma=3.0, num_aux=96, kernel_backend="jnp"
                    ).learn(jax.random.PRNGKey(2), x)
    d_p = KuLSIFDRE(sigma=3.0, num_aux=96, kernel_backend="pallas"
                    ).learn(jax.random.PRNGKey(2), x)
    np.testing.assert_allclose(np.asarray(d_p.alpha), np.asarray(d_j.alpha),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_p.estimate(tst)),
                               np.asarray(d_j.estimate(tst)),
                               rtol=1e-4, atol=1e-6)


# ------------------------------------------- KMeansDRE threshold (satellite)

def test_kmeans_dre_calibrated_threshold_stays_on_device():
    """The calibrated T^ID must not round-trip through the host (it used
    to be float(jnp.quantile(...))); public semantics are preserved —
    comparisons, float() and re-learn all behave as before."""
    key = jax.random.PRNGKey(17)
    x = jax.random.normal(key, (240, 12))
    dre = KMeansDRE(num_centroids=2).learn(jax.random.PRNGKey(0), x)
    assert isinstance(dre.threshold, jax.Array)       # no host sync
    frac = float(np.asarray(dre.is_id(x)).mean())
    assert abs(frac - dre.calibration_q) < 0.05
    # float() still yields the calibrated quantile
    d = np.asarray(dre.distances(x))
    assert abs(float(dre.threshold) - float(np.quantile(d, 0.95))) < 1e-4
    # a fixed threshold is passed through untouched (python float stays)
    fixed = KMeansDRE(num_centroids=1, threshold=2.5).learn(
        jax.random.PRNGKey(0), x)
    assert fixed.threshold == 2.5


# ------------------------------------------------------- trace stability

def test_backend_selection_never_retraces_round_phases():
    """Backend resolution happens at trace time and is baked into the
    compiled phases: re-running rounds — even with the ambient backend
    flipped between them — must not retrace anything."""
    from repro.fed.client import Client
    from repro.fed.cohort import CohortEngine
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
            seed=0, kernel_backend="pallas"))
    engine = CohortEngine(clients)
    px = rng.normal(size=(32, 8)).astype(np.float32)
    teacher = rng.normal(size=(32, 4)).astype(np.float32)
    w = np.ones((32,), np.float32)
    engine.cohort_local_train(0, 1, 32)
    engine.cohort_distill(0, px, teacher, w, 1, 32)
    first = len(traces)
    assert first > 0
    for ambient in ("jnp", "pallas", "auto"):
        with dispatch.kernel_backend(ambient):
            engine.cohort_local_train(0, 1, 32)
            engine.cohort_distill(0, px, teacher, w, 1, 32)
    assert len(traces) == first, (
        f"flipping the ambient kernel backend retraced a phase: "
        f"{first} -> {len(traces)} traces ({traces})")


# ------------------------------------------------------ end-to-end parity

def _run_rounds(method, engine, backend, num_devices=0, clients=4,
                round_mode="auto", zoo="auto"):
    # round_mode="auto" lets the REPRO_ROUND_MODE=overlap CI matrix entry
    # exercise these parity cases through the overlap scheduler; the
    # golden test below pins "sync" (its logs certify the lockstep order)
    cfg = FedConfig(num_clients=clients, rounds=2, method=method,
                    scenario="strong", proxy_batch=128, batch_size=32,
                    seed=0, engine=engine, num_devices=num_devices,
                    kernel_backend=backend, round_mode=round_mode, zoo=zoo)
    return simulator.run(cfg, "mnist_feat", n_train=600, n_test=200).rounds


@pytest.mark.parametrize("method", ["edgefd", "selective-fd"])
def test_e2e_pallas_loop_cohort_mesh_match_jnp(method):
    """Same-seed round logs: loop == cohort == mesh-sharded cohort under
    kernel_backend="pallas" (interpret on CPU), all within tolerance of
    the jnp backend. num_devices=-1 uses every visible device, so the CI
    4-device matrix entry exercises real sharding here."""
    loop_p = _run_rounds(method, "loop", "pallas")
    cohort_p = _run_rounds(method, "cohort", "pallas")
    mesh_p = _run_rounds(method, "cohort", "pallas", num_devices=-1)
    loop_j = _run_rounds(method, "loop", "jnp")
    for lp, cp, mp, lj in zip(loop_p, cohort_p, mesh_p, loop_j):
        np.testing.assert_allclose(lp.accs, cp.accs, atol=1e-6)
        np.testing.assert_allclose(lp.accs, mp.accs, atol=1e-6)
        np.testing.assert_allclose(lp.distill_loss, cp.distill_loss,
                                   rtol=1e-4)
        np.testing.assert_allclose(lp.distill_loss, mp.distill_loss,
                                   rtol=1e-4)
        # pallas vs jnp: same algorithm, different accumulation order
        np.testing.assert_allclose(lp.accs, lj.accs, atol=0.02)
        np.testing.assert_allclose(lp.distill_loss, lj.distill_loss,
                                   rtol=0.05)
        np.testing.assert_allclose(lp.id_fraction, lj.id_fraction, atol=0.02)


def test_default_backend_round_logs_bit_for_bit_golden():
    """The default backend on CPU (auto -> jnp) must reproduce the round
    logs recorded before the dispatch layer existed, bit for bit. The cfg
    pins kernel_backend="jnp" so the test also holds under the
    REPRO_KERNEL_BACKEND=pallas CI matrix entry — on a clean CPU host
    that IS the default (see test_resolve_auto_is_jnp_off_tpu)."""
    golden = json.loads(GOLDEN_PATH.read_text())
    cases = [("edgefd_loop", "edgefd", "loop"),
             ("edgefd_cohort", "edgefd", "cohort"),
             ("selectivefd_loop", "selective-fd", "loop")]
    for name, method, engine in cases:
        # zoo pinned too: the goldens are shared-population logs and must
        # hold under the REPRO_ZOO=mixed CI matrix entry
        new = _run_rounds(method, engine, "jnp", round_mode="sync",
                          zoo="shared")
        assert len(new) == len(golden[name])
        for g, n in zip(golden[name], new):
            assert g["accs"] == n.accs, (name, n.round)
            assert g["mean_acc"] == n.mean_acc
            assert g["local_loss"] == n.local_loss
            assert g["distill_loss"] == n.distill_loss
            assert g["id_fraction"] == n.id_fraction
            assert g["bytes_up"] == n.bytes_up
            assert g["bytes_down"] == n.bytes_down
