"""Transformer federated scenario + 2-D (clients, model) mesh acceptance.

Pins the ISSUE-10 parity chain: loop == cohort == 2-D-mesh-sharded round
logs for a transformer cohort (``lm_tokens`` — every client a reduced
granite backbone, ``core/fd_trainer.TransformerClientModel``) within the
established engine tolerance, and kill-and-resume through a model-sharded
round staying bit-for-bit. jax fixes the device count at first init, so
multi-device cases run in-process on a >=4-device host (the CI matrix's
forced-host-device entries) and re-run the shared checker programs in a
subprocess elsewhere.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import _mesh_parity_prog
from repro.common.types import FedConfig
from repro.fed import simulator

TOL = dict(rtol=0.0, atol=1e-5)


def _run(engine, num_devices=0, model_shards=0, **kw):
    base = dict(num_clients=3, rounds=2, proxy_batch=64, batch_size=16,
                lr=1e-2, seed=0, engine=engine, num_devices=num_devices,
                model_shards=model_shards)
    base.update(kw)
    return simulator.run(FedConfig(**base), "lm_tokens",
                         n_train=300, n_test=150)


def _assert_logs_match(a, b, exact=False):
    assert len(a.rounds) == len(b.rounds)
    for rl, rc in zip(a.rounds, b.rounds):
        if exact:
            np.testing.assert_array_equal(rl.accs, rc.accs)
            assert rl.local_loss == rc.local_loss
            assert rl.distill_loss == rc.distill_loss
            assert rl.id_fraction == rc.id_fraction
        else:
            np.testing.assert_allclose(rl.accs, rc.accs, **TOL)
            np.testing.assert_allclose(rl.local_loss, rc.local_loss, **TOL)
            np.testing.assert_allclose(rl.distill_loss, rc.distill_loss,
                                       **TOL)
            np.testing.assert_allclose(rl.id_fraction, rc.id_fraction, **TOL)
        assert rl.bytes_up == rc.bytes_up


def _subprocess_env():
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.abspath(os.path.join(here, "..", "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return here, env


def test_transformer_loop_cohort_parity():
    """The engine stack treats transformer clients like any other cohort:
    vmapped execution must reproduce the per-client loop."""
    _assert_logs_match(_run("loop"), _run("cohort"))


def test_transformer_learns_the_bands():
    """Sanity: the reduced backbone actually learns the vocab-band task —
    final accuracy beats the 1/32 chance floor with headroom."""
    res = _run("cohort", rounds=3)
    assert res.final_acc > 3.0 / 32.0


def test_transformer_2d_mesh_parity():
    """loop == cohort == 2-D-mesh-sharded (2x2 forced host devices) for a
    transformer cohort — the ISSUE-10 acceptance pin."""
    if jax.device_count() >= 4:
        _mesh_parity_prog.check_parity(4, 4, model_shards=2,
                                       dataset="lm_tokens",
                                       n_train=300, n_test=150)
        return
    here, env = _subprocess_env()
    res = subprocess.run(
        [sys.executable, os.path.join(here, "_mesh_parity_prog.py"),
         "--devices", "4", "--clients", "4", "--model-shards", "2",
         "--dataset", "lm_tokens"],
        env=env, capture_output=True, text=True, timeout=480)
    assert res.returncode == 0, (
        f"2-D mesh parity subprocess failed:\n{res.stdout}\n{res.stderr}")
    assert "PARITY-OK" in res.stdout, res.stdout


def test_transformer_2d_mesh_parity_pallas_kernels():
    """The 2-D parity with the flash-attention kernel on every engine: on
    the (clients, model) mesh it runs under ``shard_map``, attention split
    over heads on the model axis. At this vocabulary (32) distill-KL takes
    its K < 128 jnp route; ``test_kernel_dispatch`` runs its kernel under a
    mesh at K = 128."""
    if jax.device_count() >= 4:
        _mesh_parity_prog.check_parity(4, 4, model_shards=2,
                                       dataset="lm_tokens",
                                       n_train=300, n_test=150,
                                       kernel_backend="pallas")
        return
    here, env = _subprocess_env()
    res = subprocess.run(
        [sys.executable, os.path.join(here, "_mesh_parity_prog.py"),
         "--devices", "4", "--clients", "4", "--model-shards", "2",
         "--dataset", "lm_tokens", "--kernel-backend", "pallas"],
        env=env, capture_output=True, text=True, timeout=480)
    assert res.returncode == 0, (
        f"2-D mesh parity subprocess failed:\n{res.stdout}\n{res.stderr}")
    assert "PARITY-OK" in res.stdout, res.stdout


def test_model_shards_env_is_inert_without_mesh(monkeypatch):
    """$REPRO_MODEL_SHARDS (the CI matrix vehicle) must never change a
    meshless run: engine selection ignores it when num_devices == 0, so
    every existing golden stays bit-for-bit under the env."""
    base = _run("cohort")
    monkeypatch.setenv("REPRO_MODEL_SHARDS", "2")
    under_env = _run("cohort")
    _assert_logs_match(base, under_env, exact=True)


def test_sharded_kill_and_resume_bit_for_bit():
    """Kill-and-resume through a model-sharded round: snapshot at every
    phase boundary of a middle round on the 2-D mesh, restore fresh, and
    the completed logs must be bit-for-bit the uninterrupted run's."""
    if jax.device_count() >= 4:
        import _resume_prog
        n = _resume_prog.check_resume("cohort", 4, "overlap",
                                      model_shards=2)
        assert n > 0
        return
    here, env = _subprocess_env()
    res = subprocess.run(
        [sys.executable, os.path.join(here, "_resume_prog.py"),
         "--devices", "4", "--engine", "cohort", "--round-mode", "overlap",
         "--model-shards", "2"],
        env=env, capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, (
        f"sharded resume subprocess failed:\n{res.stdout}\n{res.stderr}")
    assert "RESUME-OK" in res.stdout, res.stdout


def test_engine_from_config_builds_2d_mesh():
    """FedConfig.model_shards reaches the cohort engine's mesh (and the
    loop engine rejects it legibly)."""
    from repro.core.protocol import as_engine
    with pytest.raises(ValueError, match="cohort"):
        as_engine([], "loop", model_shards=2)
    if jax.device_count() >= 4:
        from repro.fed.client import Client  # noqa: F401  (import check)
        cfg = FedConfig(num_clients=4, rounds=1, seed=0, engine="cohort",
                        num_devices=4, model_shards=2, batch_size=16,
                        proxy_batch=64)
        from repro.fed.simulator import build_engine, build_experiment
        clients, _, _, _ = build_experiment(cfg, "lm_tokens", n_train=200,
                                            n_test=100)
        engine = build_engine(clients, cfg)
        mesh = engine.cohorts[0].mesh
        assert mesh.axis_names == ("clients", "model")
        assert mesh.devices.shape == (2, 2)
