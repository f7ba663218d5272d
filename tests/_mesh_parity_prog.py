"""Multi-device cohort parity checker (shared by test + subprocess modes).

``check_parity`` runs the same experiment through the loop engine, the
unsharded cohort engine, and the mesh-sharded cohort engine, and asserts the
round logs match within the acceptance tolerance (1e-5).

jax fixes the device count at first init, so a single-device pytest process
cannot build a 4-device mesh; ``tests/test_cohort_parity.py`` re-runs this
file as a subprocess with ``--xla_force_host_platform_device_count`` set
when too few devices are visible (and calls ``check_parity`` directly when
CI already forced a multi-device host — see .github/workflows/ci.yml).

    PYTHONPATH=src python tests/_mesh_parity_prog.py --devices 4 --clients 4 5
"""
from __future__ import annotations

TOL = dict(rtol=0.0, atol=1e-5)


def check_parity(num_clients: int, devices: int, method: str = "edgefd",
                 scenario: str = "strong",
                 participation_fraction: float = 1.0,
                 participation_policy: str = "uniform",
                 staleness_decay: float = 0.0,
                 round_mode: str = "auto",
                 max_inflight: int = 2, rounds: int = 2,
                 model_shards: int = 0, dataset: str = "mnist_feat",
                 n_train: int = 800, n_test: int = 300, **cfg_kw) -> None:
    import numpy as np

    from repro.common.types import FedConfig
    from repro.fed import simulator

    results = {}
    for name, engine, ndev, ms in (("loop", "loop", 0, 0),
                                   ("cohort", "cohort", 0, 0),
                                   ("mesh", "cohort", devices, model_shards)):
        cfg = FedConfig(num_clients=num_clients, rounds=rounds, method=method,
                        scenario=scenario, proxy_batch=120, batch_size=32,
                        lr=1e-2, seed=0, engine=engine, num_devices=ndev,
                        model_shards=ms,
                        participation_fraction=participation_fraction,
                        participation_policy=participation_policy,
                        staleness_decay=staleness_decay,
                        round_mode=round_mode, max_inflight=max_inflight,
                        **cfg_kw)
        results[name] = simulator.run(cfg, dataset,
                                      n_train=n_train, n_test=n_test)
    base = results["loop"]
    for name in ("cohort", "mesh"):
        other = results[name]
        assert len(base.rounds) == len(other.rounds)
        for rl, rc in zip(base.rounds, other.rounds):
            np.testing.assert_allclose(rl.accs, rc.accs, **TOL)
            np.testing.assert_allclose(rl.mean_acc, rc.mean_acc, **TOL)
            np.testing.assert_allclose(rl.local_loss, rc.local_loss, **TOL)
            np.testing.assert_allclose(rl.distill_loss, rc.distill_loss,
                                       **TOL)
            np.testing.assert_allclose(rl.id_fraction, rc.id_fraction, **TOL)
            np.testing.assert_allclose(rl.mean_staleness, rc.mean_staleness,
                                       **TOL)
            assert rl.participants == rc.participants
            assert rl.bytes_up == rc.bytes_up
            assert rl.bytes_down == rc.bytes_down


def main(argv=None) -> None:
    import argparse
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--clients", type=int, nargs="+", default=[4, 5])
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--policy", default="uniform")
    ap.add_argument("--staleness-decay", type=float, default=0.0)
    ap.add_argument("--round-mode", default="auto")
    ap.add_argument("--max-inflight", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--model-shards", type=int, default=0,
                    help="2-D mesh for the sharded entry: fold --devices "
                         "into a (devices // M, M) (clients, model) mesh")
    ap.add_argument("--dataset", default="mnist_feat")
    ap.add_argument("--fault-mode", default="none")
    ap.add_argument("--byzantine-frac", type=float, default=0.0)
    ap.add_argument("--fault-prob", type=float, default=0.0)
    ap.add_argument("--robust-aggregation", default="mean")
    ap.add_argument("--kernel-backend", default="auto",
                    help="pallas: every engine runs the kernels (interpret "
                         "mode off-TPU), so the mesh entry runs them under "
                         "shard_map")
    args = ap.parse_args(argv)

    # must happen before the first jax import (device count is init-time)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    assert jax.device_count() >= args.devices, (
        f"forced {args.devices} host devices but jax sees "
        f"{jax.device_count()} — XLA_FLAGS arrived after jax init?")
    for c in args.clients:
        check_parity(c, args.devices,
                     model_shards=args.model_shards,
                     dataset=args.dataset,
                     participation_fraction=args.participation,
                     participation_policy=args.policy,
                     staleness_decay=args.staleness_decay,
                     round_mode=args.round_mode,
                     max_inflight=args.max_inflight, rounds=args.rounds,
                     fault_mode=args.fault_mode,
                     byzantine_frac=args.byzantine_frac,
                     fault_prob=args.fault_prob,
                     robust_aggregation=args.robust_aggregation,
                     kernel_backend=args.kernel_backend)
        print(f"PARITY-OK clients={c} devices={args.devices} "
              f"model_shards={args.model_shards} dataset={args.dataset} "
              f"participation={args.participation} "
              f"round_mode={args.round_mode} "
              f"fault_mode={args.fault_mode} "
              f"kernel_backend={args.kernel_backend}")


if __name__ == "__main__":
    main()
