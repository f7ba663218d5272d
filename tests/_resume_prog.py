"""Kill-and-resume checker (shared by test + subprocess modes).

``check_resume`` runs an experiment to completion capturing a
``RoundScheduler.snapshot()`` at every phase boundary of a middle round,
then for each boundary rebuilds the experiment from scratch (fresh-process
semantics), restores, drains, and asserts the completed round logs are
bit-for-bit identical to the uninterrupted run. ``check_cross_engine``
saves under one engine and restores under another (the engine checkpoint
format is keyed per client), asserting parity within the engine tolerance.

jax fixes the device count at first init, so the mesh-sharded cases re-run
this file as a subprocess with ``--xla_force_host_platform_device_count``
set when too few devices are visible (see tests/test_resume.py)::

    PYTHONPATH=src python tests/_resume_prog.py --devices 4 --engine cohort
"""
from __future__ import annotations

import dataclasses

# deterministic sim pricing so the timeline fields are comparable
FIXED_COSTS = {"local_train": 1.0, "report": 0.1, "aggregate": 0.3,
               "distill": 1.0, "eval": 0.0}
# host-measured wall-clock and the programs a process happened to build
# ("counters.compiles": a resumed process builds its own) can never match
# across runs; everything else must be bit-for-bit
MEASURED_FIELDS = ("wall_s", "phase_s", "counters.compiles")


def _cfg(engine: str, devices: int, round_mode: str, **kw):
    from repro.common.types import FedConfig
    base = dict(num_clients=4, rounds=3, method="edgefd", scenario="strong",
                proxy_batch=64, batch_size=32, lr=1e-2, seed=0,
                engine=engine, num_devices=devices, round_mode=round_mode,
                max_inflight=2, participation_fraction=0.75,
                staleness_decay=0.5)
    base.update(kw)
    return FedConfig(**base)


def build_sched(cfg, dataset: str = "mnist_feat"):
    import jax

    from repro.core.methods import get_method
    from repro.fed.scheduler import RoundScheduler
    from repro.fed.simulator import build_engine, build_experiment
    clients, server, x_test, y_test = build_experiment(
        cfg, dataset, n_train=400, n_test=100, mlp_hidden=(16,))
    engine = build_engine(clients, cfg)
    method = get_method(cfg.method)
    if method.client_filter != "none":
        engine.learn_dres(jax.random.PRNGKey(cfg.seed))
    return RoundScheduler(engine, server, method, cfg, x_test, y_test,
                          sim_phase_costs=FIXED_COSTS)


def strip_measured(d: dict) -> dict:
    """A round log's dict without its ``MEASURED_FIELDS`` (``a.b`` names
    key ``b`` of the dict field ``a``)."""
    out = {k: v for k, v in d.items() if k not in MEASURED_FIELDS}
    for name in MEASURED_FIELDS:
        field, _, key = name.partition(".")
        if key and field in out:
            out[field] = {k: v for k, v in out[field].items() if k != key}
    return out


def strip(logs):
    return [strip_measured(dataclasses.asdict(lg)) for lg in logs]


def check_resume(engine: str, devices: int, round_mode: str,
                 crash_round: int = 1, boundaries=None,
                 dataset: str = "mnist_feat", **cfg_kw) -> int:
    """Snapshot at every phase boundary of ``crash_round``; resume each."""
    cfg = _cfg(engine, devices, round_mode, **cfg_kw)
    ref_sched = build_sched(cfg, dataset)
    ref_sched.begin(0, cfg.rounds)
    snaps = []
    while ref_sched.has_pending():
        phase, r, _ = ref_sched.step()
        if r == crash_round and (boundaries is None or phase in boundaries):
            snaps.append(((phase, r), ref_sched.snapshot().to_tree()))
    ref = strip(ref_sched.logs)
    assert snaps, "crash round never executed"
    for (phase, r), tree in snaps:
        sched = build_sched(cfg, dataset)  # fresh-process semantics
        sched.restore(tree)
        sched.drain()
        got = strip(sched.logs)
        assert got == ref, (
            f"resume from boundary ({phase}, {r}) diverged "
            f"[engine={engine} devices={devices} mode={round_mode}]")
    return len(snaps)


def check_cross_engine(save_engine: str, save_devices: int,
                       load_engine: str, load_devices: int,
                       round_mode: str = "sync") -> None:
    """Save under one engine layout, restore under another.

    Engines agree within 1e-5 (the mesh-parity tolerance), not bitwise, so
    the restored run is compared to an uninterrupted run of the *loading*
    engine."""
    import numpy as np
    cfg_save = _cfg(save_engine, save_devices, round_mode)
    cfg_load = _cfg(load_engine, load_devices, round_mode)

    s1 = build_sched(cfg_save)
    s1.begin(0, cfg_save.rounds)
    tree = None
    while s1.has_pending():
        phase, r, _ = s1.step()
        if (phase, r) == ("eval", 0):  # a retired-round boundary
            tree = s1.snapshot().to_tree()

    s2 = build_sched(cfg_load)
    s2.restore(tree)
    s2.drain()

    s3 = build_sched(cfg_load)  # uninterrupted reference
    logs_ref = s3.run_rounds(0, cfg_load.rounds)
    assert len(s2.logs) == len(logs_ref)
    for got, ref in zip(s2.logs[1:], logs_ref[1:]):  # round 0 ran on saver
        np.testing.assert_allclose(got.accs, ref.accs, rtol=0.0, atol=1e-5)
        np.testing.assert_allclose(got.local_loss, ref.local_loss,
                                   rtol=0.0, atol=1e-5)
        np.testing.assert_allclose(got.distill_loss, ref.distill_loss,
                                   rtol=0.0, atol=1e-5)
        assert got.participants == ref.participants


def main(argv=None) -> None:
    import argparse
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--engine", default="cohort")
    ap.add_argument("--round-mode", default="overlap")
    ap.add_argument("--model-shards", type=int, default=0,
                    help="2-D (clients, model) mesh: fold --devices into "
                         "a (devices // M, M) mesh for the sharded runs")
    ap.add_argument("--dataset", default="mnist_feat")
    ap.add_argument("--cross", action="store_true",
                    help="also check mesh<->loop cross-engine restore")
    args = ap.parse_args(argv)

    # must happen before the first jax import (device count is init-time)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    assert jax.device_count() >= args.devices, (
        f"forced {args.devices} host devices but jax sees "
        f"{jax.device_count()} — XLA_FLAGS arrived after jax init?")
    n = check_resume(args.engine, args.devices, args.round_mode,
                     model_shards=args.model_shards, dataset=args.dataset)
    print(f"RESUME-OK engine={args.engine} devices={args.devices} "
          f"model_shards={args.model_shards} dataset={args.dataset} "
          f"mode={args.round_mode} boundaries={n}")
    if args.cross:
        check_cross_engine("cohort", args.devices, "loop", 0)
        check_cross_engine("loop", 0, "cohort", args.devices)
        print(f"CROSS-OK mesh@{args.devices}<->loop")


if __name__ == "__main__":
    main()
