"""Kill-and-resume: snapshot/restore bit-for-bit across engines and modes.

The headline guarantee of the resumable service (launch/fed_serve): kill
at any phase boundary, resume from the last checkpoint, and completed
round logs are bit-for-bit identical to the uninterrupted run. The
in-process tests exercise every phase boundary of a middle round through
``RoundScheduler.snapshot()/restore()`` directly; the subprocess tests
cover the mesh-sharded engine (forced 4-device host) and the real
SIGKILL-the-process path through ``fed_serve``'s crash hook.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import _resume_prog
from _resume_prog import build_sched, check_resume, strip
from repro.common.types import FedConfig


@pytest.mark.parametrize("round_mode", ["sync", "overlap"])
def test_loop_resume_every_boundary(round_mode):
    """Loop engine, partial participation + staleness: restore from every
    phase boundary of round 1 replays the rest bit-for-bit."""
    n = check_resume("loop", 0, round_mode)
    assert n == 5  # one snapshot per phase of the crash round


def test_cohort_resume_inflight_boundaries():
    """Cohort engine under overlap: the boundaries where round 1 is
    genuinely in flight (reports pending, stacked state mid-round)."""
    n = check_resume("cohort", 0, "overlap",
                     boundaries=("report", "aggregate", "distill"))
    assert n == 3


def test_mesh_resume_and_cross_engine_forced_devices():
    """Mesh-sharded engine on 4 forced host devices: same-engine resume is
    bit-for-bit, and a mesh checkpoint restores into the unsharded loop
    engine (and vice versa) within the mesh-parity tolerance. jax fixes
    the device count at first init, so single-device hosts re-run
    tests/_resume_prog.py in a subprocess."""
    if jax.device_count() >= 4:
        _resume_prog.check_resume("cohort", 4, "overlap")
        _resume_prog.check_cross_engine("cohort", 4, "loop", 0)
        _resume_prog.check_cross_engine("loop", 0, "cohort", 4)
        return
    here = os.path.dirname(os.path.abspath(__file__))
    prog = os.path.join(here, "_resume_prog.py")
    src = os.path.abspath(os.path.join(here, "..", "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, prog, "--devices", "4", "--engine", "cohort",
         "--round-mode", "overlap", "--cross"],
        env=env, capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, (
        f"mesh resume subprocess failed:\n{res.stdout}\n{res.stderr}")
    assert "RESUME-OK" in res.stdout and "CROSS-OK" in res.stdout, res.stdout


def test_fed_serve_sigkill_resume(tmp_path):
    """The real crash harness: fed_serve SIGKILLs itself at a phase
    boundary of round 1 (overlap, so round-0's checkpoint carries round-1
    in-flight state), a second invocation resumes from the checkpoint, and
    the log history matches an uninterrupted service bit-for-bit."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.abspath(os.path.join(here, "..", "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    common = [sys.executable, "-m", "repro.launch.fed_serve",
              "--clients", "3", "--rounds", "2", "--n-train", "256",
              "--n-test", "64", "--round-mode", "overlap",
              "--participation", "0.75", "--staleness-decay", "0.5",
              "--fixed-phase-costs"]
    ckpt = str(tmp_path / "svc")

    crashed = subprocess.run(
        common + ["--ckpt-dir", ckpt, "--ckpt-every", "1",
                  "--crash-after-phase", "aggregate:1"],
        env=env, capture_output=True, text=True, timeout=540)
    assert crashed.returncode == -9, (  # died by its own SIGKILL
        f"expected SIGKILL exit, got {crashed.returncode}:\n"
        f"{crashed.stdout}\n{crashed.stderr}")
    assert os.path.exists(os.path.join(ckpt, "ckpt_00000001.npz"))

    # the retired-log sidecar is appended before each checkpoint, so it
    # survives the SIGKILL alongside the checkpoint it belongs to
    sidecar = os.path.join(ckpt, "logs.jsonl")
    assert os.path.exists(sidecar)

    resumed = subprocess.run(
        common + ["--ckpt-dir", ckpt, "--ckpt-every", "1", "--resume",
                  "--json", str(tmp_path / "resumed.json")],
        env=env, capture_output=True, text=True, timeout=540)
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    assert "resumed from checkpoint step 1" in resumed.stdout

    # after the resumed service finishes, the sidecar holds exactly the
    # full retired history (checkpoints themselves carry no logs)
    with open(sidecar) as f:
        side = [json.loads(ln) for ln in f if ln.strip()]
    assert [d["round"] for d in side] == [0, 1]

    ref = subprocess.run(
        common + ["--json", str(tmp_path / "ref.json")],
        env=env, capture_output=True, text=True, timeout=540)
    assert ref.returncode == 0, ref.stdout + ref.stderr

    def load(p):
        with open(p) as f:
            return [_resume_prog.strip_measured(d) for d in json.load(f)]
    assert load(tmp_path / "resumed.json") == load(tmp_path / "ref.json")


def test_logs_tail_keeps_checkpoint_bytes_flat(tmp_path):
    """Streaming history out of the snapshot (satellite of the robustness
    PR): with ``logs_tail=0`` the checkpoint carries a monotone
    ``completed`` counter instead of the log list, so checkpoint bytes
    stop growing with service age — while full snapshots demonstrably
    grow round over round. The tail-less tree still restores (fed_serve
    reconstructs history from the sidecar)."""
    from repro.checkpoint import save_state
    from repro.fed.state import ExperimentState

    cfg = FedConfig(num_clients=4, rounds=4, method="edgefd",
                    scenario="strong", proxy_batch=64, batch_size=32,
                    seed=0, round_mode="sync")
    sched = build_sched(cfg)
    sched.begin(0, cfg.rounds)
    flat_sizes, full_sizes, trees = [], [], []
    done = 0
    while sched.has_pending():
        _, _, log = sched.step()
        if log is None:
            continue
        done += 1
        d_flat, d_full = str(tmp_path / f"flat{done}"), str(
            tmp_path / f"full{done}")
        p1 = save_state(d_flat, done, sched.snapshot(logs_tail=0).to_tree())
        p2 = save_state(d_full, done, sched.snapshot().to_tree())
        flat_sizes.append(os.path.getsize(p1))
        full_sizes.append(os.path.getsize(p2))
        trees.append(sched.snapshot(logs_tail=0).to_tree())
    assert done == 4
    # full snapshots grow with history; tail-less ones stay flat
    assert full_sizes[-1] > full_sizes[0]
    assert max(flat_sizes) - min(flat_sizes) < 512
    # a tail-less tree restores, with completed preserved and logs empty
    s2 = build_sched(cfg)
    s2.restore(ExperimentState.from_tree(trees[1]))
    assert s2.completed == 2 and s2.logs == []
    s2.drain()
    ref = build_sched(cfg).run_rounds(0, cfg.rounds)
    assert strip(s2.logs) == strip(ref[2:])


def test_backpressure_ages_never_negative():
    """Event-ordered admission under a tight report budget: overflow
    clients drain through the staleness buffer with ages moving only
    forward — mean staleness and buffer ages never go negative, and the
    cap demonstrably rejects reports under overlap."""
    cfg = FedConfig(num_clients=6, rounds=4, method="edgefd",
                    scenario="strong", proxy_batch=64, batch_size=32,
                    seed=1, round_mode="overlap", max_inflight=2,
                    staleness_decay=0.5, max_pending_reports=3,
                    straggler_factor=4.0)
    sched = build_sched(cfg)
    logs = sched.run_rounds(0, cfg.rounds)
    assert all(lg.mean_staleness >= 0.0 for lg in logs)
    # the cap binds: some round admitted fewer reporters than the fleet
    assert any(lg.participants is not None and len(lg.participants) < 6
               for lg in logs)
    buf = sched.server._stale
    assert buf is not None
    ages = logs[-1].round - np.asarray(buf.last_round)[buf.reported]
    assert (ages >= 0).all()


def test_server_distill_resume_every_boundary():
    """FedDF ensemble server: the student's params/opt/rng ride the
    checkpoint, so restoring at any boundary of round 1 — including the
    new server_distill phase — replays the rest bit-for-bit."""
    n = check_resume("loop", 0, "sync", method="server_distill")
    assert n == 6  # six phases: the extra one is server_distill


def test_concurrent_cohort_resume_boundaries():
    """Mixed zoo + per-cohort phase nodes under overlap: every cohort
    node of round 1 is a kill boundary, and the interleaved schedule
    resumes bit-for-bit."""
    n = check_resume("cohort", 0, "overlap", zoo="mixed",
                     concurrent_cohorts=True)
    # 4 clients cycle into 3 cohorts (cid % 3): 3 client phases x 3
    # cohort nodes + aggregate + eval
    assert n == 11


def test_snapshot_restore_preserves_event_loop_bookkeeping():
    """Structural round-trip: pending/done/trace/sim-times survive the
    tree form (JSON manifest types), and restore rejects a round-mode
    mismatch."""
    cfg = FedConfig(num_clients=4, rounds=3, method="edgefd",
                    scenario="strong", proxy_batch=64, batch_size=32,
                    seed=0, round_mode="overlap", max_inflight=2)
    s1 = build_sched(cfg)
    s1.begin(0, cfg.rounds)
    for _ in range(7):
        s1.step()
    tree = s1.snapshot().to_tree()

    s2 = build_sched(cfg)
    s2.restore(tree)
    assert s2._pending == s1._pending
    assert s2._done == s1._done
    assert s2.trace == s1.trace
    assert s2._sim_end == s1._sim_end
    assert strip(s2.logs) == strip(s1.logs)

    cfg_sync = FedConfig(num_clients=4, rounds=3, method="edgefd",
                         scenario="strong", proxy_batch=64, batch_size=32,
                         seed=0, round_mode="sync")
    s3 = build_sched(cfg_sync)
    with pytest.raises(ValueError, match="round_mode"):
        s3.restore(tree)
