"""Spans and counters of the round (``repro.common.tracing``).

On a C=4 cohort round: the program's spans nest as the module's table
says and carry their round; ``RoundLog.counters`` counts the device->host
reads the round makes and the programs JAX built in it; ``phase_s`` keeps
its meaning; every cohort program compiles under its ``jit_cohort_*``
name, and the server's reduce as ``jit_server_aggregate``.
"""
import dataclasses
import glob

import jax
import pytest

from repro.common.types import FedConfig
from repro.core.methods import get_method
from repro.fed import server as server_mod
from repro.fed import simulator
from repro.fed.scheduler import RoundScheduler, round_phases

SPANS = ("sched.step", "server.ingest", "server.aggregate", "server.fetch",
         "cohort.plan", "cohort.stage", "cohort.launch", "cohort.fetch")
PROGRAMS = {"_train": "train", "_distill": "distill",
            "_distill_private": "distill_private", "_predict": "predict",
            "_eval": "eval", "_classwise": "classwise",
            "_kmeans_masks": "kmeans_masks", "_kulsif_masks": "kulsif_masks"}
# device->host reads of one sync edgefd round over one C=4 cohort: the
# engine reads the training losses, the proxy logits, the filter masks,
# the distillation losses and the eval counts; the server reads
# (teacher, valid, uploaded-row count) of its one compiled reduce in one read
EDGEFD_ENGINE_SYNCS = 5
EDGEFD_SERVER_SYNCS = 1


def _sched(method="edgefd", engine="cohort", **kw):
    cfg = FedConfig(num_clients=4, rounds=4, method=method,
                    scenario="strong", proxy_batch=64, batch_size=32,
                    lr=1e-2, seed=0, engine=engine, round_mode="sync",
                    **kw)
    clients, server, x_test, y_test = simulator.build_experiment(
        cfg, "mnist_feat", n_train=400, n_test=100, mlp_hidden=(16,))
    eng = simulator.build_engine(clients, cfg)
    m = get_method(method)
    if m.client_filter != "none":
        eng.learn_dres(jax.random.PRNGKey(cfg.seed))
    return RoundScheduler(eng, server, m, cfg, x_test, y_test)


def _round(sched, r):
    sched.begin(r, 1)
    sched.drain()
    return sched.logs[-1]


def _spans(trace_dir):
    """Every program span in the trace: (name, start, end, stats)."""
    from jax.profiler import ProfileData
    files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    assert files, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS or e.name.startswith("phase."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _parent(spans, s):
    """The innermost program span that holds ``s`` (None at the top)."""
    holders = [p for p in spans if p is not s and p[1] <= s[1]
               and s[2] <= p[2] and (p[2] - p[1]) >= (s[2] - s[1])]
    return min(holders, key=lambda p: p[2] - p[1])[0] if holders else None


def test_spans_nest_and_carry_their_round(tmp_path):
    sched = _sched()
    _round(sched, 0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _round(sched, 1)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    names = {s[0] for s in spans}
    phases = round_phases(get_method("edgefd"))
    assert names == set(SPANS) | {"phase." + p for p in phases}
    assert all(s[3].get("round") == 1 for s in spans), \
        [s for s in spans if s[3].get("round") != 1]
    parents = {}
    for s in spans:
        parents.setdefault(s[0], set()).add(_parent(spans, s))
    assert parents["sched.step"] == {None}
    assert parents["server.ingest"] == {"sched.step"}
    assert parents["server.aggregate"] == {"phase.aggregate"}
    assert parents["server.fetch"] == {"server.aggregate"}
    for p in phases:
        assert parents["phase." + p] == {"sched.step"}
    client_phases = {"phase.local_train", "phase.report", "phase.distill",
                     "phase.eval"}
    for name in ("cohort.plan", "cohort.stage", "cohort.launch",
                 "cohort.fetch"):
        assert parents[name] <= client_phases, (name, parents[name])
    assert len([s for s in spans if s[0] == "sched.step"]) == len(phases)
    assert len([s for s in spans if s[0] == "cohort.fetch"]) \
        == EDGEFD_ENGINE_SYNCS
    assert len([s for s in spans if s[0] == "server.fetch"]) \
        == EDGEFD_SERVER_SYNCS


def test_counters_count_syncs_and_compiles():
    sched = _sched()
    logs = [_round(sched, r) for r in range(3)]
    for lg in logs:
        assert lg.counters["engine.syncs"] == EDGEFD_ENGINE_SYNCS
        assert lg.counters["server.syncs"] == EDGEFD_SERVER_SYNCS
        assert lg.wall_s == pytest.approx(sum(lg.phase_s.values()))
    assert logs[0].counters["compiles"] > 0
    assert logs[2].counters["compiles"] == 0
    # a new proxy batch changes the report's and the distillation's
    # shapes: their programs are built again, and the round counts them
    sched.cfg = dataclasses.replace(sched.cfg, proxy_batch=32)
    assert _round(sched, 3).counters["compiles"] > 0


def test_counters_ride_the_round_state():
    sched = _sched()
    sched.begin(0, 1)
    sched.step()                                  # local_train
    tree = sched.snapshot().to_tree()
    assert tree["scheduler"]["states"][0]["counters"]["engine.syncs"] == 1
    again = _sched()
    again.restore(tree)
    again.drain()
    assert again.logs[-1].counters["engine.syncs"] == EDGEFD_ENGINE_SYNCS


def _plan_groups(sched):
    """Size groups of one edgefd round: the private sizes of each cohort's
    ``local_train`` plan, and one shared proxy size for ``distill``."""
    return sum(len(set(c.n.tolist())) + 1 for c in sched.engine.cohorts)


def test_plan_groups_count_the_distinct_sizes():
    sched = _sched()
    assert len(sched.engine.cohorts) == 1
    for r in range(2):
        assert _round(sched, r).counters["engine.plan_groups"] \
            == _plan_groups(sched)


def test_plan_groups_ride_the_round_state():
    sched = _sched()
    sched.begin(0, 1)
    sched.step()                                  # local_train
    tree = sched.snapshot().to_tree()
    private = _plan_groups(sched) - 1
    assert tree["scheduler"]["states"][0]["counters"][
        "engine.plan_groups"] == private
    again = _sched()
    again.restore(tree)
    again.drain()
    assert again.logs[-1].counters["engine.plan_groups"] == private + 1


def test_loop_engine_books_no_plan_groups():
    assert _round(_sched(engine="loop"), 0).counters[
        "engine.plan_groups"] == 0


def test_loop_engine_books_server_syncs_only():
    lg = _round(_sched(engine="loop"), 0)
    assert lg.counters["engine.syncs"] == 0
    assert lg.counters["server.syncs"] == EDGEFD_SERVER_SYNCS


@pytest.mark.parametrize("method", ["edgefd", "fkd", "selective-fd"])
def test_cohort_programs_lower_under_their_names(method, monkeypatch):
    sched = _sched(method)
    seen = {}
    program = server_mod.server_aggregate

    def record_server(*args, **kwargs):
        seen["server"] = (program, args, kwargs)
        return program(*args, **kwargs)
    monkeypatch.setattr(server_mod, "server_aggregate", record_server)
    for c in sched.engine.cohorts:
        for attr in PROGRAMS:
            fn = getattr(c, attr)

            def record(*args, fn=fn, attr=attr):
                seen[attr] = (fn, args, {})
                return fn(*args)
            setattr(c, attr, record)
    _round(sched, 0)
    want = {"edgefd": {"_train", "_predict", "_kmeans_masks", "_distill",
                       "_eval", "server"},
            "fkd": {"_train", "_classwise", "_distill_private", "_eval"},
            "selective-fd": {"_train", "_predict", "_kulsif_masks",
                             "_distill", "_eval", "server"}}[method]
    assert set(seen) == want
    for attr, (fn, args, kwargs) in seen.items():
        text = fn.lower(*args, **kwargs).as_text()
        name = ("jit_server_aggregate" if attr == "server"
                else f"jit_cohort_{PROGRAMS[attr]}")
        assert f"@{name}" in text, attr
        assert "jit_wrapped" not in text
