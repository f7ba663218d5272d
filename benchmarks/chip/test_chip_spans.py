"""The readers of the program's spans (``fdbench.spans``) on a hand-built
trace whose numbers are known, and on a small trace of the
``mlp_feat512.c100_iid`` cell recorded on a TPU v5e."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from fdbench import harness, spans, xplane

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "testdata" / "mlp_feat512.c100_iid.spans.xplane.pb"
READERS = ("sched.own_s", "engine.plan_s", "engine.fetch_s",
           "server.ingest_s", "engine.syncs", "server.syncs",
           "device.idle_in_server_s")

# one round [0, 1000] us of two scheduler steps; (name, start us, end us)
PROGRAM = [("sched.step", 0, 400), ("phase.report", 10, 300),
           ("cohort.plan", 10, 40), ("cohort.launch", 40, 60),
           ("cohort.fetch", 60, 280), ("server.ingest", 310, 390),
           ("sched.step", 400, 900), ("phase.aggregate", 410, 880),
           ("server.aggregate", 420, 870), ("server.fetch", 430, 500),
           ("server.fetch", 600, 700)]
# device busy [60, 250], [440, 480], [650, 690]; idle [0, 60], [250, 440],
# [480, 650], [690, 1000]: 730 us
DEVICE = [("%fusion.1 = f32[8]{0} fusion(f32[8])", 60, 250),
          ("%fusion.2 = f32[8]{0} fusion(f32[8])", 440, 480),
          ("%fusion.3 = f32[8]{0} fusion(f32[8])", 650, 690)]
# idle us by innermost span: each span's idle less its children's
IDLE_BY_SPAN = {"cohort.plan": 30, "cohort.launch": 20, "cohort.fetch": 30,
                "phase.report": 20, "server.ingest": 80, "sched.step": 60,
                "server.fetch": 90, "server.aggregate": 280,
                "phase.aggregate": 20, spans.NO_SPAN: 100}


def _plane(pid, name, line, events, stats=None):
    """A text-proto plane; ``stats`` gives each event's int stats."""
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    keys = sorted({k for st in (stats or []) for k in st})
    sids = {k: i + 1 for i, k in enumerate(keys)}
    evs = ""
    for j, (n, a, b) in enumerate(events):
        st = "".join(f"stats {{ metadata_id: {sids[k]} int64_value: {v} }} "
                     for k, v in (stats[j] if stats else {}).items())
        evs += (f"events {{ metadata_id: {ids[n]} offset_ps: {a * 1000000} "
                f"duration_ps: {(b - a) * 1000000} {st}}}\n")
    meta = "".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: {json.dumps(n)} "
        f"}} }}\n" for n, i in ids.items())
    meta += "".join(
        f"stat_metadata {{ key: {i} value {{ id: {i} name: {json.dumps(k)} "
        f"}} }}\n" for k, i in sids.items())
    return (f"planes {{ id: {pid} name: {json.dumps(name)}\n"
            f"lines {{ id: 1 name: {json.dumps(line)} timestamp_ns: 0\n"
            f"{evs}}}\n{meta}}}\n")


def _text(program):
    host = [(xplane.ROUND_SPAN, 0, 1000)] + program
    stats = [{}] + [{"round": 0}] * len(program)
    return (_plane(1, "/device:TPU:0", xplane.OPS_LINE, DEVICE)
            + _plane(2, "/host:CPU", "python", host, stats))


def _ctx(tmp_path, monkeypatch, program):
    """Write the trace where the harness would and return a reader's
    context for it."""
    text = _text(program)
    out = tmp_path / "cell" / "plugins" / "profile" / "1"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    trace = xplane.Trace.from_profile(ProfileData.from_text_proto(text))
    return SimpleNamespace(trace=trace)


def test_readers_on_a_made_trace(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, PROGRAM)
    got = {m: harness.load_reader(m)(ctx) for m in READERS}
    assert got == {
        "sched.own_s": pytest.approx(60e-6),       # (400-370) + (500-470)
        "engine.plan_s": pytest.approx(30e-6),
        "engine.fetch_s": pytest.approx(220e-6),
        "server.ingest_s": pytest.approx(80e-6),
        "engine.syncs": 1.0, "server.syncs": 2.0,
        # ingest [310, 390] and aggregate [420, 870]
        "device.idle_in_server_s": pytest.approx(450e-6)}


def test_spans_nest_and_idle_table(tmp_path, monkeypatch):
    got = spans.of(_ctx(tmp_path, monkeypatch, PROGRAM))
    assert [s.name for s in got.top] == ["sched.step", "sched.step"]
    assert [c.name for c in got.top[0].children] == ["phase.report",
                                                     "server.ingest"]
    assert {s.round for s in got.all} == {0}
    table, inside = got.idle_by_span()
    assert inside == pytest.approx(730e-6)
    assert table == {k: pytest.approx(v * 1e-6)
                     for k, v in IDLE_BY_SPAN.items()}
    us = 1000                                   # the trace counts in ns
    assert got.idle_ns(0, 1000 * us) == 730 * us
    assert got.idle_ns(100 * us, 120 * us) == 0
    assert got.idle_ns(200 * us, 300 * us) == 50 * us


def test_readers_return_nothing_without_program_spans(tmp_path,
                                                      monkeypatch):
    """The parent program opens no spans: every reader returns None."""
    ctx = _ctx(tmp_path, monkeypatch, [])
    assert all(harness.load_reader(m)(ctx) is None for m in READERS)
    assert all(harness.load_reader(m)(SimpleNamespace(trace=None)) is None
               for m in READERS)


def test_recorded_trace():
    """A traced window of the cell on a v5e, with the program's spans."""
    profile = ProfileData.from_file(str(RECORDED))
    trace = xplane.Trace.from_profile(profile)
    got = spans.Spans.from_profile(trace, profile)
    n = got.rounds
    phases = ("local_train", "report", "aggregate", "distill", "eval")
    assert len(got.named("sched.step")) == 5 * n
    for p in phases:
        assert len(got.named("phase." + p)) == n
    # the counts the program books per round (tests/test_tracing.py)
    assert got.per_round_count("cohort.fetch") == 5
    assert got.per_round_count("server.fetch") == 2
    rounds = sorted({s.round for s in got.all})
    assert len(rounds) == n and rounds == list(range(rounds[0],
                                                     rounds[0] + n))
    table, inside = got.idle_by_span()
    assert sum(table.values()) == pytest.approx(inside)
    assert table[spans.NO_SPAN] < 0.1 * inside
    # the device trace names every cohort program a round runs
    modules = {e.name.split("(")[0] for plane in profile.planes
               if plane.name.startswith("/device:TPU")
               for line in plane.lines if line.name == "XLA Modules"
               for e in line.events}
    assert {"jit_cohort_train", "jit_cohort_predict",
            "jit_cohort_kmeans_masks", "jit_cohort_distill",
            "jit_cohort_eval"} <= modules
    assert "jit_wrapped" not in modules
