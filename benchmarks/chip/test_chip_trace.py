"""Trace reduction (idle share, kernel time, gap labels, top ops) on a
hand-built trace whose numbers are known, and on a small trace recorded
on a TPU v5e by the harness's traced window."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from fdbench import harness, xplane

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "testdata" / "mlp_feat512.c100_iid.xplane.pb"
RECORDED_PHASES = HERE / "testdata" / "mlp_feat512.c100_iid.phases.json"
PEAKS = harness.load_json(HERE / "peaks.json")["devices"]["TPU v5 lite"]

FWD = "%jvp_jit__run__.4 = f32[20,512,1]{2,1,0} custom-call(f32[20,512,10])"
BWD = ("%transpose_jvp_jit__run_bwd___.4 = f32[20,512,10]{2,1,0} "
       "custom-call(f32[20,512,10])")
# (name, start us, end us) on the device; the round spans [0, 1000] us and
# its three steps [0, 300], [300, 600], [600, 1000]
DEVICE = [("%while.1 = (s32[]) while(s32[])", 100, 250),
          ("%fusion.7 = f32[8]{0} fusion(f32[8])", 120, 200),
          (FWD, 400, 450), (BWD, 450, 500),
          ("%convolution.2 = f32[8]{0} convolution(f32[8])", 700, 900)]
STEPS = [(0, 300), (300, 600), (600, 1000)]
PHASES = ["local_train", "report", "eval"]


def _plane(pid, name, line, events):
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    evs = "".join(
        f"events {{ metadata_id: {ids[n]} offset_ps: {a * 1000000} "
        f"duration_ps: {(b - a) * 1000000} }}\n" for n, a, b in events)
    meta = "".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: {json.dumps(n)} "
        f"}} }}\n" for n, i in ids.items())
    return (f"planes {{ id: {pid} name: {json.dumps(name)}\n"
            f"lines {{ id: 1 name: {json.dumps(line)} timestamp_ns: 0\n"
            f"{evs}}}\n{meta}}}\n")


@pytest.fixture(scope="module")
def made():
    host = [(xplane.ROUND_SPAN, 0, 1000)] + [(xplane.STEP_SPAN, a, b)
                                             for a, b in STEPS]
    text = (_plane(1, "/device:TPU:0", xplane.OPS_LINE, DEVICE)
            + _plane(2, "/host:CPU", "python", host))
    return xplane.Trace.from_profile(ProfileData.from_text_proto(text))


def test_busy_idle_and_window(made):
    assert made.window_s == pytest.approx(1000e-6)
    # busy: [100, 250] u [400, 500] u [700, 900]
    assert made.busy_s == pytest.approx(450e-6)
    assert made.idle_share == pytest.approx(0.55)


def test_gaps_are_labelled_with_the_host_phase(made):
    gaps = made.labelled_gaps(PHASES)
    assert gaps == [("eval", pytest.approx(200e-6)),
                    ("report", pytest.approx(150e-6)),
                    ("local_train", pytest.approx(100e-6)),
                    ("eval", pytest.approx(100e-6))]
    assert xplane.gap_seconds_by_phase(made, PHASES) == {
        "eval": pytest.approx(300e-6), "report": pytest.approx(150e-6),
        "local_train": pytest.approx(100e-6)}
    with pytest.raises(ValueError):
        made.labelled_gaps(PHASES[:2])


def test_top_ops_count_self_time(made):
    top = dict(made.top_ops())
    assert top["convolution.2"] == pytest.approx(200e-6)
    assert top["fusion.7"] == pytest.approx(80e-6)
    assert top["while.1"] == pytest.approx(70e-6)     # 150 less its body
    assert list(dict(made.top_ops(1))) == ["convolution.2"]


def test_kernel_time_and_roofline(made):
    reader = harness.load_reader("distill_kl_roofline")
    fwd = made.kernel_calls(r"^(jvp_)?jit__run__")
    bwd = made.kernel_calls(r"jit__run_bwd_")
    assert [s for _, s in fwd] == [pytest.approx(50e-6)]
    assert [s for _, s in bwd] == [pytest.approx(50e-6)]
    assert xplane.result_dims(fwd[0][0]) == (20, 512, 1)
    ctx = SimpleNamespace(trace=made, peaks=PEAKS,
                          config={"num_classes": 10},
                          traffic={"batch_size": 64, "proxy_batch": 512})
    # 20 clients x 64 rows x 10 logits: memory bound on both kernels
    need = (20 * 64 * (10 * 8 + 4) + 20 * 64 * (10 * 12 + 4)) / 819e9
    assert reader(ctx) == pytest.approx(100 * need / 100e-6)
    ctx.trace = None
    assert reader(ctx) is None


def test_recorded_trace():
    """A traced window of the ``mlp_feat512.c100_iid`` cell on a v5e."""
    trace = xplane.Trace.from_file(str(RECORDED))
    phases = json.loads(RECORDED_PHASES.read_text())
    ops = trace.device_ops["/device:TPU:0"]
    busy = xplane.union([(max(a, trace.lo), min(b, trace.hi))
                         for _, a, b in ops if b > trace.lo and a < trace.hi])
    assert trace.busy_s == pytest.approx(sum(b - a for a, b in busy) * 1e-9)
    assert 0.0 < trace.idle_share < 1.0
    assert len(trace.steps) == len(phases) == 5 * len(trace.rounds)
    gaps = trace.labelled_gaps(phases)
    assert {g for g, _ in gaps} <= set(phases) | {xplane.OUTSIDE}
    assert sum(s for _, s in gaps) == pytest.approx(
        trace.window_s - trace.busy_s)
    # one forward and one backward distill-KL call per distill step
    steps = 512 // 64 * len(trace.rounds)
    fwd = trace.kernel_calls(r"^(jvp_)?jit__run__")
    bwd = trace.kernel_calls(r"jit__run_bwd_")
    assert len(fwd) == len(bwd) == steps
    assert {xplane.result_dims(n)[0] for n, _ in fwd} == {100}
