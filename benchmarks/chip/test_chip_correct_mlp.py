"""The check that decides ``correct``, driven through a whole run of the
``mlp_feat512.c100_iid`` cell at a CPU-sized fleet: a sound run passes; the
control (the bfloat16 reference in the program's place) and each fault
planted in the timed path fail. The harness's look for a chip is skipped;
everything else is the run the benchmark makes."""
import json
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from fdbench import harness, reference

ROOT = Path(__file__).resolve().parents[2]
CELL = "mlp_feat512.c100_iid"
SMALL = {"num_clients": 4, "samples_per_client": 200, "proxy_batch": 128,
         "n_test": 100}
SEED = 2 ** 31 + 3


@pytest.fixture(autouse=True)
def no_global_cache(monkeypatch):
    # the test process is shared with other test files: leave JAX's
    # compile-cache settings alone
    monkeypatch.setattr(harness, "enable_cache", lambda: None)


def run(**kw):
    return harness.run(CELL, SEED, 0.2, False, root=ROOT, t_process=0.0,
                       require_tpu=False, traffic_overrides=SMALL,
                       log=lambda *a, **k: None, **kw)


def test_sound_run_is_correct():
    res = run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "round_s", "round_p95_s",
                                   "peak_hbm_gib"}


def test_control_is_not_correct():
    def control(config, traffic, data, seed):
        return reference.run(config, traffic, data, seed,
                             dtype=jnp.bfloat16, precision=None)

    res = run(readings_source=control)
    assert not res["correct"], res["checks"]


def test_state_left_unchanged_is_not_correct(monkeypatch):
    import repro.fed.cohort as cohort
    monkeypatch.setattr(cohort, "apply_updates", lambda p, u: p)
    res = run()
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_not_correct(monkeypatch):
    import repro.fed.cohort as cohort
    plan = cohort.padded_epoch_plan

    def half(perms, batch_size, num_steps):
        idx, w, valid = plan(perms, batch_size, num_steps)
        w[:, batch_size // 2:] = 0.0        # the mean over the rest
        return idx, w, valid

    monkeypatch.setattr(cohort, "padded_epoch_plan", half)
    res = run()
    assert not res["correct"], res["checks"]


def test_answer_altered_is_not_correct(monkeypatch):
    import repro.fed.cohort as cohort
    evaluate = cohort._Cohort.evaluate

    def altered(self, x_test, y_test, batch_size=512):
        return [a - 0.1 for a in evaluate(self, x_test, y_test, batch_size)]

    monkeypatch.setattr(cohort._Cohort, "evaluate", altered)
    res = run()
    assert not res["correct"]
    assert res["checks"]["acc_gap"]["value"] == pytest.approx(0.1)


def test_report_altered_is_not_correct(monkeypatch):
    import repro.fed.cohort as cohort
    logits = cohort._Cohort.proxy_logits

    def altered(self, px, part=None):
        out = np.array(logits(self, px, part))
        out[:, : out.shape[1] // 4] *= -1.0
        return out

    monkeypatch.setattr(cohort._Cohort, "proxy_logits", altered)
    res = run()
    assert not res["correct"], res["checks"]


def test_threshold_altered_is_not_correct(monkeypatch):
    import dataclasses

    import repro.fed.cohort as cohort
    learn = cohort._Cohort.learn_dres

    def altered(self, key):
        learn(self, key)
        for c in self.members:
            c.dre = dataclasses.replace(
                c.dre, threshold=c.dre.threshold * reference.THRESHOLD_FAULT)
        self._pack_filter_state()

    monkeypatch.setattr(cohort._Cohort, "learn_dres", altered)
    res = run()
    assert not res["correct"], res["checks"]
    assert res["checks"]["id_gap"]["value"] > 0.1


def test_refuses_without_a_tpu():
    with pytest.raises(harness.Refused, match="no TPU"):
        harness.run(CELL, SEED, 0.2, False, root=ROOT, t_process=0.0)


def test_entry_prints_no_result_off_the_chip(tmp_path):
    # a checkout's root here, and one holding only the benchmark's files
    bench = tmp_path / "alone"
    (bench / "benchmarks").mkdir(parents=True)
    (bench / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(ROOT / "benchmarks" / "chip"),
                    str(bench / "benchmarks")], check=True)
    for where in (ROOT, bench):
        out = subprocess.run(
            [sys.executable, str(where / "benchmarks" / "chip" / "run.py"),
             "--workload", CELL, "--seed", "5", "--seconds", "1",
             "--trace", "0"], cwd=where, capture_output=True, text=True,
            timeout=300)
        assert out.returncode != 0
        for line in out.stdout.splitlines():
            with pytest.raises(json.JSONDecodeError):
                json.loads(line)
