"""The CNN zoo's second witness. On the chip the program's rounds on
``cnn_zoo_cifar10`` with the ``c10_strong`` traffic drift from the float32
reference and turn non-finite, so that cell is not in ``BENCHMARK.json``
(PERF.md, Open questions). Here, on the CPU and at a small size, a whole
harness run of the same configuration and traffic agrees with the
reference to float32 rounding, and a planted fault is still seen."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from fdbench import compare, harness

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
# the c10_strong traffic (ten clients of one class each, 5,000 samples
# apiece) cut to a size the CPU runs in seconds
TRAFFIC = {"method": "edgefd", "num_clients": 2, "samples_per_client": 30,
           "scenario": "strong", "participation_fraction": 1.0,
           "proxy_fraction": 0.2, "proxy_batch": 12, "local_epochs": 1,
           "distill_epochs": 1, "batch_size": 8, "lr": 0.01,
           "temperature": 3.0, "n_test": 20, "round_mode": "sync",
           "engine": "cohort"}
SEED = 2 ** 32 + 9
# float32 rounding over three rounds of a few SGD steps on the CPU
AGREE = {name: 1e-3 for name in compare.NUMBERS}


@pytest.fixture(autouse=True)
def cnn_cell(monkeypatch):
    # the test process is shared with other test files: leave JAX's
    # compile-cache settings alone
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    cell = SimpleNamespace(
        name="cnn_zoo_cifar10.c10_strong", cell={"chips": 1},
        config=harness.load_json(HERE / "configs" / "cnn_zoo_cifar10.json"),
        traffic=TRAFFIC,
        limits={"limits": AGREE}, per_layer=[],
        end_to_end=[{"name": "round_s", "unit": "s"}])
    monkeypatch.setattr(harness, "load_cell", lambda root, name: cell)


def run():
    return harness.run("cnn_zoo_cifar10.c10_strong", SEED, 0.1, False,
                       root=ROOT, t_process=0.0, require_tpu=False,
                       log=lambda *a, **k: None)


def test_program_agrees_with_the_reference_on_the_cpu():
    res = run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


def test_state_left_unchanged_is_seen(monkeypatch):
    import repro.fed.cohort as cohort
    monkeypatch.setattr(cohort, "apply_updates", lambda p, u: p)
    res = run()
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)
