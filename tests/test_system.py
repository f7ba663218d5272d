"""End-to-end behaviour tests: the paper's claims at small scale.

These are the integration gates: Algorithm 1 runs, EdgeFD's filtering
produces the accuracy ordering of Table III, and the communication
accounting moves the right way.
"""
import numpy as np
import pytest

from repro.common.types import FedConfig
from repro.fed import simulator


def _run(method, scenario, rounds=4, **kw):
    # round_mode is pinned: these tests assert the *paper's* accuracy
    # orderings, which are claims about the lockstep Algorithm-1 protocol
    # — overlap mode trades a slightly different trajectory for round
    # throughput (its accuracy tolerance is gated by
    # benchmarks/async_rounds.py and tests/test_scheduler.py), so the
    # REPRO_ROUND_MODE=overlap CI entry must not move these thresholds.
    cfg = FedConfig(num_clients=5, rounds=rounds, method=method,
                    scenario=scenario, proxy_batch=200, lr=1e-2,
                    round_mode="sync", **kw)
    return simulator.run(cfg, "mnist_feat", n_train=1500, n_test=400)


@pytest.fixture(scope="module")
def strong_results():
    return {m: _run(m, "strong") for m in ("edgefd", "fedmd", "indlearn")}


def test_protocol_runs_and_improves(strong_results):
    res = strong_results["edgefd"]
    assert len(res.rounds) == 4
    assert res.final_acc > res.rounds[0].mean_acc * 0.9
    assert res.final_acc > 0.5


def test_edgefd_beats_unfiltered_strong_noniid(strong_results):
    """Table III, strong non-IID: client-side filtering must help."""
    assert strong_results["edgefd"].best_acc > \
        strong_results["fedmd"].best_acc - 0.02


def test_collaboration_beats_indlearn(strong_results):
    """IndLearn is capped by local label coverage (2/10 classes)."""
    assert strong_results["indlearn"].best_acc < 0.35
    assert strong_results["edgefd"].best_acc > \
        strong_results["indlearn"].best_acc + 0.3


def test_edgefd_filter_selective(strong_results):
    """Under strong non-IID the ID fraction must be well below 1 (the
    filter rejects other clients' classes) and above the own-share floor."""
    idf = strong_results["edgefd"].rounds[-1].id_fraction
    assert 0.1 < idf < 0.8


def test_iid_all_methods_comparable():
    e = _run("edgefd", "iid", rounds=3)
    f = _run("fedmd", "iid", rounds=3)
    assert abs(e.best_acc - f.best_acc) < 0.15


def test_data_free_method_runs():
    r = _run("fkd", "weak", rounds=3)
    assert r.final_acc > 0.3   # data-free FD learns something under weak


def test_selective_fd_baseline_runs():
    r = _run("selective-fd", "strong", rounds=3)
    assert r.final_acc > 0.4


def test_comm_accounting_monotone(strong_results):
    logs = strong_results["edgefd"].rounds
    ups = [log.bytes_up for log in logs]
    assert all(b > a for a, b in zip(ups, ups[1:]))
    # filtered upload must be smaller than unfiltered (same rounds/batch)
    assert strong_results["edgefd"].rounds[-1].bytes_up < \
        strong_results["fedmd"].rounds[-1].bytes_up


def test_fed_train_entry_point_reports_each_round(capsys):
    """``fed_train.main`` (the entry point ``chip_smoke.py`` drives in
    process) hands every retired round to ``on_round`` after printing it."""
    from repro.launch import fed_train
    seen = []
    res = fed_train.main(["--method", "edgefd", "--scenario", "strong",
                          "--dataset", "mnist_feat", "--engine", "cohort",
                          "--clients", "4", "--rounds", "2",
                          "--n-train", "800", "--n-test", "200"],
                         on_round=seen.append)
    assert [log.round for log in seen] == [0, 1]
    assert seen == list(res.rounds)
    assert capsys.readouterr().out.count("round ") >= 2
