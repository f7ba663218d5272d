"""Fleet-scale machinery gates (wave streaming, two-tier server, clock
traces, communication accounting).

What must hold, in order of importance:

  * the scale knobs are invisible when disabled: ``wave_size=0`` and
    ``num_edge_aggregators=1`` reproduce the historical round logs
    **bit-for-bit**, and a waved run equals the device-resident run
    exactly (per-client lanes are independent, so padding differences
    cannot leak into results);
  * the two-tier server is a regrouped sum: E edges vs the flat server
    agree on accuracies within float tolerance and on the byte ledger
    *exactly*;
  * upload pricing is pre-filter (what crossed the network), downloads
    are priced on every teacher broadcast — including the data-free
    classwise path;
  * the clock's trace machinery (speeds, arrivals, churn, dropout) is
    deterministic in ``(seed, round, client)`` and stable under fleet
    growth, and every vectorized rewrite (stale merge, timeline) is
    pinned bit-identical to its per-client loop reference;
  * streaming waves changes plan *data*, never shapes: one trace per
    phase, no matter how many waves pass through the device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import FedConfig
from repro.core.filtering import server_entropy_filter
from repro.core.protocol import as_engine
from repro.data.proxy import ProxyData
from repro.fed import simulator
from repro.fed.clock import (SimTimeline, arrival_offsets, client_speeds,
                             dropout_mask, online_mask)
from repro.fed.cohort import CohortEngine
from repro.fed.participation import StalenessBuffer, cohort_size
from repro.fed.server import Server

TOL = dict(rtol=0.0, atol=1e-5)


def _cfg(**kw):
    base = dict(num_clients=5, rounds=2, method="edgefd", scenario="strong",
                proxy_batch=120, batch_size=32, lr=1e-2, seed=0,
                engine="cohort")
    base.update(kw)
    return FedConfig(**base)


def _run(cfg):
    return simulator.run(cfg, "mnist_feat", n_train=600, n_test=200)


def _tiny_clients(n=5, apply_fn=None, d_in=8, num_classes=4):
    from repro.fed.client import Client
    from repro.models.cnn import MLPClassifier
    from repro.optim.optimizers import sgd

    mlp = MLPClassifier(d_in=d_in, hidden=(16,), num_classes=num_classes)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    opt = sgd(1e-2)          # cohort members must share one instance
    clients = []
    for cid in range(n):
        key, sub = jax.random.split(key)
        clients.append(Client(
            cid, apply_fn or mlp.apply, mlp.init(sub), opt,
            rng.normal(size=(64, d_in)).astype(np.float32),
            rng.integers(0, num_classes, size=64),
            num_classes=num_classes, arch_key="mlp", seed=0))
    return mlp, clients


def _stub_server(t=6, k=4, num_edges=1):
    proxy = ProxyData(x=np.zeros((t, 3), np.float32),
                      y=np.zeros((t,), np.int64),
                      owner=np.zeros((t,), np.int32))
    return Server(proxy, seed=0, num_edges=num_edges)


# ------------------------------------------------------------ wave parity

def test_wave_streaming_bit_identical():
    """Streaming C=5 through the device in waves of 2 must reproduce the
    device-resident run bit-for-bit — results, losses and the byte ledger."""
    a = _run(_cfg())
    b = _run(_cfg(wave_size=2))
    for ra, rb in zip(a.rounds, b.rounds):
        np.testing.assert_array_equal(np.asarray(ra.accs),
                                      np.asarray(rb.accs))
        assert ra.local_loss == rb.local_loss
        assert ra.distill_loss == rb.distill_loss
        assert ra.id_fraction == rb.id_fraction
        assert ra.bytes_up == rb.bytes_up
        assert ra.bytes_down == rb.bytes_down


def test_wave_streaming_with_participation_and_staleness():
    """Waves compose with the subset/staleness path: same sampled subsets,
    same teachers, same ledger."""
    kw = dict(participation_fraction=0.6, staleness_decay=0.5, rounds=3)
    a = _run(_cfg(**kw))
    b = _run(_cfg(wave_size=2, **kw))
    for ra, rb in zip(a.rounds, b.rounds):
        assert ra.participants == rb.participants
        np.testing.assert_allclose(np.asarray(ra.accs),
                                   np.asarray(rb.accs), **TOL)
        assert ra.bytes_up == rb.bytes_up
        assert ra.mean_staleness == rb.mean_staleness


def test_wave_size_validation():
    _, clients = _tiny_clients(3)
    with pytest.raises(ValueError, match="wave_size"):
        CohortEngine(clients, wave_size=-1)
    with pytest.raises(ValueError, match="cohort"):
        as_engine(clients, "loop", wave_size=2)


def test_wave_streaming_does_not_retrace():
    """Every wave reuses the compiled phases: lead shapes are the padded
    wave size, so wave 1..W hit the trace of wave 0 — and later rounds hit
    it too. O(1) compiles regardless of C/wave_size."""
    from repro.models.cnn import MLPClassifier

    mlp = MLPClassifier(d_in=8, hidden=(16,), num_classes=4)
    traces = []

    def counting_apply(params, x, train):
        traces.append(tuple(x.shape))    # one entry per (re)trace
        return mlp.apply(params, x, train)

    _, clients = _tiny_clients(5, apply_fn=counting_apply)
    engine = CohortEngine(clients, wave_size=2)   # 3 waves over C=5
    rng = np.random.default_rng(0)
    px = rng.normal(size=(32, 8)).astype(np.float32)
    teacher = rng.normal(size=(32, 4)).astype(np.float32)
    w = np.ones((32,), np.float32)
    engine.cohort_local_train(0, 1, 32)
    engine.cohort_distill(0, px, teacher, w, 1, 32)
    first = len(traces)
    for _ in range(2):
        engine.cohort_local_train(0, 1, 32)
        engine.cohort_distill(0, px, teacher, w, 1, 32)
    assert len(traces) == first, (
        f"wave streaming retraced a phase: {first} -> {len(traces)} "
        f"traces ({traces})")


# ------------------------------------------------------- two-tier server

@pytest.mark.parametrize("kw", [
    dict(),                                                  # full, fresh
    dict(participation_fraction=0.6, staleness_decay=0.5),   # subset+stale
    dict(method="selective-fd"),               # entropy filter at the edges
])
def test_two_tier_matches_flat_server(kw):
    """E edge aggregators are a regrouped sum over client shards: same
    accuracies (float tolerance), identical byte ledger and staleness."""
    a = _run(_cfg(rounds=3, **kw))
    b = _run(_cfg(rounds=3, num_edge_aggregators=3, **kw))
    for ra, rb in zip(a.rounds, b.rounds):
        np.testing.assert_allclose(np.asarray(ra.accs),
                                   np.asarray(rb.accs), **TOL)
        assert ra.bytes_up == rb.bytes_up
        assert ra.bytes_down == rb.bytes_down
        np.testing.assert_allclose(ra.mean_staleness, rb.mean_staleness,
                                   **TOL)


def test_more_edges_than_clients_is_capped():
    res = _run(_cfg(num_edge_aggregators=64))
    assert res.rounds[-1].mean_acc >= 0.0


def test_edge_count_validation():
    with pytest.raises(ValueError, match="num_edges"):
        _stub_server(num_edges=0)


def test_two_tier_subset_prices_fresh_uploads_only():
    """Edges price uploads from the *pre-filter fresh* masks of this
    round's reporters — stale reuse crosses no network and costs nothing;
    flat and two-tier servers agree on the ledger exactly."""
    rng = np.random.default_rng(0)
    C, t, k = 4, 6, 4
    part = np.array([True, False, True, False])
    idx = np.arange(t)
    logits = rng.normal(size=(C, t, k)).astype(np.float32)
    masks = rng.random((C, t)) < 0.7
    logits[~part] = 0.0
    masks[~part] = False
    expected = int(masks[part].sum()) * k * 4

    for edges in (1, 2):
        srv = _stub_server(t=t, k=k, num_edges=edges)
        srv.ingest_reports(0, part, idx, logits, masks, decay=0.5)
        srv.aggregate_round(0)
        assert srv.bytes_received == expected, f"num_edges={edges}"


# ------------------------------------------------- communication ledger

def test_aggregate_prices_prefilter_uploads():
    """Clients upload their ID rows *before* the server-side entropy
    filter tightens the masks: bytes_received must price the pre-filter
    masks (the filtered count undercounted Selective-FD's uploads)."""
    C, t, k = 3, 6, 4
    logits = np.zeros((C, t, k), np.float32)
    logits[:, :3] = np.array([8.0, 0.0, 0.0, 0.0])   # confident → kept
    masks = np.ones((C, t), bool)                    # flat rows → filtered
    kept = np.asarray(server_entropy_filter(jnp.asarray(logits),
                                            jnp.asarray(masks)))
    assert kept.sum() < masks.sum(), "filter must tighten some rows"

    srv = _stub_server(t=t, k=k)
    srv.aggregate(logits, masks, entropy_filter=True)
    assert srv.bytes_received == int(masks.sum()) * k * 4


def test_classwise_broadcast_is_accounted():
    """The fused classwise teacher is broadcast like any other teacher:
    the data-free FKD/PLS path must not report zero download traffic."""
    rng = np.random.default_rng(0)
    C, k_cls, k = 4, 5, 5
    mc = [(rng.normal(size=(k_cls, k)).astype(np.float32),
           rng.integers(0, 3, size=k_cls).astype(np.float32))
          for _ in range(C)]
    srv = _stub_server(t=6, k=k)
    teacher, _ = srv.aggregate_classwise(mc, count_weighted=True)
    assert srv.bytes_broadcast == teacher.size * 4
    assert srv.bytes_received == C * k_cls * k * 4


# ----------------------------------------------------- clock trace pins

@pytest.mark.parametrize("seed", [0, 1, 12345])
def test_client_speeds_match_per_client_generator(seed):
    """The vectorized SeedSequence/PCG64 lanes must stay bit-identical to
    constructing one numpy Generator per client."""
    got = client_speeds(7, seed=seed, straggler_factor=4.0)
    for cid in range(7):
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, cid, 0xC10C]))
        assert got[cid] == 1.0 + 3.0 * rng.random(), f"client {cid}"


def test_poisson_arrivals_match_per_client_generator():
    off = arrival_offsets(5, 3, seed=7, process="poisson", spread=10.0)
    for cid in range(5):
        rng = np.random.default_rng(
            np.random.SeedSequence([7, 3, cid, 0xA881]))
        assert off[cid] == 10.0 * -np.log1p(-rng.random()), f"client {cid}"


def test_arrival_traces_deterministic_and_stable_under_growth():
    """A client's trace depends on (seed, round, client) only — growing
    the fleet must not reshuffle the existing clients' arrivals."""
    for proc in ("poisson", "bursty"):
        a = arrival_offsets(16, 2, seed=3, process=proc, spread=30.0)
        b = arrival_offsets(16, 2, seed=3, process=proc, spread=30.0)
        np.testing.assert_array_equal(a, b)
        big = arrival_offsets(64, 2, seed=3, process=proc, spread=30.0)
        np.testing.assert_array_equal(big[:16], a)
        other = arrival_offsets(16, 3, seed=3, process=proc, spread=30.0)
        assert not np.array_equal(other, a), "trace must vary per round"
        assert (a >= 0).all()


def test_arrival_static_and_zero_spread_are_free():
    assert arrival_offsets(8, 0, seed=0, process="static", spread=5.0) is None
    assert arrival_offsets(8, 0, seed=0, process="poisson", spread=0.0) is None
    assert arrival_offsets(0, 0, seed=0, process="poisson", spread=5.0) is None


def test_churn_and_dropout_masks():
    assert online_mask(8, 0, seed=0, churn=0.0) is None
    assert dropout_mask(8, 0, seed=0, dropout=0.0) is None
    on = online_mask(4096, 1, seed=0, churn=0.25)
    assert 0.6 < on.mean() < 0.9          # ~75% stay online
    np.testing.assert_array_equal(on, online_mask(4096, 1, seed=0,
                                                  churn=0.25))
    drop = dropout_mask(4096, 1, seed=0, dropout=0.1)
    assert 0.02 < drop.mean() < 0.2
    assert not np.array_equal(drop, dropout_mask(4096, 2, seed=0,
                                                 dropout=0.1))


def test_timeline_matches_per_client_loop():
    """The vectorized lane update must equal the serial per-client loop —
    lane occupancy, barriers and all — across rounds with offsets."""
    speeds = client_speeds(6, seed=0)
    vec, ref = SimTimeline(speeds), SimTimeline(speeds)
    rng = np.random.default_rng(0)
    for r in range(4):
        part = rng.random(6) < 0.7
        offs = (rng.random(6) * 3.0).astype(np.float64)
        base, ready = 1.0 + r, float(r)
        got = vec.client_phase(part, base, ready_s=ready, offsets=offs)
        finishes = []
        for c in np.flatnonzero(part):
            start = max(ready + offs[c], ref.client_free[c])
            fin = start + base * speeds[c]
            ref.client_free[c] = fin
            finishes.append(fin)
        assert got == max([ready] + finishes)
        np.testing.assert_array_equal(vec.client_free, ref.client_free)


def test_stale_merge_matches_per_client_loop():
    """The fancy-index buffer write (one numpy op) must stay bit-identical
    to the historical per-client loop it replaced."""
    rng = np.random.default_rng(1)
    C, P, t, K = 6, 12, 5, 3
    buf = StalenessBuffer(C, P, K)
    ref_logits = np.zeros((C, P, K), np.float32)
    ref_masks = np.zeros((C, P), bool)
    for r in range(4):
        part = rng.random(C) < 0.5
        part[r % C] = True                       # never an empty round
        idx = rng.choice(P, size=t, replace=False)
        logits = rng.normal(size=(C, t, K)).astype(np.float32)
        masks = rng.random((C, t)) < 0.8
        logits[~part] = 0.0
        masks[~part] = False
        merged = buf.merge(r, part, idx, logits, masks, 0.5)
        for c in np.flatnonzero(part):
            ref_logits[c, idx] = logits[c]
            ref_masks[c, idx] = masks[c]
        np.testing.assert_array_equal(buf.logits, ref_logits)
        np.testing.assert_array_equal(buf.masks, ref_masks)
        np.testing.assert_array_equal(
            merged.masks, np.where(part[:, None], masks, ref_masks[:, idx]))


# ------------------------------------------------------- cohort_size pin

def test_cohort_size_bankers_rounding_pinned():
    """round() is banker's rounding: half-integers go to the nearest even
    count. Every golden/round log encodes this, so it is pinned."""
    assert cohort_size(5, 0.5) == 2      # 2.5 → 2, not 3
    assert cohort_size(7, 0.5) == 4      # 3.5 → 4
    assert cohort_size(10, 0.25) == 2    # 2.5 → 2
    assert cohort_size(6, 0.5) == 3
    assert cohort_size(3, 0.01) == 1     # clamped to >= 1
    assert cohort_size(3, 1.0) == 3


# ------------------------------------------------- scheduler integration

def test_churn_dropout_round_runs_and_ages_reports():
    """A full stack round with bursty arrivals + churn + dropout on top of
    subset sampling must run, keep accuracies sane and age some reports."""
    cfg = _cfg(rounds=3, participation_fraction=0.6, staleness_decay=0.5,
               arrival_process="bursty", arrival_spread=30.0,
               churn_prob=0.2, dropout_prob=0.1, num_edge_aggregators=2,
               wave_size=2)
    res = _run(cfg)
    assert all(0.0 <= r.mean_acc <= 1.0 for r in res.rounds)
    assert any(r.mean_staleness > 0.0 for r in res.rounds[1:]), (
        "churn/dropout must leave some aggregated reports stale")
    # arrivals push the simulated finish later than the static clock
    static = _run(_cfg(rounds=3, participation_fraction=0.6,
                       staleness_decay=0.5))
    assert res.rounds[-1].sim_finish_s > static.rounds[-1].sim_finish_s


def test_bad_traffic_config_fails_fast():
    from repro.fed.scheduler import validate_config
    with pytest.raises(ValueError, match="arrival_process"):
        validate_config(_cfg(arrival_process="diurnal"))
    with pytest.raises(ValueError, match="churn"):
        validate_config(_cfg(churn_prob=1.0))
    with pytest.raises(ValueError, match="dropout"):
        validate_config(_cfg(dropout_prob=-0.1))
    with pytest.raises(ValueError, match="arrival_spread"):
        validate_config(_cfg(arrival_spread=-1.0))
