"""The single-tier server reduce as one compiled program.

``Server.aggregate`` runs the filter, the reducer, the sharpening and the
upload count in ``server_aggregate`` and reads them back once. On the CPU
its teacher, validity and byte accounting must equal, bit for bit, the
eager formulas of ``repro.core.aggregation`` called one op at a time.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import tracing
from repro.core import aggregation
from repro.core.filtering import server_entropy_filter
from repro.data.proxy import ProxyData
from repro.fed.server import Server

C, T, K = 6, 48, 10


def _reports(c=C, t=T, k=K, seed=0, nonfinite=False):
    rng = np.random.default_rng(seed)
    logits = (3.0 * rng.normal(size=(c, t, k))).astype(np.float32)
    # a few confident rows, so the entropy filter keeps some and drops some
    logits[:, : t // 4, 0] += 12.0
    masks = rng.random((c, t)) < 0.7
    if nonfinite:
        logits[1, 3, 2] = np.nan
        logits[2, 5, 0] = np.inf
        logits[4, 7, 9] = -np.inf
    return logits, masks


def _weights(c=C):
    w = (0.5 ** (np.arange(c) % 3)).astype(np.float32)
    w[0] = 0.0                                   # a report decayed away
    return w


def _rows(c=C):
    return np.arange(c) % 2 == 0                 # a strict subset uploaded


def _eager(logits, masks, *, mode="mean", guard=True, weights=None,
           rows=None, entropy=False, sharpen=None, trim=0.2):
    """The server's reduce written out as eager calls of the
    aggregation module: (teacher, valid, bytes_received,
    bytes_broadcast)."""
    lo, mk = jnp.asarray(logits), jnp.asarray(masks)
    used = server_entropy_filter(lo, mk) if entropy else mk
    if mode != "mean":
        if weights is not None:
            used = jnp.logical_and(used, jnp.asarray(weights > 0.0)[:, None])
        teacher, valid = aggregation.robust_reduce(
            lo, used, mode, trim_frac=trim, temperature_sharpen=sharpen)
    elif weights is not None and not np.all(weights == 1.0):
        teacher, valid = aggregation.weighted_masked_mean_logits(
            lo, used, jnp.asarray(weights), temperature_sharpen=sharpen,
            guard_finite=guard)
    else:
        teacher, valid = aggregation.masked_mean_logits(
            lo, used, temperature_sharpen=sharpen, guard_finite=guard)
    up = masks if rows is None else masks[rows]
    k = logits.shape[-1]
    return (np.asarray(teacher), np.asarray(valid),
            int(jnp.sum(jnp.asarray(up))) * k * 4,
            logits.shape[1] * k * 4)


def _server(mode="mean", guard=True, trim=0.2):
    n = 8
    proxy = ProxyData(x=np.zeros((n, 4), np.float32),
                      y=np.zeros((n,), np.int64),
                      owner=np.zeros((n,), np.int32))
    return Server(proxy, seed=0, robust_aggregation=mode, trim_frac=trim,
                  sanitize=guard)


CASES = {
    # name: (server kwargs, report kwargs, aggregate kwargs)
    "mean_guarded": ({}, {}, {}),
    "mean_unguarded": ({"guard": False}, {}, {}),
    "mean_cell_shape": ({}, {"c": 100, "t": 512}, {}),
    "weighted": ({}, {}, {"weights": _weights()}),
    "weighted_all_ones": ({}, {}, {"weights": np.ones(C, np.float32)}),
    "weighted_unguarded": ({"guard": False}, {}, {"weights": _weights()}),
    "entropy_filter": ({}, {}, {"entropy": True}),
    "sharpen": ({}, {}, {"sharpen": 0.3}),
    "sharpen_dsfl": ({}, {}, {"sharpen": 0.5}),
    "weighted_sharpen": ({}, {}, {"weights": _weights(), "sharpen": 0.7}),
    "entropy_filter_sharpen": ({}, {}, {"entropy": True, "sharpen": 3.0}),
    "trimmed_mean": ({"mode": "trimmed_mean"}, {}, {}),
    "trimmed_mean_sharpen": ({"mode": "trimmed_mean", "trim": 0.3}, {},
                             {"sharpen": 0.3}),
    "median": ({"mode": "median"}, {}, {}),
    "krum_row": ({"mode": "krum_row"}, {}, {}),
    "robust_weighted": ({"mode": "median"}, {}, {"weights": _weights()}),
    "nonfinite_mean": ({}, {"nonfinite": True}, {}),
    "nonfinite_weighted": ({}, {"nonfinite": True},
                           {"weights": _weights()}),
    "nonfinite_trimmed_mean": ({"mode": "trimmed_mean"},
                               {"nonfinite": True}, {}),
    "nonfinite_krum_row": ({"mode": "krum_row"}, {"nonfinite": True}, {}),
    "uploaded_subset": ({}, {}, {"rows": _rows()}),
    "uploaded_subset_weighted": ({}, {}, {"rows": _rows(),
                                          "weights": _weights()}),
    "uploaded_subset_robust": ({"mode": "trimmed_mean"}, {},
                               {"rows": _rows(), "weights": _weights(),
                                "entropy": True}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiled_aggregate_equals_eager_formulas(case):
    srv_kw, rep_kw, agg_kw = CASES[case]
    logits, masks = _reports(**rep_kw)
    srv = _server(**srv_kw)
    mark = tracing.counts()
    teacher, valid = srv.aggregate(
        logits, masks, sharpen=agg_kw.get("sharpen"),
        entropy_filter=agg_kw.get("entropy", False),
        client_weights=agg_kw.get("weights"),
        uploaded_rows=agg_kw.get("rows"))
    got = {}
    tracing.book(got, mark)
    assert got["server.syncs"] == 1      # one read-back per aggregate
    want = _eager(logits, masks, mode=srv.robust_aggregation,
                  guard=srv.sanitize, trim=srv.trim_frac, **agg_kw)
    np.testing.assert_array_equal(teacher, want[0])
    np.testing.assert_array_equal(valid, want[1])
    assert (srv.bytes_received, srv.bytes_broadcast) == want[2:]
    if "rows" in agg_kw:
        assert want[2] < int(masks.sum()) * K * 4
    if rep_kw.get("nonfinite") or "weights" in agg_kw:
        assert np.isfinite(teacher).all()


def test_aggregate_program_is_reused_across_rounds():
    """The variant and the shapes key the compile cache: a second round of
    the same shapes builds nothing, and a new temperature is an operand,
    not a new program."""
    srv = _server()
    logits, masks = _reports()
    srv.aggregate(logits, masks, sharpen=0.3)
    before = tracing.compiles()[0]
    srv.aggregate(*_reports(seed=1), sharpen=0.7)
    assert tracing.compiles()[0] == before
