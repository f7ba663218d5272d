"""Server-side aggregation (Algorithm 1 line 15) — masked mean of ID logits.

EdgeFD's server does exactly one thing: average the ID predictions each
client uploaded. No filtering, no teacher model. On the production mesh this
is a psum over the ``data`` axis (DESIGN.md §3) instead of a gather at a hub.

Robust variants (``ROBUST_AGGREGATIONS``) replace the mean over the client
axis with coordinate-wise trimmed mean / median or per-position Krum — the
Byzantine-resilient reducers the FD robustness surveys call for. Every
reducer (including the plain mean) guards against non-finite client rows: a
single inf/NaN logit from a diverged client must never poison the fused
teacher (the guard is an exact no-op on finite inputs, so the legacy logs
stay bit-for-bit).

The single-tier server (``repro.fed.server.server_aggregate``) traces
these reducers, with the server-side filter, the sharpening and the upload
count, into one compiled program read back once; on the CPU it equals the
eager calls bit for bit.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# reducers over the client axis of the stacked (C, t, K) reports; "mean" is
# the legacy masked mean (bit-for-bit with pre-robustness logs)
ROBUST_AGGREGATIONS = ("mean", "trimmed_mean", "median", "krum_row")


def _finite_rows(logits, mask):
    """Drop non-finite client rows: a (c, t) row with any inf/NaN entry is
    removed from the mask and zeroed in the values (``0 * nan`` is nan, so
    masking alone is not enough). Exact identity on finite inputs."""
    lo = jnp.asarray(logits, jnp.float32)
    fin = jnp.isfinite(lo).all(axis=-1)                      # (C, t)
    return jnp.where(fin[..., None], lo, 0.0), fin


def sharpen_logits(teacher, temperature):
    """DS-FL's entropy reduction: the log of ``softmax(teacher / T)``,
    floored at 1e-12. ``temperature`` may be traced."""
    probs = jax.nn.softmax(teacher / temperature, axis=-1)
    return jnp.log(jnp.maximum(probs, 1e-12))


def _sharpen(teacher, temperature_sharpen: Optional[float]):
    if temperature_sharpen:
        return sharpen_logits(teacher, temperature_sharpen)
    return teacher


def masked_mean_logits(logits, mask, *, temperature_sharpen: Optional[float] = None,
                       guard_finite: bool = True):
    """logits: (C, t, K) per-client proxy logits; mask: (C, t) ID decisions.

    Returns (teacher (t, K), valid (t,) bool). Samples where no client is ID
    get a zero teacher and valid=False — the distillation loss masks them.
    DS-FL-style temperature sharpening (entropy reduction) is optional.
    Non-finite client rows are excluded (see ``_finite_rows``) unless
    ``guard_finite=False`` re-exposes the historical poison-the-teacher
    behavior (the ``sanitize_reports=False`` attack surface the divergence
    watchdog defends).
    """
    if guard_finite:
        lo, fin = _finite_rows(logits, mask)
        mb = jnp.logical_and(mask, fin)
    else:
        lo, mb = jnp.asarray(logits, jnp.float32), mask
    m = mb.astype(jnp.float32)[..., None]                    # (C, t, 1)
    s = jnp.sum(lo * m, axis=0)                              # (t, K)
    cnt = jnp.sum(m, axis=0)                                 # (t, 1)
    teacher = s / jnp.maximum(cnt, 1.0)
    valid = cnt[..., 0] > 0.0
    return _sharpen(teacher, temperature_sharpen), valid


def weighted_masked_mean_logits(logits, mask, client_weights, *,
                                temperature_sharpen: Optional[float] = None,
                                guard_finite: bool = True):
    """``masked_mean_logits`` with a per-client reliability weight.

    ``client_weights``: (C,) — the staleness model's ``decay ** age`` (see
    ``repro.fed.participation``). A fresh report carries weight 1, a stale
    one decays geometrically, weight 0 removes the client entirely; with
    all-ones weights this reduces to ``masked_mean_logits`` exactly (the
    server takes that code path instead for bit-for-bit stability).
    """
    if guard_finite:
        lo, fin = _finite_rows(logits, mask)
        mb = jnp.logical_and(mask, fin)
    else:
        lo, mb = jnp.asarray(logits, jnp.float32), mask
    w = mb.astype(jnp.float32) * client_weights[:, None]     # (C, t)
    wl = w[..., None]                                        # (C, t, 1)
    s = jnp.sum(lo * wl, axis=0)                             # (t, K)
    den = jnp.sum(wl, axis=0)                                # (t, 1)
    # divide by den itself (not a floor): the weights must cancel, so a
    # position whose only contributor is heavily decayed still recovers
    # that contributor's logits exactly. s is exactly 0 wherever den is 0
    # (all weights zero), so the dummy divisor there yields a zero teacher
    # — matching the unweighted form.
    teacher = s / jnp.where(den > 0.0, den, 1.0)
    valid = den[..., 0] > 0.0
    return _sharpen(teacher, temperature_sharpen), valid


def partial_masked_sums(logits, mask, client_weights=None, *,
                        guard_finite: bool = True):
    """One edge aggregator's contribution to the masked (weighted) mean.

    logits: (C_e, t, K) — this edge's client shard; mask: (C_e, t);
    ``client_weights``: optional (C_e,) staleness weights (None = all fresh).
    Returns ``(num (t, K), den (t,))`` — the weighted logit sums and weight
    sums this shard contributes. ``fuse_partial_sums`` over every shard's
    pair reproduces ``masked_mean_logits`` / ``weighted_masked_mean_logits``
    on the full stack (the mean is a ratio of sums, so it fuses exactly;
    only float summation order differs across shardings).
    """
    if guard_finite:
        lo, fin = _finite_rows(logits, mask)
        mb = jnp.logical_and(mask, fin)
    else:
        lo, mb = jnp.asarray(logits, jnp.float32), mask
    w = mb.astype(jnp.float32)
    if client_weights is not None:
        w = w * client_weights[:, None]
    num = jnp.sum(lo * w[..., None], axis=0)
    return num, jnp.sum(w, axis=0)


def fuse_partial_sums(nums, dens, *,
                      temperature_sharpen: Optional[float] = None):
    """Root fusion of E edge partials: (E, t, K) nums + (E, t) dens ->
    (teacher (t, K), valid (t,)). The divisor is the summed weight itself
    (floored to a dummy 1 only where it is exactly 0, matching
    ``weighted_masked_mean_logits``; with integer counts this equals the
    unweighted ``max(cnt, 1)`` floor)."""
    s = jnp.sum(jnp.asarray(nums, jnp.float32), axis=0)      # (t, K)
    den = jnp.sum(jnp.asarray(dens, jnp.float32), axis=0)    # (t,)
    teacher = s / jnp.where(den > 0.0, den, 1.0)[..., None]
    valid = den > 0.0
    return _sharpen(teacher, temperature_sharpen), valid


def masked_mean_logits_psum(local_logits, local_mask, axis_name: str = "data"):
    """Collective form for the sharded FD runtime: each mesh rank holds one
    client's logits; the masked mean is one all-reduce (psum of (Σ m·y, Σ m))
    over the federation axis. Semantically identical to masked_mean_logits.
    """
    m = local_mask.astype(jnp.float32)[..., None]
    num = jax.lax.psum(local_logits.astype(jnp.float32) * m, axis_name)
    den = jax.lax.psum(m, axis_name)
    teacher = num / jnp.maximum(den, 1.0)
    return teacher, den[..., 0] > 0.0


def classwise_mean_logits(logits, labels, num_classes: int):
    """FKD/PLS-style data-free aggregation: per-label mean logits.

    logits: (n, K) local logits on *private* data; labels: (n,).
    Returns (K_classes, K) matrix of mean logits per class (zero rows for
    absent classes) and per-class counts.
    """
    one_hot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)  # (n, C)
    sums = one_hot.T @ logits.astype(jnp.float32)                     # (C, K)
    cnt = jnp.sum(one_hot, axis=0)[:, None]
    return sums / jnp.maximum(cnt, 1.0), cnt[:, 0]


# ---------------------------------------------------------------------------
# Robust reducers over the client axis
# ---------------------------------------------------------------------------

def _sorted_valid(logits, mask):
    """Sort each (t, K) coordinate over the client axis with invalid
    (masked-out or non-finite) rows pushed to ``+inf``, so the first
    ``n[t]`` entries per coordinate are the valid values ascending."""
    lo = jnp.asarray(logits, jnp.float32)
    fin = jnp.isfinite(lo).all(axis=-1)
    m = jnp.logical_and(mask, fin)                           # (C, t)
    xs = jnp.sort(jnp.where(m[..., None], lo, jnp.inf), axis=0)
    n = jnp.sum(m, axis=0)                                   # (t,) int
    return xs, n, m


def trimmed_mean_logits(logits, mask, *, trim_frac: float = 0.2,
                        temperature_sharpen: Optional[float] = None):
    """Coordinate-wise trimmed mean over the client axis.

    Per (t, k) coordinate, drops the ``floor(trim_frac * n_t)`` smallest
    and largest of the ``n_t`` valid client values and averages the rest
    (``trim_frac < 0.5`` guarantees at least one survivor). Tolerates up to
    a ``trim_frac`` fraction of arbitrarily-corrupted clients per position.
    """
    if not 0.0 <= trim_frac < 0.5:
        raise ValueError(f"trim_frac must be in [0, 0.5), got {trim_frac!r}")
    xs, n, _ = _sorted_valid(logits, mask)
    k = jnp.floor(trim_frac * n).astype(n.dtype)             # (t,)
    ranks = jnp.arange(xs.shape[0])[:, None, None]           # (C, 1, 1)
    keep = ((ranks >= k[None, :, None])
            & (ranks < (n - k)[None, :, None]))              # (C, t, 1)
    num = jnp.sum(jnp.where(keep, xs, 0.0), axis=0)          # (t, K)
    den = jnp.sum(keep, axis=0).astype(jnp.float32)          # (t, 1)
    teacher = num / jnp.maximum(den, 1.0)
    return _sharpen(teacher, temperature_sharpen), n > 0


def median_logits(logits, mask, *,
                  temperature_sharpen: Optional[float] = None):
    """Coordinate-wise median over the client axis (the 50%-breakdown
    robust center; even counts average the two middle values)."""
    xs, n, _ = _sorted_valid(logits, mask)
    top = xs.shape[0] - 1
    shape = (1,) + xs.shape[1:]

    def pick(idx):
        idx = jnp.clip(idx, 0, top).astype(jnp.int32)        # (t,)
        return jnp.take_along_axis(
            xs, jnp.broadcast_to(idx[None, :, None], shape), axis=0)[0]

    med = 0.5 * (pick((n - 1) // 2) + pick(n // 2))          # (t, K)
    teacher = jnp.where((n > 0)[:, None], med, 0.0)
    return _sharpen(teacher, temperature_sharpen), n > 0


def krum_row_logits(logits, mask, *,
                    temperature_sharpen: Optional[float] = None):
    """Per-proxy-position Krum: each (t,) position selects the single
    client whose logits sit closest to its ``n_t - 2`` nearest neighbours
    (sum of squared distances), i.e. the most-corroborated report. Ties
    resolve to the lowest client id. O(C^2 t K) — intended for modest
    cohort sizes; prefer trimmed_mean/median at fleet scale."""
    lo = jnp.asarray(logits, jnp.float32)
    fin = jnp.isfinite(lo).all(axis=-1)
    m = jnp.logical_and(mask, fin)                           # (C, t)
    safe = jnp.where(m[..., None], lo, 0.0)
    num_clients = lo.shape[0]
    diff = safe[:, None] - safe[None, :]                     # (C, C, t, K)
    d2 = jnp.sum(diff * diff, axis=-1)                       # (C, C, t)
    pair = m[:, None, :] & m[None, :, :]
    eye = jnp.eye(num_clients, dtype=bool)[:, :, None]
    d2 = jnp.where(pair & ~eye, d2, jnp.inf)
    ds = jnp.sort(d2, axis=1)                                # neighbours asc
    n = jnp.sum(m, axis=0)                                   # (t,)
    q = jnp.maximum(n - 2, 1)
    take = jnp.arange(num_clients)[None, :, None] < q[None, None, :]
    score = jnp.sum(jnp.where(take & jnp.isfinite(ds), ds, 0.0), axis=1)
    score = jnp.where(m, score, jnp.inf)                     # (C, t)
    best = jnp.argmin(score, axis=0)                         # (t,)
    teacher = jnp.take_along_axis(
        safe, jnp.broadcast_to(best[None, :, None],
                               (1,) + safe.shape[1:]), axis=0)[0]
    teacher = jnp.where((n > 0)[:, None], teacher, 0.0)
    return _sharpen(teacher, temperature_sharpen), n > 0


def robust_reduce(logits, mask, mode: str, *, trim_frac: float = 0.2,
                  temperature_sharpen: Optional[float] = None):
    """Dispatch one of ``ROBUST_AGGREGATIONS`` over the client axis.

    ``mean`` takes the exact legacy ``masked_mean_logits`` path. The robust
    modes are unweighted by design — staleness weights act only as a
    contribute/exclude mask upstream (a decayed-but-honest report is one
    vote, not a fractional one; robust order statistics have no natural
    notion of fractional voters).
    """
    if mode == "mean":
        return masked_mean_logits(logits, mask,
                                  temperature_sharpen=temperature_sharpen)
    if mode == "trimmed_mean":
        return trimmed_mean_logits(logits, mask, trim_frac=trim_frac,
                                   temperature_sharpen=temperature_sharpen)
    if mode == "median":
        return median_logits(logits, mask,
                             temperature_sharpen=temperature_sharpen)
    if mode == "krum_row":
        return krum_row_logits(logits, mask,
                               temperature_sharpen=temperature_sharpen)
    raise ValueError(
        f"robust_aggregation must be one of {ROBUST_AGGREGATIONS}, "
        f"got {mode!r}")


# ---------------------------------------------------------------------------
# Host-side sanitation + outlier scoring (defense-stack helpers)
# ---------------------------------------------------------------------------

def scrub_nonfinite(logits: np.ndarray,
                    masks: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                                np.ndarray]:
    """Server-side sanitize pass over raw ``(C, t, K)`` reports.

    Rows with any non-finite entry are zeroed and removed from the mask
    *before* they can enter the staleness buffer or an edge partial.
    Returns ``(logits, masks, scrubbed_per_client)`` where the count is the
    number of claimed-ID rows each client lost. Clean inputs are returned
    as the same objects (no copy), keeping the common path bit-for-bit.
    """
    lo = np.asarray(logits, np.float32)
    mk = np.asarray(masks, bool)
    fin = np.isfinite(lo).all(axis=-1)                       # (C, t)
    scrubbed = (mk & ~fin).sum(axis=1).astype(np.int64)      # (C,)
    if fin.all():
        return lo, mk, scrubbed
    return (np.where(fin[..., None], lo, 0.0).astype(np.float32),
            mk & fin, scrubbed)


def client_outlier_distance(logits, masks,
                            teacher) -> Tuple[np.ndarray, np.ndarray]:
    """Per-client mean squared distance from the fused (robust) center.

    The trust/quarantine signal: for each client, the mean over its
    claimed-ID rows of ``mean_k (logit - teacher)^2``, computed only where
    both the client row and the teacher row are finite. A client whose own
    claimed rows contain non-finite values scores ``inf`` (sending NaN *is*
    the strongest outlier evidence). Returns ``(dist (C,), contributing
    (C,) bool)`` — non-contributing clients score 0 and must not have their
    trust updated.
    """
    lo = np.asarray(logits, np.float32)
    mk = np.asarray(masks, bool)
    th = np.asarray(teacher, np.float32)
    own_fin = np.isfinite(lo).all(axis=-1)                   # (C, t)
    th_fin = np.isfinite(th).all(axis=-1)                    # (t,)
    use = mk & own_fin & th_fin[None, :]
    lo_c = np.where(own_fin[..., None], lo, 0.0)
    th_c = np.where(th_fin[:, None], th, 0.0)
    diff = lo_c - th_c[None]
    d2 = np.where(use, (diff * diff).mean(axis=-1), 0.0)     # (C, t)
    cnt = use.sum(axis=1)
    dist = d2.sum(axis=1) / np.maximum(cnt, 1)
    dist = np.where((mk & ~own_fin).any(axis=1), np.inf, dist)
    return dist.astype(np.float64), mk.any(axis=1)
