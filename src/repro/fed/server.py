"""Federated server: proxy bookkeeping + aggregation. Trusted entity that
never trains a model (EdgeFD needs no pre-trained teacher).

Report *ingest* and *aggregation* are separate steps so in-flight rounds
can interleave (``repro.fed.scheduler`` with ``round_mode="overlap"``):
``ingest_reports`` records a round's engine outputs — merging stale rows
from the ``StalenessBuffer`` at ingest time, while the buffer still
reflects only earlier rounds — and ``aggregate_round`` later fuses the
recorded reports into a teacher. Under the lockstep ``sync`` mode the two
run back-to-back and reproduce the historical single-call path
bit-for-bit.

With ``num_edges > 1`` the server is **two-tier**: E edge aggregators each
own a contiguous client shard and, at ingest time, locally apply the
server-side filter, run staleness bookkeeping against a *per-shard*
lazily-materialized ``StalenessBuffer``, and reduce their shard to one
``(num, den)`` masked/weighted partial sum (``repro.core.aggregation``).
The root only ever sees E partials — its per-round work and the in-flight
report footprint scale with E and the proxy batch, not with C, which is
what lets ``benchmarks/scale.py`` push C to 16k on a laptop-class host.
``num_edges=1`` (default) is the flat single-tier server, bit-for-bit the
legacy aggregation and byte accounting."""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import tracing
from repro.common.tracing import fetch
from repro.core import aggregation
from repro.core import distill as D
from repro.core.filtering import server_entropy_filter
from repro.data.proxy import ProxyData, select_round_indices
from repro.fed.batching import epoch_batches
from repro.fed.participation import StaleMerge, StalenessBuffer
from repro.optim.optimizers import Optimizer, apply_updates


class _ServerStudent:
    """FedDF-style central student (``method="server_distill"``).

    The server — which otherwise never trains — owns one model and distills
    it each round on the unlabeled proxy batch against the masked/weighted
    ensemble teacher the clients are about to receive (Lin et al., FedDF:
    ensemble distillation is the standard fusion for model-heterogeneous
    zoos, since parameter averaging needs a shared architecture). The step
    mirrors ``Client._distill_step`` so the student's KD objective is the
    exact client objective."""

    def __init__(self, apply_fn, params, opt: Optimizer, *,
                 temperature: float = 3.0, seed: int = 0):
        self.apply_fn = apply_fn
        self.params = params
        self.opt = opt
        self.opt_state = opt.init(params)
        self.temperature = temperature
        # epoch shuffling stream, disjoint from the server's admission rng
        # (seed + 7) and every client's stream (seed + 1000 * cid)
        self.rng = np.random.default_rng(seed + 31)

        @jax.jit
        def _distill_step(params, opt_state, xb, teacher, w):
            def loss_fn(p):
                logits = apply_fn(p, xb, True)
                return D.kd_kl_loss(logits, teacher, temperature, w)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            upd, opt_state = opt.update(grads, opt_state, params)
            return apply_updates(params, upd), opt_state, loss

        @jax.jit
        def _predict(params, xb):
            return apply_fn(params, xb, False)

        self._distill_step = _distill_step
        self._predict = _predict

    def distill(self, px, teacher, weight, epochs: int,
                batch_size: int) -> float:
        n = len(px)
        losses = []
        for _ in range(epochs):
            for idx in epoch_batches(self.rng.permutation(n), batch_size):
                self.params, self.opt_state, loss = self._distill_step(
                    self.params, self.opt_state, jnp.asarray(px[idx]),
                    jnp.asarray(teacher[idx]), jnp.asarray(weight[idx]))
                losses.append(float(fetch(loss, "server")))
        return float(np.mean(losses)) if losses else 0.0

    def evaluate(self, x_test, y_test, batch_size: int = 512) -> float:
        hits = 0
        for lo in range(0, len(y_test), batch_size):
            xb = jnp.asarray(x_test[lo:lo + batch_size])
            preds = fetch(jnp.argmax(self._predict(self.params, xb),
                                     axis=-1), "server")
            hits += int((preds == np.asarray(y_test[lo:lo + batch_size]))
                        .sum())
        return hits / max(len(y_test), 1)

    def state_dict(self) -> dict:
        from repro.fed.state import rng_state_dict
        from repro.checkpoint.ckpt import flatten_tree
        return {
            "params": flatten_tree(self.params),
            "opt_state": flatten_tree(self.opt_state),
            "rng": rng_state_dict(self.rng),
        }

    def load_state_dict(self, sd: dict) -> None:
        from repro.fed.state import load_rng_state
        from repro.checkpoint.ckpt import unflatten_like
        self.params = unflatten_like(sd["params"], self.params)
        self.opt_state = unflatten_like(sd["opt_state"], self.opt_state)
        load_rng_state(self.rng, sd["rng"])


class _PendingReports(NamedTuple):
    """One round's ingested-but-not-yet-aggregated proxy reports.

    Exactly one payload is held: the raw engine outputs on the
    full-participation path, or the stale-merged rows on the subset path
    (keeping both would double the in-flight footprint — overlap mode
    parks up to ``max_inflight`` of these)."""
    participants: Optional[np.ndarray]   # (C,) bool, None = everyone
    logits: Optional[np.ndarray]         # (C, t, K); None when merged is set
    masks: Optional[np.ndarray]          # (C, t);   None when merged is set
    merged: Optional[StaleMerge]         # stale-filled rows (subset rounds)


class _PendingPartials(NamedTuple):
    """One round's edge-reduced reports (``num_edges > 1`` only).

    Each edge already collapsed its client shard to a masked/weighted
    partial sum, so a pending round costs O(E · t · K) — the (C, t, K)
    stack never outlives ``ingest_reports``."""
    nums: np.ndarray        # (E, t, K) per-edge weighted logit sums
    dens: np.ndarray        # (E, t) per-edge weight sums
    uploaded_bytes: int     # upload traffic, priced from pre-filter masks
    mean_staleness: float   # exact fleet-wide Σ age / Σ contributing
    # trust-signal extras (track_outliers only; None keeps old checkpoints
    # loadable): per-client distance from the *edge-local* center and the
    # contributing mask, computed at ingest since the stack dies here
    outlier: Optional[np.ndarray] = None    # (C,) float
    contrib: Optional[np.ndarray] = None    # (C,) bool


@functools.partial(jax.jit, static_argnames=(
    "mode", "trim_frac", "entropy_filter", "guard_finite"))
def server_aggregate(logits, masks, client_weights, uploaded_rows, sharpen,
                     *, mode: str, trim_frac: float, entropy_filter: bool,
                     guard_finite: bool):
    """The single-tier server reduce as one program.

    logits: (C, t, K); masks: (C, t) bool; ``client_weights``: (C,)
    staleness weights, or None for fresh reports; ``uploaded_rows``: (C,)
    bool, the clients that uploaded this round, or None for all;
    ``sharpen``: DS-FL's temperature, or None. The temperature is an
    operand, not a constant: XLA would turn a division by a constant into
    a product with its reciprocal, which rounds differently.

    Returns ``(teacher (t, K), valid (t,), uploaded)``. ``uploaded``
    counts the ID rows the uploaders sent from the *pre-filter* masks:
    that is what crossed the network, and the server-side filter only
    tightens what the reduce uses."""
    if uploaded_rows is not None:
        uploaded = jnp.sum(jnp.logical_and(masks, uploaded_rows[:, None]))
    else:
        uploaded = jnp.sum(masks)
    if entropy_filter:  # Selective-FD baseline's extra server stage
        masks = server_entropy_filter(logits, masks)
    if mode != "mean":
        # robust order statistics have no fractional voters: staleness
        # weights act only as a contribute/exclude mask here
        if client_weights is not None:
            masks = jnp.logical_and(masks, (client_weights > 0.0)[:, None])
        teacher, valid = aggregation.robust_reduce(
            logits, masks, mode, trim_frac=trim_frac)
    elif client_weights is not None:
        teacher, valid = aggregation.weighted_masked_mean_logits(
            logits, masks, client_weights, guard_finite=guard_finite)
    else:
        teacher, valid = aggregation.masked_mean_logits(
            logits, masks, guard_finite=guard_finite)
    if sharpen is not None:
        # the barrier keeps XLA from folding the mean's divide into the
        # temperature's ((s / n) / T into s / (n * T)), which rounds
        # differently from the reducers' own sharpening
        teacher = aggregation.sharpen_logits(
            jax.lax.optimization_barrier(teacher), sharpen)
    return teacher, valid, uploaded


# EWMA trust scores for non-finite senders are pinned here instead of inf
# so the running average stays finite (inf would never decay back)
_TRUST_CAP = 1e9


class Server:
    def __init__(self, proxy: ProxyData, *, seed: int = 0,
                 num_edges: int = 1, max_pending_reports: int = 0,
                 robust_aggregation: str = "mean", trim_frac: float = 0.2,
                 sanitize: bool = True, quarantine_threshold: float = 0.0,
                 trust_ewma: float = 0.5, quarantine_rounds: int = 2,
                 track_outliers: bool = False):
        if num_edges < 1:
            raise ValueError(f"num_edges must be >= 1, got {num_edges!r}")
        if max_pending_reports < 0:
            raise ValueError(f"max_pending_reports must be >= 0 "
                             f"(0 = unbounded), got {max_pending_reports!r}")
        if robust_aggregation not in aggregation.ROBUST_AGGREGATIONS:
            raise ValueError(
                f"robust_aggregation must be one of "
                f"{aggregation.ROBUST_AGGREGATIONS}, "
                f"got {robust_aggregation!r}")
        if not 0.0 <= trim_frac < 0.5:
            raise ValueError(
                f"trim_frac must be in [0, 0.5), got {trim_frac!r}")
        if quarantine_threshold < 0.0:
            raise ValueError(f"quarantine_threshold must be >= 0 "
                             f"(0 = off), got {quarantine_threshold!r}")
        if not 0.0 < trust_ewma <= 1.0:
            raise ValueError(
                f"trust_ewma must be in (0, 1], got {trust_ewma!r}")
        if quarantine_rounds < 1:
            raise ValueError(f"quarantine_rounds must be >= 1, "
                             f"got {quarantine_rounds!r}")
        self.proxy = proxy
        self.rng = np.random.default_rng(seed + 7)
        self.num_edges = int(num_edges)
        # -- defense stack --------------------------------------------------
        self.robust_aggregation = robust_aggregation
        self.trim_frac = float(trim_frac)
        self.sanitize = bool(sanitize)
        self.quarantine_threshold = float(quarantine_threshold)
        self.trust_ewma = float(trust_ewma)
        self.quarantine_rounds = int(quarantine_rounds)
        # outlier distances are only worth computing when someone consumes
        # them: the auto-quarantine rule or the scheduler's watchdog
        self.track_outliers = bool(track_outliers) or quarantine_threshold > 0
        # sanitize-pass accounting: cumulative scrubbed rows (total and per
        # client) plus the per-round counts the scheduler pops into RoundLog
        self.scrub_total = 0
        self.scrub_clients: Optional[np.ndarray] = None       # (C,) int64
        self._scrubbed_rounds: Dict[int, int] = {}
        # trust & quarantine (lazily sized to the fleet on first signal):
        # trust = EWMA of the median-normalized outlier distance;
        # quarantined_until[c] > r means c sits out round r; strikes
        # escalate re-quarantine duration
        self.trust: Optional[np.ndarray] = None               # (C,) float
        self.quarantined_until: Optional[np.ndarray] = None   # (C,) int64
        self.strikes: Optional[np.ndarray] = None             # (C,) int64
        # per-round normalized outlier scores / quarantine events, parked
        # until the scheduler pops them at round retire (both checkpointed
        # — aggregate and retire can be separated by a kill)
        self._round_outlier: Dict[int, np.ndarray] = {}
        self._quarantine_events: Dict[int, List[int]] = {}
        # admission/backpressure: the ingest queue holds at most this many
        # client reports across all in-flight rounds (0 = unbounded, the
        # legacy behavior). A report arriving at a full queue is refused —
        # the client's round contribution drains through the staleness
        # machinery like a dropout. Counted per round in
        # ``_inflight_reports`` and released by ``aggregate_round``.
        self.max_pending_reports = int(max_pending_reports)
        self._inflight_reports: Dict[int, int] = {}
        self.bytes_received = 0
        self.bytes_broadcast = 0
        # lazily-sized staleness buffer (partial participation only): the
        # last report of every client, by proxy-dataset position.
        # single-tier keeps one flat buffer; two-tier keeps one per edge
        # shard (each materialized on that shard's first subset ingest)
        self._stale: Optional[StalenessBuffer] = None
        self._edge_stale: List[Optional[StalenessBuffer]] = []
        self._shard_slices: Optional[List[slice]] = None
        # rounds whose reports were ingested but not yet aggregated,
        # keyed by round index (overlap mode keeps up to max_inflight here)
        self._pending: Dict[int, Union[_PendingReports,
                                       _PendingPartials]] = {}
        # FedDF central student (method="server_distill" only) — attached
        # by the simulator after model init so the server stays model-free
        # for every other method
        self.student: Optional[_ServerStudent] = None

    def attach_student(self, apply_fn, params, opt: Optimizer, *,
                       temperature: float = 3.0, seed: int = 0) -> None:
        """Give the server a trainable student for ensemble distillation."""
        self.student = _ServerStudent(apply_fn, params, opt,
                                      temperature=temperature, seed=seed)

    def ensemble_distill(self, px, teacher, valid, *, epochs: int,
                         batch_size: int) -> float:
        """One FedDF server round: fit the student on the proxy batch
        against the masked/weighted ensemble teacher. ``valid`` is the
        aggregate coverage mask — rows no client predicted carry zero
        weight, exactly as in client-side distillation."""
        if self.student is None:
            raise RuntimeError("ensemble_distill requires attach_student()")
        w = np.asarray(valid, np.float32)
        return self.student.distill(np.asarray(px), np.asarray(teacher), w,
                                    epochs, batch_size)

    def evaluate_student(self, x_test, y_test) -> float:
        if self.student is None:
            raise RuntimeError("evaluate_student requires attach_student()")
        return self.student.evaluate(x_test, y_test)

    def _shards(self, num_clients: int) -> List[slice]:
        """Contiguous per-edge client shards, fixed at first use."""
        if self._shard_slices is None:
            e = min(self.num_edges, num_clients)
            bounds = np.linspace(0, num_clients, e + 1).astype(int)
            self._shard_slices = [slice(int(a), int(b))
                                  for a, b in zip(bounds[:-1], bounds[1:])
                                  if b > a]
            self._edge_stale = [None] * len(self._shard_slices)
        return self._shard_slices

    def select_indices(self, batch: int) -> np.ndarray:
        return select_round_indices(self.rng, self.proxy, batch)

    # ------------------------------------------------ trust & quarantine
    def _ensure_fleet(self, num_clients: int) -> None:
        """Size (or grow) the per-client bookkeeping arrays. Growth pads
        with zeros — callers that only know a subset of ids (quarantine)
        stay safe when a fleet-sized caller comes along later."""
        def grow(a, dtype):
            if a is None:
                return np.zeros((num_clients,), dtype)
            if a.shape[0] < num_clients:
                b = np.zeros((num_clients,), dtype)
                b[:a.shape[0]] = a
                return b
            return a
        self.trust = grow(self.trust, np.float64)
        self.quarantined_until = grow(self.quarantined_until, np.int64)
        self.strikes = grow(self.strikes, np.int64)
        self.scrub_clients = grow(self.scrub_clients, np.int64)

    def quarantine_mask(self, round_idx: int) -> Optional[np.ndarray]:
        """(C,) bool — True where a client sits out this round. ``None``
        (nobody ever quarantined) keeps the legacy participant draw
        untouched."""
        if self.quarantined_until is None:
            return None
        mask = self.quarantined_until > round_idx
        return mask if mask.any() else None

    def quarantine(self, ids, first_round: int, *,
                   event_round: Optional[int] = None) -> List[int]:
        """Demote ``ids`` to non-participants from ``first_round`` on.

        Duration escalates with each client's strike count
        (``quarantine_rounds * strikes``); on release the client re-enters
        on probation — its trust is reset to half the threshold, so one
        more outlier round re-quarantines it while honest behaviour decays
        it back toward zero. The event is recorded under ``event_round``
        (default ``first_round``) for the scheduler to surface on that
        round's ``RoundLog``."""
        ids = sorted(int(c) for c in np.asarray(ids).ravel())
        if not ids:
            return []
        self._ensure_fleet(max(ids) + 1)
        for c in ids:
            self.strikes[c] += 1
            until = first_round + self.quarantine_rounds * int(
                self.strikes[c])
            self.quarantined_until[c] = max(
                int(self.quarantined_until[c]), until)
            self.trust[c] = 0.5 * self.quarantine_threshold
        key = first_round if event_round is None else event_round
        self._quarantine_events.setdefault(key, []).extend(ids)
        return ids

    def _update_trust(self, round_idx: int, dist: np.ndarray,
                      contributing: np.ndarray) -> None:
        """Fold one round's outlier distances into the EWMA trust scores.

        Distances are normalized by the round's median over finite
        contributors (scale-free across rounds/methods); non-finite
        senders pin at ``_TRUST_CAP``. Non-contributing clients are left
        untouched — absence is not evidence."""
        dist = np.asarray(dist, np.float64)
        contributing = np.asarray(contributing, bool)
        self._ensure_fleet(dist.shape[0])
        finite = np.isfinite(dist) & contributing
        scale = float(np.median(dist[finite])) if finite.any() else 0.0
        with np.errstate(invalid="ignore"):
            norm = np.where(np.isfinite(dist),
                            dist / max(scale, 1e-12), np.inf)
        norm = np.minimum(np.where(contributing, norm, 0.0), _TRUST_CAP)
        a = self.trust_ewma
        self.trust = np.where(contributing,
                              (1.0 - a) * self.trust + a * norm, self.trust)
        self._round_outlier[round_idx] = norm
        if self.quarantine_threshold > 0.0:
            bad = contributing & (self.trust > self.quarantine_threshold)
            if bad.any():
                # round_idx just aggregated — exclusion starts next round
                self.quarantine(np.nonzero(bad)[0], round_idx + 1,
                                event_round=round_idx)

    def pop_scrubbed(self, round_idx: int) -> int:
        """Rows the sanitize pass scrubbed from this round's reports."""
        return int(self._scrubbed_rounds.pop(round_idx, 0))

    def pop_quarantined(self, round_idx: int) -> List[int]:
        """Clients quarantined on this round's evidence (may be empty)."""
        return self._quarantine_events.pop(round_idx, [])

    def pop_round_outlier(self, round_idx: int) -> Optional[np.ndarray]:
        """This round's normalized outlier scores (watchdog suspect
        ranking); None when tracking is off or the round had none."""
        return self._round_outlier.pop(round_idx, None)

    def admit_reports(self, round_idx: int,
                      ordered_ids: np.ndarray) -> np.ndarray:
        """Admission control over one round's report arrivals.

        ``ordered_ids``: the round's reporting client ids in simulated-
        arrival order (the scheduler sorts by report-phase lane finish,
        ties broken by id). Each arrival is admitted while the ingest
        queue has room — ``max_pending_reports`` minus the reports already
        parked for not-yet-aggregated rounds — and refused afterwards, so
        exactly the *earliest* arrivals of an overloaded round get in.
        Returns the admitted prefix; with ``max_pending_reports=0`` every
        report is admitted and nothing is recorded (the legacy path).
        """
        ordered_ids = np.asarray(ordered_ids)
        if self.max_pending_reports <= 0:
            return ordered_ids
        used = sum(self._inflight_reports.values())
        free = max(0, self.max_pending_reports - used)
        admitted = ordered_ids[:free]
        self._inflight_reports[round_idx] = int(admitted.size)
        return admitted

    def merge_stale(self, round_idx: int, participants, idx, logits, masks,
                    *, decay: float) -> StaleMerge:
        """Record this round's fresh reports and fill non-participant rows
        from each client's last report (``repro.fed.participation``)."""
        if self._stale is None:
            c, _, k = np.asarray(logits).shape
            self._stale = StalenessBuffer(c, len(self.proxy.x), k)
        return self._stale.merge(round_idx, participants, idx, logits, masks,
                                 decay)

    def ingest_reports(self, round_idx: int, participants, idx, logits,
                       masks, *, decay: float,
                       entropy_filter: bool = False) -> None:
        """Record one round's engine reports for a later ``aggregate_round``.

        Stale rows are merged *now*: ingests arrive in round order (the
        scheduler's order edges guarantee it), so the buffer reflects
        exactly the rounds before this one and report ages can never go
        negative — even while later rounds' aggregations are still pending.
        ``participants=None`` (full participation) skips the buffer
        entirely, keeping the legacy everyone-reports path untouched.

        ``entropy_filter`` matters only on the two-tier path (the edges
        apply the Selective-FD server filter locally *before* reducing
        their shard); single-tier ingests keep the raw reports and the
        filter runs inside ``aggregate`` as it always has.
        """
        if round_idx in self._pending:
            raise ValueError(f"round {round_idx} reports already ingested "
                             "and not yet aggregated")
        if self.sanitize:
            # scrub *before* anything downstream — most importantly before
            # the staleness merge, so a corrupt row can never enter the
            # buffer and get replayed into later rounds. Clean reports come
            # back as the same objects (bit-for-bit the legacy path).
            logits, masks, per_client = aggregation.scrub_nonfinite(
                np.asarray(logits, np.float32), np.asarray(masks, bool))
            n_bad = int(per_client.sum())
            if n_bad:
                self._scrubbed_rounds[round_idx] = (
                    self._scrubbed_rounds.get(round_idx, 0) + n_bad)
                self.scrub_total += n_bad
                self._ensure_fleet(len(per_client))
                self.scrub_clients += per_client
        if self.num_edges > 1:
            self._pending[round_idx] = self._ingest_edges(
                round_idx, participants, idx, logits, masks, decay=decay,
                entropy_filter=entropy_filter)
            return
        if participants is None:
            self._pending[round_idx] = _PendingReports(
                None, logits, masks, None)
            return
        merged = self.merge_stale(round_idx, participants, idx, logits,
                                  masks, decay=decay)
        self._pending[round_idx] = _PendingReports(
            participants, None, None, merged)

    def _ingest_edges(self, round_idx: int, participants, idx, logits,
                      masks, *, decay: float,
                      entropy_filter: bool) -> _PendingPartials:
        """Two-tier ingest: every edge reduces its client shard to one
        masked/weighted ``(num, den)`` partial, doing the server-side
        filter and staleness bookkeeping shard-locally. The full (C, t, K)
        stack is consumed here and never parked in ``_pending``.

        With a robust ``robust_aggregation`` each edge runs the robust
        reduce over its *own shard* and contributes ``(center * n_e, n_e)``
        — the root then fuses contributor-weighted edge centers. This is an
        **approximation** of the flat robust reduce (a mean of per-shard
        medians is not the global median; its breakdown point degrades when
        attackers concentrate in one shard), traded for the same O(E·t·K)
        root cost as the mean path. ``num_edges=1`` never enters this
        method, so E=1 equals the flat robust reduce exactly."""
        logits = np.asarray(logits, np.float32)
        masks = np.asarray(masks, bool)
        part = (None if participants is None
                else np.asarray(participants, bool))
        k = logits.shape[-1]
        shards = self._shards(logits.shape[0])
        nums, dens = [], []
        uploaded_bytes = 0
        ages_sum, n_contrib = 0.0, 0
        subset = part is not None
        robust = self.robust_aggregation != "mean"
        outlier = (np.zeros((logits.shape[0],), np.float64)
                   if self.track_outliers else None)
        contrib = (np.zeros((logits.shape[0],), bool)
                   if self.track_outliers else None)
        for e, sl in enumerate(shards):
            l_e, m_e = logits[sl], masks[sl]
            cw = None
            if part is None:
                # everyone reported: uploads are the raw ID rows
                uploaded_bytes += int(m_e.sum()) * k * 4
            else:
                # uploads priced from the *pre-filter* fresh masks of this
                # round's reporters; stale reuse costs no bytes
                uploaded_bytes += int(m_e[part[sl]].sum()) * k * 4
                if self._edge_stale[e] is None:
                    self._edge_stale[e] = StalenessBuffer(
                        l_e.shape[0], len(self.proxy.x), k)
                merged = self._edge_stale[e].merge(
                    round_idx, part[sl], idx, l_e, m_e, decay)
                l_e, m_e, cw = merged.logits, merged.masks, merged.client_weights
                ages_sum += merged.ages_sum
                n_contrib += merged.num_contributing
            if entropy_filter:  # per-client-row filter — shard-local is exact
                m_e = fetch(server_entropy_filter(
                    jnp.asarray(l_e), jnp.asarray(m_e)), "server")
            if robust:
                # robust modes use staleness weights only as a
                # contribute/exclude mask (one vote per surviving client)
                m_r = m_e if cw is None else (m_e & (cw > 0.0)[:, None])
                t_e, _ = aggregation.robust_reduce(
                    jnp.asarray(l_e), jnp.asarray(m_r),
                    self.robust_aggregation, trim_frac=self.trim_frac)
                center = fetch(t_e, "server")
                cnt = m_r.sum(axis=0).astype(np.float32)      # (t,)
                num, den = center * cnt[:, None], cnt
            else:
                m_r = m_e
                num, den = aggregation.partial_masked_sums(
                    jnp.asarray(l_e), jnp.asarray(m_e),
                    None if cw is None else jnp.asarray(cw),
                    guard_finite=self.sanitize)
                num, den = fetch((num, den), "server")
                center = None
            if self.track_outliers:
                if center is None:
                    with np.errstate(invalid="ignore"):
                        center = num / np.maximum(den, 1.0)[:, None]
                d_e, c_e = aggregation.client_outlier_distance(
                    l_e, m_r, center)
                outlier[sl], contrib[sl] = d_e, c_e
            nums.append(num)
            dens.append(den)
        mean_staleness = (ages_sum / n_contrib
                          if subset and n_contrib else 0.0)
        return _PendingPartials(np.stack(nums), np.stack(dens),
                                uploaded_bytes, mean_staleness,
                                outlier, contrib)

    @tracing.spanned("server.aggregate")
    def aggregate_round(self, round_idx: int, *,
                        sharpen: Optional[float] = None,
                        entropy_filter: bool = False):
        """Fuse a previously ingested round into (teacher, valid,
        mean_staleness). Full-participation rounds take the exact legacy
        ``aggregate`` call (bit-for-bit the historical teacher and byte
        accounting); subset rounds aggregate the stale-merged rows with
        per-client staleness weights."""
        try:
            p = self._pending.pop(round_idx)
        except KeyError:
            raise ValueError(
                f"no ingested reports for round {round_idx}; call "
                "ingest_reports first") from None
        # aggregation consumes the round's parked reports — release their
        # admission-queue slots so later rounds stop being backpressured
        self._inflight_reports.pop(round_idx, None)
        if isinstance(p, _PendingPartials):
            # two-tier root: fuse the E edge partials (the filter and
            # staleness weights were already folded in at the edges)
            teacher, valid = aggregation.fuse_partial_sums(
                jnp.asarray(p.nums), jnp.asarray(p.dens),
                temperature_sharpen=sharpen)
            self.bytes_received += p.uploaded_bytes
            self.bytes_broadcast += int(teacher.shape[0]) * int(
                teacher.shape[-1]) * 4
            if self.track_outliers and p.outlier is not None:
                self._update_trust(round_idx, p.outlier, p.contrib)
            return (*fetch((teacher, valid), "server"), p.mean_staleness)
        if p.merged is None:
            teacher, valid = self.aggregate(p.logits, p.masks,
                                            sharpen=sharpen,
                                            entropy_filter=entropy_filter)
            if self.track_outliers:
                dist, contrib = aggregation.client_outlier_distance(
                    p.logits, p.masks, teacher)
                self._update_trust(round_idx, dist, contrib)
            return teacher, valid, 0.0
        teacher, valid = self.aggregate(
            p.merged.logits, p.merged.masks, sharpen=sharpen,
            entropy_filter=entropy_filter,
            client_weights=p.merged.client_weights,
            uploaded_rows=p.participants)
        if self.track_outliers:
            m_eff = (np.asarray(p.merged.masks, bool)
                     & (np.asarray(p.merged.client_weights) > 0.0)[:, None])
            dist, contrib = aggregation.client_outlier_distance(
                p.merged.logits, m_eff, teacher)
            self._update_trust(round_idx, dist, contrib)
        return teacher, valid, p.merged.mean_staleness

    def aggregate(self, logits, masks, *, sharpen: Optional[float] = None,
                  entropy_filter: bool = False, client_weights=None,
                  uploaded_rows=None):
        """logits: (C, t, K); masks: (C, t). Returns (teacher, valid).

        ``client_weights`` (C,) down-weights stale contributions by
        ``staleness_decay ** age`` (all-ones — every report fresh — takes
        the plain masked-mean path, bit-for-bit the legacy teacher).
        ``uploaded_rows`` (C,) restricts the upload accounting to clients
        that actually reported this round: stale reuse costs no bytes.

        The filter, the reduce and the upload count run as one compiled
        program (``server_aggregate``) read back in a single fetch; the
        host keeps only the byte arithmetic.
        """
        cw = (None if client_weights is None
              else np.asarray(client_weights, np.float32))
        if (cw is not None and self.robust_aggregation == "mean"
                and np.all(cw == 1.0)):
            cw = None
        rows = (None if uploaded_rows is None
                else np.asarray(uploaded_rows, bool))
        teacher, valid, uploaded = fetch(server_aggregate(
            logits, masks, cw, rows, sharpen or None,
            mode=self.robust_aggregation, trim_frac=self.trim_frac,
            entropy_filter=entropy_filter, guard_finite=self.sanitize),
            "server")
        # accounting: clients upload only ID logits (mask-compressed), and
        # only the round's participants upload at all
        k = teacher.shape[-1]
        self.bytes_received += int(uploaded) * k * 4
        self.bytes_broadcast += int(teacher.shape[0]) * k * 4
        return teacher, valid

    @tracing.spanned("server.aggregate")
    def aggregate_classwise(self, means_counts, *, count_weighted: bool,
                            uploaded_rows=None,
                            round_idx: Optional[int] = None):
        """FKD/PLS: fuse per-class mean logits from all clients.

        ``uploaded_rows`` (C,) restricts the upload accounting to this
        round's participants (sampled-out clients hand in zero counts and
        upload nothing); ``None`` keeps the legacy everyone-uploads count.

        With ``num_edges > 1`` each edge reduces its client shard's
        classwise sums first and the root fuses E partials — a regrouped
        sum, identical up to float ordering.

        A robust ``robust_aggregation`` applies the same client-axis
        reducers to the ``(C, K_cls, K)`` stack (class slots standing in
        for proxy positions), unweighted — per-class sample counts become
        a contribute/exclude mask, one vote per reporting client. The
        classwise payload is tiny (K_cls · K), so the robust reduce is
        always global, even with ``num_edges > 1``.
        """
        means = jnp.stack([m for m, _ in means_counts])     # (C, K_cls, K)
        counts = jnp.stack([c for _, c in means_counts])    # (C, K_cls)
        if self.sanitize:
            mn, cn = fetch((means, counts), "server")
            mn = np.asarray(mn, np.float32)
            fin = np.isfinite(mn).all(axis=-1)               # (C, K_cls)
            if not fin.all():
                per_client = ((cn > 0) & ~fin).sum(axis=1).astype(np.int64)
                n_bad = int(per_client.sum())
                if n_bad:
                    if round_idx is not None:
                        self._scrubbed_rounds[round_idx] = (
                            self._scrubbed_rounds.get(round_idx, 0) + n_bad)
                    self.scrub_total += n_bad
                    self._ensure_fleet(len(per_client))
                    self.scrub_clients += per_client
                means = jnp.asarray(np.where(fin[..., None], mn, 0.0))
                counts = jnp.asarray(np.where(fin, cn, 0))
        if self.robust_aggregation != "mean":
            teacher, valid = aggregation.robust_reduce(
                means, counts > 0, self.robust_aggregation,
                trim_frac=self.trim_frac)
            teacher, valid = jnp.asarray(teacher), jnp.asarray(valid)
        else:
            if count_weighted:
                w = counts[..., None]
            else:
                w = (counts > 0).astype(jnp.float32)[..., None]
            if self.num_edges > 1:
                shards = self._shards(int(means.shape[0]))
                num = sum(jnp.sum((means * w)[sl], axis=0) for sl in shards)
                den = sum(jnp.sum(w[sl], axis=0) for sl in shards)
            else:
                num = jnp.sum(means * w, axis=0)
                den = jnp.sum(w, axis=0)
            teacher = num / jnp.maximum(den, 1.0)
            valid = jnp.sum(counts, axis=0) > 0
        reporting = (means.shape[0] if uploaded_rows is None
                     else int(np.asarray(uploaded_rows, bool).sum()))
        self.bytes_received += reporting * int(np.prod(means.shape[1:])) * 4
        # the fused classwise teacher is broadcast to every client, exactly
        # like the proxy-logit teacher in ``aggregate`` (this path used to
        # report zero download traffic for FKD/PLS data-free rounds)
        self.bytes_broadcast += int(np.prod(teacher.shape)) * 4
        return fetch((teacher, valid), "server")

    # ------------------------------------------------- resumable service
    def state_dict(self) -> dict:
        """All mutable server state (``repro.fed.state.ExperimentState``):
        rng, byte ledger, staleness buffers (flat + per-edge), shard
        bounds, admission-queue occupancy and the parked per-round report
        payloads. The proxy dataset is rebuilt from config, not captured.
        """
        from repro.fed.state import rng_state_dict
        pending = []
        for r in sorted(self._pending):
            p = self._pending[r]
            if isinstance(p, _PendingPartials):
                pending.append({
                    "round": r, "kind": "partials",
                    "nums": p.nums, "dens": p.dens,
                    "uploaded_bytes": int(p.uploaded_bytes),
                    "mean_staleness": float(p.mean_staleness),
                    "outlier": p.outlier, "contrib": p.contrib})
                continue
            m = p.merged
            pending.append({
                "round": r, "kind": "reports",
                "participants": p.participants,
                "logits": p.logits, "masks": p.masks,
                "merged": None if m is None else {
                    "logits": m.logits, "masks": m.masks,
                    "client_weights": m.client_weights,
                    "mean_staleness": float(m.mean_staleness),
                    "ages_sum": float(m.ages_sum),
                    "num_contributing": int(m.num_contributing)}})
        return {
            "rng": rng_state_dict(self.rng),
            "bytes_received": int(self.bytes_received),
            "bytes_broadcast": int(self.bytes_broadcast),
            "stale": (None if self._stale is None
                      else self._stale.state_dict()),
            "edge_stale": [None if b is None else b.state_dict()
                           for b in self._edge_stale],
            "shard_bounds": (None if self._shard_slices is None
                             else [[s.start, s.stop]
                                   for s in self._shard_slices]),
            "inflight_reports": [[r, n] for r, n
                                 in sorted(self._inflight_reports.items())],
            "pending": pending,
            "student": (None if self.student is None
                        else self.student.state_dict()),
            # defense stack: sanitize accounting + trust/quarantine (all
            # optional on load, so pre-robustness checkpoints stay valid)
            "scrub_total": int(self.scrub_total),
            "scrub_clients": self.scrub_clients,
            "scrubbed_rounds": [[r, n] for r, n
                                in sorted(self._scrubbed_rounds.items())],
            "trust": self.trust,
            "quarantined_until": self.quarantined_until,
            "strikes": self.strikes,
            "round_outlier": [[r, a] for r, a
                              in sorted(self._round_outlier.items())],
            "quarantine_events": [
                [r, [int(c) for c in ids]]
                for r, ids in sorted(self._quarantine_events.items())],
        }

    def load_state_dict(self, sd: dict) -> None:
        from repro.fed.state import load_rng_state, opt_array
        load_rng_state(self.rng, sd["rng"])
        self.bytes_received = int(sd["bytes_received"])
        self.bytes_broadcast = int(sd["bytes_broadcast"])
        self._stale = (None if sd["stale"] is None
                       else StalenessBuffer.from_state_dict(sd["stale"]))
        self._edge_stale = [
            None if b is None else StalenessBuffer.from_state_dict(b)
            for b in (sd.get("edge_stale") or [])]
        bounds = sd.get("shard_bounds")
        self._shard_slices = (None if bounds is None
                              else [slice(int(a), int(b))
                                    for a, b in bounds])
        self._inflight_reports = {int(r): int(n)
                                  for r, n in sd.get("inflight_reports", [])}
        self._pending = {}
        for e in sd["pending"]:
            r = int(e["round"])
            if e["kind"] == "partials":
                self._pending[r] = _PendingPartials(
                    np.asarray(e["nums"]), np.asarray(e["dens"]),
                    int(e["uploaded_bytes"]), float(e["mean_staleness"]),
                    opt_array(e.get("outlier"), np.float64),
                    opt_array(e.get("contrib"), bool))
                continue
            m = e["merged"]
            merged = None if m is None else StaleMerge(
                np.asarray(m["logits"], np.float32),
                np.asarray(m["masks"], bool),
                np.asarray(m["client_weights"], np.float32),
                float(m["mean_staleness"]), float(m["ages_sum"]),
                int(m["num_contributing"]))
            self._pending[r] = _PendingReports(
                opt_array(e["participants"], bool),
                opt_array(e["logits"], np.float32),
                opt_array(e["masks"], bool), merged)
        # the student object (model/opt/jit) is rebuilt from config by the
        # simulator; here we only restore its mutable tensors + rng
        student = sd.get("student")
        if student is not None and self.student is not None:
            self.student.load_state_dict(student)
        # defense stack (absent in pre-robustness checkpoints)
        self.scrub_total = int(sd.get("scrub_total", 0))
        self.scrub_clients = opt_array(sd.get("scrub_clients"), np.int64)
        self._scrubbed_rounds = {int(r): int(n)
                                 for r, n in sd.get("scrubbed_rounds", [])}
        self.trust = opt_array(sd.get("trust"), np.float64)
        self.quarantined_until = opt_array(sd.get("quarantined_until"),
                                           np.int64)
        self.strikes = opt_array(sd.get("strikes"), np.int64)
        self._round_outlier = {int(r): np.asarray(a, np.float64)
                               for r, a in sd.get("round_outlier", [])}
        self._quarantine_events = {
            int(r): [int(c) for c in ids]
            for r, ids in sd.get("quarantine_events", [])}
