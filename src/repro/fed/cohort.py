"""Batched cohort engine: vmapped clients, scanned minibatches, one jit.

The per-client ``LoopEngine`` (``repro.core.protocol``) pays a Python
dispatch and a host↔device transfer per client per step, capping
simulations at a handful of clients. This engine stacks clients into
leading-axis ``(C, ...)`` pytrees and runs every round phase — local
training, proxy logits, filter masks, distillation, evaluation — as a
single compiled call: ``jax.vmap`` over clients, ``jax.lax.scan`` over
minibatch steps. The KMeans-DRE learn/estimate path is vmapped too
(``core.kmeans.kmeans_fit_batched``), so all clients' filters run in one
call per round.

Homogeneous-cohort grouping rule
--------------------------------
``vmap`` requires every stacked client to share one ``apply_fn`` and one
parameter-tree structure, so clients are grouped by ``Client.arch_key``:
clients with equal keys form one cohort; a client with ``arch_key=None``
becomes a singleton cohort (still batched internally, trivially). The
paper's headline setting (Tables I/II) gives *every* client a distinct
CNN — there this engine degenerates to ten singleton cohorts and wins
little; its target is the paper's CIFAR10* feature mode and the FedDF /
FedD3-style scaling regimes (tens to hundreds of clients sharing an
architecture), where one compiled call replaces C Python loops. Mixed
populations work fine: each architecture group is its own cohort and the
round log is assembled in global client order.

Clients with unequal private-set sizes are padded to the cohort maximum;
padded samples carry zero loss weight and padded steps are no-ops
(params/opt-state gated by a validity flag), so results match the loop
engine exactly (``tests/test_cohort_parity.py``).

Device-mesh sharding
--------------------
Pass a 1-D ``Mesh`` (``repro.fed.mesh.build_client_mesh``) and every
stacked pytree is placed with its client axis split across the mesh
(``NamedSharding``), so each compiled round phase runs device-parallel
with zero cross-device collectives (per-client work is independent; the
server's cross-client aggregation happens on host). Cohorts whose client
count is not a multiple of the mesh size are padded with *dummy clients*
whose step-validity flags are all False — the same ``_where_tree`` gating
that freezes short clients makes every dummy step a no-op — and dummy
rows are sliced off before any result leaves the engine. Outputs of the
jitted phases are pinned back to the client axis via the logical-rules
machinery in ``repro.models.sharding`` (logical axis ``"clients"``), so
params/opt-state never decay to a single device between rounds.

Wave streaming
--------------
``wave_size > 0`` bounds *peak device memory by the wave, not by C*: the
cohort host-stages every stacked ``(C, ...)`` array (data, params,
opt-state, filter state) as numpy and runs each compiled phase
``wave_size`` clients at a time — rows ``[lo, hi)`` are staged onto the
device (padded to the wave's mesh-divisible ``c_pad`` with the same
validity-gated dummy lanes used everywhere else), the phase runs, results
stream back to the host arrays, and the device buffers are dropped before
the next wave. Every jitted phase is built once with the *wave* as its
leading axis, so shapes never change across waves, rounds, or
participation subsets — zero retraces (guarded in
``tests/test_scale.py``). Per-client math is lane-independent, so waved
results match the single-wave path; ``wave_size = 0`` (default) or
``wave_size >= C`` keeps the historical device-resident path bit-for-bit.

Partial participation
---------------------
Every round phase accepts a per-round participation mask
(``repro.fed.participation``). Sampled-out clients ride along as *no-op
lanes*: their step-validity flags stay all-False — the same
``_where_tree`` gating that freezes dummy padding clients — their rng
streams are not advanced (keeping loop↔cohort parity), and their
logits/mask rows are zeroed before leaving the engine. The mask changes
only data, never array shapes, so sampling a different subset each round
reuses every compiled phase, and it composes with mesh padding (a dummy
row is simply a lane no mask ever validates).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import tracing
from repro.common.tracing import fetch
from repro.core import distill as D
from repro.core.dre import KMeansDRE, KuLSIFDRE
from repro.core.kmeans import kmeans_fit_batched, min_dist_to_centroids
from repro.fed.batching import padded_epoch_plan, steps_per_epoch
from repro.fed.client import Client
from repro.fed.mesh import (DEFAULT_CLIENT_AXIS, MODEL_LOGICAL_RULES,
                            model_axis_name, padded_size, replicate,
                            shard_clients, shard_stacked_state,
                            stacked_state_shardings)
from repro.kernels import dispatch
from repro.models.sharding import constrain, logical_rules
from repro.optim.optimizers import apply_updates


def _stack_trees(trees):
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


def _unstack_tree(tree, i: int):
    return jax.tree.map(lambda leaf: leaf[i], tree)


def _where_tree(flag, new, old):
    return jax.tree.map(lambda a, b: jnp.where(flag, a, b), new, old)


class _Cohort:
    """One homogeneous architecture group: stacked state + jitted round ops."""

    def __init__(self, members: Sequence[Client], positions: Sequence[int],
                 mesh=None, mesh_axis: str = DEFAULT_CLIENT_AXIS,
                 wave_size: int = 0):
        self.members = list(members)
        self.positions = list(positions)     # index into the global client list
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        # 2-D (clients, model) mesh: weight matrices shard over this axis
        # too (repro.fed.mesh.stacked_state_shardings); None on a 1-D mesh
        self.model_axis = model_axis_name(mesh)
        if wave_size < 0:
            raise ValueError(f"wave_size must be >= 0, got {wave_size!r}")
        # wave streaming kicks in only when it would actually split the
        # cohort; a wave covering everyone IS the legacy single-wave path
        self._waved = 0 < wave_size < len(self.members)
        self.wave_size = wave_size if self._waved else len(self.members)
        # client axis of the *device-resident* stack after padding to a
        # multiple of the mesh size: the whole cohort in legacy mode, one
        # wave in streaming mode. Rows past the live members are
        # validity-gated dummy clients either way.
        self.c_pad = padded_size(self.wave_size, mesh)
        c0 = members[0]
        # arch_key only contracts identical (init, apply) structure; the
        # training hyperparameters below are baked into the cohort's jitted
        # fns once, so they must agree across members
        for c in members[1:]:
            if c.opt is not c0.opt:
                # Optimizer is a NamedTuple of closures — equivalence is
                # undecidable, so cohort members must share one instance
                raise ValueError(
                    f"cohort members {c0.cid} and {c.cid} share arch_key "
                    f"{c0.arch_key!r} but hold distinct Optimizer instances; "
                    "construct one optimizer and pass it to every member "
                    "(or give them distinct arch_keys)")
            if c.apply_fn != c0.apply_fn:
                # bound-method equality compares (__self__, __func__), so
                # clients sharing one model spec / MLP instance still pass
                raise ValueError(
                    f"cohort members {c0.cid} and {c.cid} share arch_key "
                    f"{c0.arch_key!r} but hold different apply_fns; the "
                    "cohort would silently run member 0's network for "
                    "everyone — share one model object per arch_key "
                    "(or give them distinct arch_keys)")
            for attr in ("temperature", "distill_loss", "num_classes"):
                if getattr(c, attr) != getattr(c0, attr):
                    raise ValueError(
                        f"cohort members {c0.cid} and {c.cid} share arch_key "
                        f"{c0.arch_key!r} but differ in {attr}: "
                        f"{getattr(c0, attr)!r} vs {getattr(c, attr)!r}")
            # compare *resolved* backends: None and "auto" (and "pallas" vs
            # "auto" on TPU) select the same kernels and must not split a
            # cohort
            if (dispatch.resolve(c.kernel_backend)
                    != dispatch.resolve(c0.kernel_backend)):
                raise ValueError(
                    f"cohort members {c0.cid} and {c.cid} share arch_key "
                    f"{c0.arch_key!r} but resolve to different kernel "
                    f"backends: {c0.kernel_backend!r} vs "
                    f"{c.kernel_backend!r}")
        self.apply_fn = c0.apply_fn
        self.opt = c0.opt
        self.temperature = c0.temperature
        self.loss_kind = c0.distill_loss
        self.num_classes = c0.num_classes
        # resolved once at construction and baked into the jitted phases —
        # flipping the ambient backend later never retraces a phase
        self.kernel_backend = dispatch.resolve(c0.kernel_backend)

        self.n = np.array([len(c.y) for c in members], np.int64)
        n_max = int(self.n.max())
        lead = len(members) if self._waved else self.c_pad
        x_pad = np.zeros((lead, n_max, *c0.x.shape[1:]),
                         np.asarray(c0.x).dtype)
        y_pad = np.zeros((lead, n_max), np.asarray(c0.y).dtype)
        m_pad = np.zeros((lead, n_max), np.float32)
        for i, c in enumerate(members):
            x_pad[i, : self.n[i]] = c.x
            y_pad[i, : self.n[i]] = c.y
            m_pad[i, : self.n[i]] = 1.0
        if self._waved:
            # streaming mode: the master copies live on host; each phase
            # stages wave_size rows at a time (see ``_stage``/``_waves``)
            self._hx, self._hy, self._hm = x_pad, y_pad, m_pad
            # stack in numpy — the full (C, ...) params/opt stack must
            # never touch the device, that's the whole point
            def _np_stack(*leaves):
                return np.stack([np.asarray(l) for l in leaves])
            self._hparams = jax.tree.map(_np_stack,
                                         *[c.params for c in members])
            self._hopt = jax.tree.map(_np_stack,
                                      *[c.opt_state for c in members])
            self.x = self.y = self.sample_mask = None
            self.params = self.opt_state = None
        else:
            self.x = self._put_c(x_pad)
            self.y = self._put_c(y_pad)
            self.sample_mask = self._put_c(m_pad)

            # dummy rows clone member 0's state; their steps never validate,
            # so the clone is inert ballast that keeps the client axis
            # mesh-divisible
            stand_ins = [members[0]] * (self.c_pad - len(members))
            self.params = self._put_state(
                _stack_trees([c.params for c in [*members, *stand_ins]]))
            self.opt_state = self._put_state(
                _stack_trees([c.opt_state for c in [*members, *stand_ins]]))

        # filter state (filled by learn_dres, or packed right away when the
        # clients arrive with already-learned DREs — e.g. the transient
        # engine run_round builds per call from a raw client list)
        self.filter_kind = "none"
        self._filter_state: Dict[str, jax.Array] = {}

        self._build_fns()
        self._pack_learned_filter_state()

    # ----------------------------------------------------- mesh placement
    @tracing.stage
    def _put_c(self, tree):
        """Place leaves with the leading client axis split over the mesh."""
        return shard_clients(jax.tree.map(jnp.asarray, tree),
                             self.mesh, self.mesh_axis)

    @tracing.stage
    def _put_rep(self, tree):
        """Place leaves replicated on every mesh device (shared inputs)."""
        return replicate(jax.tree.map(jnp.asarray, tree), self.mesh)

    def _put_state(self, tree):
        """Place a stacked params/opt-state pytree: client split on a 1-D
        mesh (bit-for-bit the historical ``_put_c``), per-leaf client ×
        model ``NamedSharding``s on a 2-D mesh."""
        return shard_stacked_state(jax.tree.map(jnp.asarray, tree),
                                   self.mesh, self.mesh_axis)

    def _pad_rows(self, arr, fill=None):
        """Pad per-member stacked rows (leading axis C) out to ``c_pad``.

        ``fill=None`` repeats the first row (values are discarded — dummy
        rows only exist to keep the axis mesh-divisible); a scalar ``fill``
        writes that value (e.g. 1.0 where a dummy row would divide by n)."""
        arr = jnp.asarray(arr)
        extra = self.c_pad - arr.shape[0]
        if extra == 0:
            return arr
        if fill is None:
            pad = jnp.tile(arr[:1], (extra,) + (1,) * (arr.ndim - 1))
        else:
            pad = jnp.full((extra, *arr.shape[1:]), fill, arr.dtype)
        return jnp.concatenate([arr, pad])

    # ----------------------------------------------------- wave streaming
    def _waves(self):
        """Yield the ``[lo, hi)`` member ranges of each wave (one full-range
        wave in legacy mode — callers never branch on ``_waved``)."""
        c = len(self.members)
        for lo in range(0, c, self.wave_size):
            yield lo, min(lo + self.wave_size, c)

    @tracing.stage
    def _stage(self, arr, lo: int, hi: int, fill=0):
        """Stage host rows ``[lo, hi)`` as a ``(c_pad, ...)`` device-ready
        array. Rows past ``hi - lo`` are dummy lanes: ``fill`` is a pad
        value (0 for data/plans, sentinels like -1/1.0/1e6 where a dummy
        row feeds a divide or an RBF kernel), or ``None`` to repeat row
        ``lo`` (params/opt-state ballast, values never read back)."""
        arr = np.asarray(arr)
        n = hi - lo
        if n == self.c_pad:
            return arr[lo:hi]
        if fill is None:
            pad = np.repeat(arr[lo:lo + 1], self.c_pad - n, axis=0)
            return np.concatenate([arr[lo:hi], pad])
        out = np.full((self.c_pad, *arr.shape[1:]), fill, arr.dtype)
        out[:n] = arr[lo:hi]
        return out

    @tracing.stage
    def _stage_state(self, lo: int, hi: int):
        """One wave's params/opt-state, staged host -> device."""
        pd = self._put_state(jax.tree.map(
            lambda leaf: self._stage(leaf, lo, hi, fill=None), self._hparams))
        od = self._put_state(jax.tree.map(
            lambda leaf: self._stage(leaf, lo, hi, fill=None), self._hopt))
        return pd, od

    def _write_state(self, params_dev, opt_dev, lo: int, hi: int) -> None:
        """Stream one wave's updated params/opt-state back to the host
        masters (dummy rows dropped); the device buffers die with their
        last reference when the next wave stages."""
        n = hi - lo
        params_h, opt_h = fetch((params_dev, opt_dev))
        jax.tree.map(lambda h, d: h.__setitem__(slice(lo, hi), d[:n]),
                     self._hparams, params_h)
        jax.tree.map(lambda h, d: h.__setitem__(slice(lo, hi), d[:n]),
                     self._hopt, opt_h)

    def _ctx(self):
        """Logical-rules scope for every jitted call: inside it the logical
        ``"clients"`` axis resolves to this cohort's mesh axis, so traces
        pin outputs to the client mesh and never pick up an outer
        launcher's model-parallel rules. On a 2-D (clients, model) mesh the
        model-side logical axes (heads/ff/vocab/experts) resolve to the
        model axis too, so ``constrain`` calls inside transformer apply_fns
        keep activations in the Megatron layout (replicated residual
        stream, model-sharded heads); on a 1-D mesh those rules resolve to
        nothing and the trace is bit-for-bit the historical one."""
        if self.mesh is None:
            return logical_rules(None, None)
        rules = {**MODEL_LOGICAL_RULES, "clients": self.mesh_axis}
        return logical_rules(rules, self.mesh)

    # ------------------------------------------------------------- jitted ops
    def _build_fns(self):
        apply_fn, opt = self.apply_fn, self.opt
        temp, loss_kind, k_cls = self.temperature, self.loss_kind, self.num_classes
        backend = self.kernel_backend

        # per-leaf output shardings for the training-state outputs: on a
        # 2-D mesh constraining params to P("clients") alone would undo
        # the model split every step (and re-replicate each client's
        # weights across the model axis — exactly the memory the 2-D mesh
        # exists to save), so state outputs pin to the same per-leaf specs
        # their inputs were placed with. Shapes come from whichever stack
        # exists (device stack, or the host masters in waved mode) — only
        # the non-leading dims matter for the specs and they are equal.
        if self.model_axis is not None:
            p_like = self.params if not self._waved else self._hparams
            o_like = self._hopt if self._waved else self.opt_state
            p_sh = stacked_state_shardings(p_like, self.mesh, self.mesh_axis)
            o_sh = stacked_state_shardings(o_like, self.mesh, self.mesh_axis)
        else:
            p_sh = o_sh = None

        # the client vmap maps onto the mesh's client axis, so sharding
        # constraints and kernel shard_maps inside a phase keep each
        # client's work on the device that holds it
        spmd_axis = None if self.mesh is None else self.mesh_axis

        def vmap(fn, in_axes=0):
            return jax.vmap(fn, in_axes=in_axes, spmd_axis_name=spmd_axis)

        def pin_clients(tree):
            return jax.tree.map(lambda leaf: constrain(leaf, "clients"),
                                tree)

        def pin_state(tree, shardings):
            if shardings is None:
                return pin_clients(tree)
            return jax.tree.map(
                lambda leaf, sh: jax.lax.with_sharding_constraint(leaf, sh),
                tree, shardings)

        def pinned(fn, name: str, state_out: bool = False):
            """jit(fn) with every output pinned to the client axis (no-op
            when traced without a mesh in scope — see ``_ctx``), compiled
            as ``jit_cohort_<name>`` and launched in a ``cohort.launch``
            span. ``state_out`` marks fns returning (params, opt_state,
            losses): their state outputs take the per-leaf client × model
            specs."""
            def wrapped(*args):
                out = fn(*args)
                if state_out:
                    params, opt_state, losses = out
                    return (pin_state(params, p_sh),
                            pin_state(opt_state, o_sh),
                            pin_clients(losses))
                return pin_clients(out)
            wrapped.__name__ = wrapped.__qualname__ = "cohort_" + name
            return tracing.launched(jax.jit(wrapped))

        def scan_steps(batch_loss):
            """Shared scan skeleton: grad step + validity gating; the three
            training modes differ only in how (idx-batch, weights) become a
            loss. ``batch_loss(params, ib, wb) -> scalar``."""
            def chunk(params, opt_state, idx, w, valid):
                def step(carry, inp):
                    p, o = carry
                    ib, wb, v = inp
                    loss, grads = jax.value_and_grad(batch_loss)(p, ib, wb)
                    upd, o2 = opt.update(grads, o, p)
                    p2 = apply_updates(p, upd)
                    return (_where_tree(v, p2, p), _where_tree(v, o2, o)), loss

                (params, opt_state), losses = jax.lax.scan(
                    step, (params, opt_state), (idx, w, valid))
                return params, opt_state, losses
            return chunk

        def train_chunk(params, opt_state, x, y, idx, w, valid):
            """One client's scan over (steps, batch) index/weight plans."""
            def loss_fn(pp, ib, wb):
                logits = apply_fn(pp, jnp.take(x, ib, axis=0), True)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                yb = jnp.take(y, ib, axis=0)
                ll = jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]
                return -jnp.sum(ll * wb) / jnp.maximum(jnp.sum(wb), 1.0)

            return scan_steps(loss_fn)(params, opt_state, idx, w, valid)

        def kd_loss(logits, teacher, wb):
            if loss_kind == "mse":
                return D.kd_mse_loss(logits, teacher, wb)
            return D.kd_kl_loss(logits, teacher, temp, wb, backend=backend)

        def distill_chunk(params, opt_state, px, teacher, idx, w, valid):
            """Shared proxy batch; per-client weights fold in teacher validity."""
            def loss_fn(pp, ib, wb):
                xb = jnp.take(px, ib, axis=0)
                tb = jnp.take(teacher, ib, axis=0)
                return kd_loss(apply_fn(pp, xb, True), tb, wb)

            return scan_steps(loss_fn)(params, opt_state, idx, w, valid)

        def distill_private_chunk(params, opt_state, x, y, tbc, vbc,
                                  idx, w, valid):
            """Data-free (FKD/PLS): teacher gathered per label from tbc."""
            def loss_fn(pp, ib, wb):
                xb = jnp.take(x, ib, axis=0)
                yb = jnp.take(y, ib, axis=0)
                return kd_loss(apply_fn(pp, xb, True), tbc[yb], wb * vbc[yb])

            return scan_steps(loss_fn)(params, opt_state, idx, w, valid)

        def classwise_chunk(params, x, y, m):
            logits = apply_fn(params, x, False).astype(jnp.float32)
            oh = jax.nn.one_hot(y, k_cls, dtype=jnp.float32) * m[:, None]
            sums = oh.T @ logits
            cnt = jnp.sum(oh, axis=0)
            return sums / jnp.maximum(cnt[:, None], 1.0), cnt

        def kmeans_mask_chunk(cents, thr, cid, pxf, owner):
            d = min_dist_to_centroids(pxf, cents)
            return (owner == cid) | (d <= thr)

        def eval_chunk(params, xb, yb, mb):
            """Fixed-shape eval: (nb, B, ...) batches, padded tail masked by
            ``mb`` — one compile regardless of ``len(y_test) % B``."""
            def body(correct, inp):
                x1, y1, m1 = inp
                pred = jnp.argmax(apply_fn(params, x1, False), -1)
                return correct + jnp.sum((pred == y1) * m1), None
            correct, _ = jax.lax.scan(body, jnp.zeros((), jnp.int32),
                                      (xb, yb, mb))
            return correct

        self._train = pinned(vmap(train_chunk), "train", state_out=True)
        self._distill = pinned(
            vmap(distill_chunk, in_axes=(0, 0, None, None, 0, 0, 0)),
            "distill", state_out=True)
        self._distill_private = pinned(
            vmap(distill_private_chunk,
                     in_axes=(0, 0, 0, 0, None, None, 0, 0, 0)),
            "distill_private", state_out=True)
        self._predict = pinned(
            vmap(lambda p, xb: apply_fn(p, xb, False), in_axes=(0, None)),
            "predict")
        self._eval = pinned(
            vmap(eval_chunk, in_axes=(0, None, None, None)), "eval")
        self._classwise = pinned(vmap(classwise_chunk), "classwise")
        self._kmeans_masks = pinned(
            vmap(kmeans_mask_chunk, in_axes=(0, 0, 0, None, None)),
            "kmeans_masks")

        def kulsif_mask_chunk(alpha, aux, priv, n, thr, cid, sigma, lam,
                              pxf, owner):
            # dispatched like KuLSIFDRE.estimate — under vmap the Pallas
            # path batches through the kernel's grid (one trace per cohort)
            k_ta = dispatch.rbf_matrix(pxf, aux, sigma, backend=backend)
            k_tp = dispatch.rbf_matrix(pxf, priv, sigma, backend=backend)
            r = k_ta @ alpha + jnp.sum(k_tp, axis=1) / (lam * n)
            return (owner == cid) | (r >= thr)

        self._kulsif_masks = pinned(
            vmap(kulsif_mask_chunk,
                     in_axes=(0, 0, 0, 0, 0, 0, None, None, None, None)),
            "kulsif_masks")

    # -------------------------------------------------------------- DRE learn
    @staticmethod
    def _check_kulsif_uniform(dres) -> None:
        # sigma/lam/kernel_backend are baked into the vmapped ratio
        # evaluation once, so they must agree across members (thresholds
        # are per-client); backends compare *resolved* — None and "auto"
        # select the same kernels
        for d in dres[1:]:
            if ((d.sigma, d.lam, dispatch.resolve(d.kernel_backend))
                    != (dres[0].sigma, dres[0].lam,
                        dispatch.resolve(dres[0].kernel_backend))):
                raise ValueError(
                    f"cohort KuLSIF DREs disagree on (sigma, lam, "
                    f"kernel_backend): "
                    f"{(dres[0].sigma, dres[0].lam, dres[0].kernel_backend)}"
                    f" vs {(d.sigma, d.lam, d.kernel_backend)}; give such "
                    "clients distinct arch_keys")

    def learn_dres(self, key) -> None:
        if all(c.dre is None for c in self.members):
            return
        keys = [jax.random.fold_in(key, pos) for pos in self.positions]
        dres = [c.dre for c in self.members]

        if all(isinstance(d, KMeansDRE) for d in dres):
            ks = {d.num_centroids for d in dres}
            # the vmapped fit bakes ONE (threshold, calibration_q, max_iter,
            # kernel_backend) into the whole batch, so every fit
            # hyperparameter must agree — anything less silently
            # mis-calibrates the odd member out
            # thresholds may be device scalars after a previous learn()
            # (unhashable) — compare by value; backends compare *resolved*
            # (None and "auto" mean the same thing and must not drop the
            # cohort to the slow per-client fit loop)
            thrs_cfg = {None if d.threshold is None else float(d.threshold)
                        for d in dres}
            fit_backends = {dispatch.resolve(d.kernel_backend) for d in dres}
            uniform = (len(set(self.n)) == 1 and len(ks) == 1
                       and len(thrs_cfg) == 1
                       and len({d.calibration_q for d in dres}) == 1
                       and len({d.max_iter for d in dres}) == 1
                       and len(fit_backends) == 1)
            if uniform:
                # the vmapped learn path: every filter fit in one call per
                # wave, device-parallel over the (padded) client axis;
                # dummy rows fit on all-zero features and are never read
                # back. The fit is per-client math, so waving it changes
                # nothing but peak memory.
                k = ks.pop()
                backend = fit_backends.pop()
                keys_h = np.stack([np.asarray(kk) for kk in keys])
                n0 = int(self.n[0])
                C = len(self.members)
                cents_host = None
                thrs_host = np.zeros((C,), np.float32)
                for lo, hi in self._waves():
                    if self._waved:
                        feats = self._put_c(self._stage(
                            self._hx.reshape(C, n0, -1), lo, hi))
                        keys_w = self._put_c(self._stage(keys_h, lo, hi,
                                                         fill=None))
                    else:
                        feats = self.x.reshape(self.c_pad, n0, -1)
                        keys_w = self._put_c(self._pad_rows(jnp.stack(keys)))
                    with self._ctx():
                        res = kmeans_fit_batched(keys_w, feats, k,
                                                 dres[0].max_iter,
                                                 backend=backend)
                        if dres[0].threshold is None:
                            dmin = jax.vmap(min_dist_to_centroids)(
                                feats, res.centroids)
                            thrs = jnp.quantile(dmin, dres[0].calibration_q,
                                                axis=1)
                        else:
                            thrs = jnp.full((self.c_pad,), dres[0].threshold)
                    # pull centroids/thresholds to host in one gather each:
                    # rows of a mesh-sharded fit live on different devices,
                    # and jnp.stack in the packing step rejects mixed
                    # committed devices (one np.asarray, not C per-scalar
                    # float() syncs)
                    cw = np.asarray(res.centroids)[: hi - lo]
                    if cents_host is None:
                        cents_host = np.zeros((C, *cw.shape[1:]), cw.dtype)
                    cents_host[lo:hi] = cw
                    thrs_host[lo:hi] = np.asarray(thrs)[: hi - lo]
                for i, c in enumerate(self.members):
                    c.dre = dataclasses.replace(
                        c.dre, centroids=jnp.asarray(cents_host[i]),
                        threshold=jnp.float32(thrs_host[i]))
            else:
                for c, kk in zip(self.members, keys):
                    c.learn_dre(kk)
        else:
            # per-client learn (learn_dre no-ops on dre=None); KuLSIF
            # uniformity must fail before any state is mutated
            if all(isinstance(d, KuLSIFDRE) for d in dres):
                self._check_kulsif_uniform(dres)
            for c, kk in zip(self.members, keys):
                c.learn_dre(kk)
        self._pack_filter_state()

    def _pack_filter_state(self) -> None:
        """Stack the members' *learned* DREs into vmappable filter state.

        Legacy mode parks the stacked state on device (padded to
        ``c_pad``); waved mode keeps it host-side numpy with the full
        member axis and ``filter_masks`` stages one wave at a time."""
        dres = [c.dre for c in self.members]
        if all(isinstance(d, KMeansDRE) for d in dres):
            kmax = max(c.dre.centroids.shape[0] for c in self.members)
            cents = []
            for c in self.members:
                cc = np.asarray(c.dre.centroids)
                if cc.shape[0] < kmax:  # pad by repeating the first centroid:
                    pad = np.tile(cc[:1], (kmax - cc.shape[0], 1))
                    cc = np.concatenate([cc, pad])  # min-distance unchanged
                cents.append(cc)
            thrs = np.asarray([c.dre.threshold for c in self.members],
                              np.float32)
            self.filter_kind = "kmeans"
            if self._waved:
                self._filter_state = {"centroids": np.stack(cents),
                                      "thresholds": thrs}
                return
            self._filter_state = {
                "centroids": self._put_c(self._pad_rows(
                    jnp.stack([jnp.asarray(cc) for cc in cents]))),
                "thresholds": self._put_c(self._pad_rows(
                    jnp.asarray(thrs))),
            }
        elif all(isinstance(d, KuLSIFDRE) for d in dres):
            self._check_kulsif_uniform(dres)
            n_max = int(self.n.max())
            d = self.members[0].dre.private.shape[1]
            # pad private sets with a far-away sentinel: its RBF kernel mass
            # underflows to exactly 0, so padded rows contribute nothing —
            # dummy-client rows are entirely sentinel for the same reason.
            # The underflow needs (1e6)^2/(2 sigma^2) >> 88 (float32), so
            # refuse sigmas anywhere near that scale when padding exists
            # (waved cohorts always pad: the last wave is rarely full)
            padded = (self._waved or self.c_pad > len(self.members)
                      or int(self.n.min()) < n_max)
            if padded and dres[0].sigma > 1e4:
                raise ValueError(
                    f"KuLSIF sentinel padding requires sigma <= 1e4 so the "
                    f"pad rows' RBF mass underflows to exactly 0; got "
                    f"sigma={dres[0].sigma!r} with a padded cohort — use "
                    "equal private-set sizes and a mesh-divisible client "
                    "count, or give such clients distinct arch_keys")
            lead = len(self.members) if self._waved else self.c_pad
            priv = np.full((lead, n_max, d), 1e6, np.float32)
            for i, c in enumerate(self.members):
                priv[i, : self.n[i]] = np.asarray(c.dre.private)
            self.filter_kind = "kulsif"
            if self._waved:
                self._filter_state = {
                    "alpha": np.stack([np.asarray(c.dre.alpha)
                                       for c in self.members]),
                    "aux": np.stack([np.asarray(c.dre.aux)
                                     for c in self.members]),
                    "private": priv,
                    "n": np.asarray(self.n, np.float32),
                    "thresholds": np.asarray(
                        [c.dre.threshold for c in self.members], np.float32),
                    "sigma": float(dres[0].sigma),
                    "lam": float(dres[0].lam),
                }
                return
            self._filter_state = {
                "alpha": self._put_c(self._pad_rows(
                    jnp.stack([jnp.asarray(c.dre.alpha)
                               for c in self.members]))),
                "aux": self._put_c(self._pad_rows(
                    jnp.stack([jnp.asarray(c.dre.aux)
                               for c in self.members]))),
                "private": self._put_c(priv),
                # dummy rows divide by n — pad with 1.0, never 0
                "n": self._put_c(self._pad_rows(
                    jnp.asarray(self.n, jnp.float32), fill=1.0)),
                "thresholds": self._put_c(self._pad_rows(
                    jnp.asarray([c.dre.threshold for c in self.members],
                                jnp.float32))),
                "sigma": jnp.float32(dres[0].sigma),
                "lam": jnp.float32(dres[0].lam),
            }
        else:  # unknown or mixed estimators: per-client mask calls
            self.filter_kind = "loop"

    def _pack_learned_filter_state(self) -> None:
        """Adopt DREs the clients *already* learned (a transient engine —
        run_round builds one per call from a raw client list — must filter
        exactly like the long-lived engine whose learn_dres ran)."""
        d0 = self.members[0].dre
        if isinstance(d0, KMeansDRE):
            learned = all(isinstance(c.dre, KMeansDRE)
                          and c.dre.centroids is not None
                          for c in self.members)
        elif isinstance(d0, KuLSIFDRE):
            learned = all(isinstance(c.dre, KuLSIFDRE)
                          and c.dre.alpha is not None
                          for c in self.members)
        elif d0 is not None:
            # unknown estimator: "learned" is undecidable here, so take the
            # per-client mask fallback unconditionally — exactly what the
            # loop engine does with the same clients (unlearned ones fail
            # identically there)
            self.filter_kind = "loop"
            return
        else:
            learned = False  # no DRE: nothing to adopt
        if learned:
            self._pack_filter_state()

    # ----------------------------------------------------------- round phases
    @tracing.spanned("cohort.plan")
    def _plan(self, draw_n: int, epochs: int, batch_size: int,
              weight=None, part=None
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Draw per-client epoch permutations (advancing each client's rng
        exactly as the loop engine would) and pack them into fixed arrays.

        ``part`` (len(members),) bool marks this round's participants:
        sampled-out members draw no permutation (their rng stream stays in
        lockstep with the loop engine, which skips them entirely) and keep
        all-False step validity — the same ``_where_tree`` no-op gating
        that freezes dummy padding clients. The plan arrays keep their
        shapes either way, so a changing subset never retraces a phase.

        Drawing members are packed by size: members of one size ``n`` are
        shuffled in place into one ``(G, epochs, n)`` buffer
        (``rng.shuffle`` of ``arange(n)`` is the stream ``permutation(n)``
        draws), their ``idx`` rows are cut from it in one reshape, and
        ``w``/``valid`` — which depend on ``n`` alone — come from one
        ``padded_epoch_plan`` call per size. Each group counts one
        ``engine.plan_groups``.
        """
        C = len(self.members)
        if draw_n >= 0:
            ns = [draw_n] * C          # shared proxy set
        else:
            ns = [int(v) for v in self.n]
        steps = max(steps_per_epoch(n, batch_size) for n in ns) * epochs
        # dummy-client rows [C:lead] stay all-zero / valid=False: every one
        # of their steps is a no-op under the _where_tree gating (waved
        # mode plans the full member axis and stages per wave, so rng
        # draws happen exactly once per member regardless of wave count)
        lead = C if self._waved else self.c_pad
        idx = np.zeros((lead, steps, batch_size), np.int32)
        w = np.zeros((lead, steps, batch_size), np.float32)
        valid = np.zeros((lead, steps), bool)
        groups: Dict[int, List[int]] = {}
        for i in range(C):
            if part is None or part[i]:  # sampled-out members draw nothing
                groups.setdefault(ns[i], []).append(i)
        for n, rows in groups.items():
            # intp rows: numpy shuffles pointer-sized items fastest
            buf = np.empty((len(rows), epochs, n), np.intp)
            buf[...] = np.arange(n)
            for j, i in enumerate(rows):
                rng = self.members[i].rng
                for e in range(epochs):
                    rng.shuffle(buf[j, e])
            s = steps_per_epoch(n, batch_size)
            width = min(n, batch_size)
            idx[rows, : epochs * s, :width] = buf[:, :, : s * width].reshape(
                len(rows), epochs * s, width)
            # w/valid depend on n alone: one member's plan stands for all
            _, w[rows], valid[rows] = padded_epoch_plan(
                list(buf[0]), batch_size, steps)
        tracing.add("engine.plan_groups", len(groups))
        if weight is not None:
            w = w * np.asarray(weight, np.float32)[idx]
        return idx, w, valid

    def _mean_losses(self, losses, valid) -> List[float]:
        losses = np.asarray(losses, np.float64)
        valid = np.asarray(valid, np.float64)
        cnt = valid.sum(axis=1)
        tot = (losses * valid).sum(axis=1)
        return [float(t / c) if c else 0.0 for t, c in zip(tot, cnt)]

    def local_train(self, epochs: int, batch_size: int,
                    part=None) -> List[float]:
        idx, w, valid = self._plan(-1, epochs, batch_size, part=part)
        C = len(self.members)
        if not self._waved:
            with self._ctx():
                self.params, self.opt_state, losses = self._train(
                    self.params, self.opt_state, self.x, self.y,
                    self._put_c(idx), self._put_c(w), self._put_c(valid))
            return self._mean_losses(fetch(losses)[:C], valid[:C])
        losses_h = np.zeros((C, valid.shape[1]), np.float32)
        for lo, hi in self._waves():
            pd, od = self._stage_state(lo, hi)
            with self._ctx():
                pd, od, losses = self._train(
                    pd, od,
                    self._put_c(self._stage(self._hx, lo, hi)),
                    self._put_c(self._stage(self._hy, lo, hi)),
                    self._put_c(self._stage(idx, lo, hi)),
                    self._put_c(self._stage(w, lo, hi)),
                    self._put_c(self._stage(valid, lo, hi)))
            self._write_state(pd, od, lo, hi)
            losses_h[lo:hi] = fetch(losses)[: hi - lo]
        return self._mean_losses(losses_h, valid[:C])

    def distill(self, px, teacher, weight, epochs: int,
                batch_size: int, part=None) -> List[float]:
        idx, w, valid = self._plan(len(px), epochs, batch_size, weight=weight,
                                   part=part)
        C = len(self.members)
        if not self._waved:
            with self._ctx():
                self.params, self.opt_state, losses = self._distill(
                    self.params, self.opt_state,
                    self._put_rep(px), self._put_rep(teacher),
                    self._put_c(idx), self._put_c(w), self._put_c(valid))
            return self._mean_losses(fetch(losses)[:C], valid[:C])
        pxd, td = self._put_rep(px), self._put_rep(teacher)  # shared by waves
        losses_h = np.zeros((C, valid.shape[1]), np.float32)
        for lo, hi in self._waves():
            pd, od = self._stage_state(lo, hi)
            with self._ctx():
                pd, od, losses = self._distill(
                    pd, od, pxd, td,
                    self._put_c(self._stage(idx, lo, hi)),
                    self._put_c(self._stage(w, lo, hi)),
                    self._put_c(self._stage(valid, lo, hi)))
            self._write_state(pd, od, lo, hi)
            losses_h[lo:hi] = fetch(losses)[: hi - lo]
        return self._mean_losses(losses_h, valid[:C])

    def distill_private(self, teacher_by_class, valid_by_class, epochs: int,
                        batch_size: int, part=None) -> List[float]:
        idx, w, valid = self._plan(-1, epochs, batch_size, part=part)
        C = len(self.members)
        if not self._waved:
            with self._ctx():
                self.params, self.opt_state, losses = self._distill_private(
                    self.params, self.opt_state, self.x, self.y,
                    self._put_rep(teacher_by_class),
                    self._put_rep(np.asarray(valid_by_class, np.float32)),
                    self._put_c(idx), self._put_c(w), self._put_c(valid))
            return self._mean_losses(fetch(losses)[:C], valid[:C])
        td = self._put_rep(teacher_by_class)
        vd = self._put_rep(np.asarray(valid_by_class, np.float32))
        losses_h = np.zeros((C, valid.shape[1]), np.float32)
        for lo, hi in self._waves():
            pd, od = self._stage_state(lo, hi)
            with self._ctx():
                pd, od, losses = self._distill_private(
                    pd, od,
                    self._put_c(self._stage(self._hx, lo, hi)),
                    self._put_c(self._stage(self._hy, lo, hi)),
                    td, vd,
                    self._put_c(self._stage(idx, lo, hi)),
                    self._put_c(self._stage(w, lo, hi)),
                    self._put_c(self._stage(valid, lo, hi)))
            self._write_state(pd, od, lo, hi)
            losses_h[lo:hi] = fetch(losses)[: hi - lo]
        return self._mean_losses(losses_h, valid[:C])

    def classwise_means(self, part=None):
        if not self._waved:
            with self._ctx():
                means, counts = self._classwise(self.params, self.x, self.y,
                                                self.sample_mask)
            means, counts = fetch((means, counts))
        else:
            C = len(self.members)
            means = np.zeros((C, self.num_classes, self.num_classes),
                             np.float32)
            counts = np.zeros((C, self.num_classes), np.float32)
            for lo, hi in self._waves():
                pd, _ = self._stage_state(lo, hi)
                with self._ctx():
                    m_w, c_w = self._classwise(
                        pd,
                        self._put_c(self._stage(self._hx, lo, hi)),
                        self._put_c(self._stage(self._hy, lo, hi)),
                        self._put_c(self._stage(self._hm, lo, hi)))
                m_w, c_w = fetch((m_w, c_w))
                means[lo:hi] = m_w[: hi - lo]
                counts[lo:hi] = c_w[: hi - lo]
        if part is not None:
            # sampled-out members report nothing (zero counts drop them
            # from the classwise fuse exactly like the loop engine's skip)
            means, counts = means.copy(), counts.copy()
            means[~np.asarray(part, bool)] = 0.0
            counts[~np.asarray(part, bool)] = 0.0
        return [(means[i], counts[i]) for i in range(len(self.members))]

    def proxy_logits(self, px, part=None) -> np.ndarray:
        if not self._waved:
            with self._ctx():
                out = self._predict(self.params, self._put_rep(px))
            out = fetch(out)[: len(self.members)]
        else:
            C = len(self.members)
            pxd = self._put_rep(px)
            out = np.zeros((C, len(px), self.num_classes), np.float32)
            for lo, hi in self._waves():
                pd, _ = self._stage_state(lo, hi)
                with self._ctx():
                    o_w = self._predict(pd, pxd)
                out[lo:hi] = fetch(o_w)[: hi - lo]
        if part is not None:
            out = out.copy()
            out[~np.asarray(part, bool)] = 0.0
        return out

    def filter_masks(self, px, powner, part=None) -> np.ndarray:
        t = len(px)
        part = None if part is None else np.asarray(part, bool)

        def gated(masks):
            if part is not None:
                masks = masks.copy()
                masks[~part] = False     # sampled-out clients report nothing
            return masks

        if self.filter_kind == "none" \
                and all(c.dre is None for c in self.members):
            return gated(np.ones((len(self.members), t), bool))
        if self.filter_kind in ("none", "loop"):
            # "none" with any DRE present means no state was learned or
            # packed (e.g. a transient engine over unlearned clients, or a
            # mixed some-have-DREs cohort): defer to the per-client path so
            # it behaves exactly like the loop engine — including failing
            # loudly on unlearned estimators instead of silently returning
            # all-True masks (sampled-out members are skipped, again like
            # the loop engine)
            return np.stack([
                fetch(c.filter_mask(px, powner).mask)
                if part is None or part[i] else np.zeros((t,), bool)
                for i, c in enumerate(self.members)])
        pxf = self._put_rep(np.asarray(px).reshape(t, -1))
        owner = self._put_rep(powner)
        st = self._filter_state
        if not self._waved:
            # dummy rows get cid -1 (never an owner), masks are sliced off
            cids = self._put_c(self._pad_rows(
                jnp.asarray([c.cid for c in self.members]), fill=-1))
            with self._ctx():
                if self.filter_kind == "kmeans":
                    masks = self._kmeans_masks(st["centroids"],
                                               st["thresholds"],
                                               cids, pxf, owner)
                else:
                    masks = self._kulsif_masks(st["alpha"], st["aux"],
                                               st["private"], st["n"],
                                               st["thresholds"], cids,
                                               st["sigma"], st["lam"],
                                               pxf, owner)
            return gated(fetch(masks)[: len(self.members)])
        # waved: filter state lives host-side, staged one wave at a time.
        # Pad fills keep dummy lanes inert where they feed real math: cid
        # -1 never owns, kulsif n=1.0 never divides by zero, private rows
        # ride the existing 1e6 far-away sentinel.
        C = len(self.members)
        cids_h = np.asarray([c.cid for c in self.members])
        out = np.zeros((C, t), bool)
        for lo, hi in self._waves():
            cids = self._put_c(self._stage(cids_h, lo, hi, fill=-1))
            with self._ctx():
                if self.filter_kind == "kmeans":
                    masks = self._kmeans_masks(
                        self._put_c(self._stage(st["centroids"], lo, hi)),
                        self._put_c(self._stage(st["thresholds"], lo, hi)),
                        cids, pxf, owner)
                else:
                    masks = self._kulsif_masks(
                        self._put_c(self._stage(st["alpha"], lo, hi)),
                        self._put_c(self._stage(st["aux"], lo, hi)),
                        self._put_c(self._stage(st["private"], lo, hi,
                                                fill=np.float32(1e6))),
                        self._put_c(self._stage(st["n"], lo, hi,
                                                fill=np.float32(1.0))),
                        self._put_c(self._stage(st["thresholds"], lo, hi)),
                        cids, jnp.float32(st["sigma"]),
                        jnp.float32(st["lam"]), pxf, owner)
            out[lo:hi] = fetch(masks)[: hi - lo]
        return gated(out)

    def evaluate(self, x_test, y_test, batch_size: int = 512) -> List[float]:
        """Masked fixed-shape eval: the tail batch is padded to ``batch_size``
        instead of sliced ragged (which recompiled ``_predict`` for every
        distinct ``n % batch_size`` tail), and the whole pass — scan over
        batches, vmap over clients — is one compiled, device-parallel call."""
        x = np.asarray(x_test)
        y = np.asarray(y_test)
        n = len(y)
        nb = max(1, -(-n // batch_size))
        pad = nb * batch_size - n
        if pad:
            x = np.concatenate([x, np.zeros((pad, *x.shape[1:]), x.dtype)])
            y = np.concatenate([y, np.zeros((pad,), y.dtype)])
        m = np.zeros((nb * batch_size,), np.int32)
        m[:n] = 1
        xb = self._put_rep(x.reshape(nb, batch_size, *x.shape[1:]))
        yb = self._put_rep(y.reshape(nb, batch_size))
        mb = self._put_rep(m.reshape(nb, batch_size))
        if not self._waved:
            with self._ctx():
                correct = self._eval(self.params, xb, yb, mb)
            return [int(c) / n
                    for c in fetch(correct)[: len(self.members)]]
        C = len(self.members)
        correct = np.zeros((C,), np.int64)
        for lo, hi in self._waves():
            pd, _ = self._stage_state(lo, hi)
            with self._ctx():
                c_w = self._eval(pd, xb, yb, mb)
            correct[lo:hi] = fetch(c_w)[: hi - lo]
        return [int(c) / n for c in correct]

    def sync_to_clients(self) -> None:
        """Write stacked params/opt-state back onto the Client objects."""
        if self._waved:
            # the masters already live on host — hand back per-client views
            for i, c in enumerate(self.members):
                c.params = jax.tree.map(lambda l: jnp.asarray(l[i]),
                                        self._hparams)
                c.opt_state = jax.tree.map(lambda l: jnp.asarray(l[i]),
                                           self._hopt)
            return
        params, opt_state = self.params, self.opt_state
        if self.mesh is not None:
            # gather through host first: rows of a mesh-sharded stack live on
            # different devices, but clients expect default-device arrays
            params = jax.tree.map(lambda leaf: jnp.asarray(np.asarray(leaf)),
                                  params)
            opt_state = jax.tree.map(
                lambda leaf: jnp.asarray(np.asarray(leaf)), opt_state)
        for i, c in enumerate(self.members):
            c.params = _unstack_tree(params, i)
            c.opt_state = _unstack_tree(opt_state, i)

    def adopt_member_state(self) -> None:
        """Re-stage the stacked params/opt-state from the member ``Client``
        objects — the inverse of ``sync_to_clients``, used on checkpoint
        restore (the engine checkpoint format is per-client, so a restore
        writes the clients first and re-stacks here). Replays the exact
        construction-time staging: numpy host masters in waved mode,
        mesh-placed padded device stacks otherwise."""
        members = self.members
        if self._waved:
            def _np_stack(*leaves):
                return np.stack([np.asarray(l) for l in leaves])
            self._hparams = jax.tree.map(_np_stack,
                                         *[c.params for c in members])
            self._hopt = jax.tree.map(_np_stack,
                                      *[c.opt_state for c in members])
            return
        stand_ins = [members[0]] * (self.c_pad - len(members))
        self.params = self._put_state(
            _stack_trees([c.params for c in [*members, *stand_ins]]))
        self.opt_state = self._put_state(
            _stack_trees([c.opt_state for c in [*members, *stand_ins]]))


class CohortEngine:
    """Engine over architecture-grouped cohorts; same contract as LoopEngine.

    The ``Client`` objects remain the source of private data, DRE config and
    rng streams, but their params/opt-state live *stacked on device* for the
    engine's lifetime; call ``sync_to_clients()`` before reading them back
    (e.g. for checkpointing).

    ``mesh`` (``repro.fed.mesh.build_client_mesh``) shards every cohort's
    client axis across a 1-D device mesh; ``None`` keeps the single-device
    semantics. Each cohort pads its own client axis to a mesh-size multiple
    with validity-gated dummy clients, so any population shape works.

    ``wave_size`` streams each cohort's client axis through the device in
    fixed-size waves (see the module docstring); 0 keeps the whole axis
    device-resident. Composes with ``mesh`` — each wave is padded to a
    mesh multiple and sharded.
    """

    def __init__(self, clients: Sequence[Client], mesh=None,
                 mesh_axis: str = DEFAULT_CLIENT_AXIS, wave_size: int = 0):
        self.clients = list(clients)
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.wave_size = wave_size
        groups: Dict[object, Tuple[List[Client], List[int]]] = {}
        for pos, c in enumerate(self.clients):
            key = c.arch_key if c.arch_key is not None else ("solo", pos)
            members, positions = groups.setdefault(key, ([], []))
            members.append(c)
            positions.append(pos)
        self.cohorts = [_Cohort(m, p, mesh=mesh, mesh_axis=mesh_axis,
                                wave_size=wave_size)
                        for m, p in groups.values()]

    @property
    def num_clients(self) -> int:
        return len(self.clients)

    def _scatter(self, per_cohort_lists) -> List:
        out = [None] * len(self.clients)
        for cohort, values in zip(self.cohorts, per_cohort_lists):
            for pos, v in zip(cohort.positions, values):
                out[pos] = v
        return out

    def _part_for(self, cohort, participants):
        """Slice a global participation mask down to one cohort's members
        (the cohort composes it with its own dummy-padding validity)."""
        if participants is None:
            return None
        part = np.asarray(participants, bool)
        if part.shape != (len(self.clients),):
            raise ValueError(
                f"participation mask shape {part.shape} != "
                f"({len(self.clients)},)")
        return part[cohort.positions]

    def learn_dres(self, key) -> None:
        for cohort in self.cohorts:
            cohort.learn_dres(key)

    # ------------------------------------------------ per-cohort entry points
    # The scheduler drives every client phase one _Cohort at a time: a
    # serial phase node walks every cohort in order, a concurrent-cohort
    # node (cfg.concurrent_cohorts=True) covers one. Each call returns
    # values aligned to that cohort's client positions
    # (``cohort_positions()[ci]``); the scheduler scatters them back into
    # fleet-length structures. LoopEngine implements the same interface
    # with the same grouping rule, so loop == cohort parity holds
    # node-for-node.

    def cohort_positions(self) -> List[np.ndarray]:
        return [np.asarray(c.positions, int) for c in self.cohorts]

    def cohort_local_train(self, ci: int, epochs: int, batch_size: int,
                           participants=None) -> List[float]:
        c = self.cohorts[ci]
        return c.local_train(epochs, batch_size,
                             part=self._part_for(c, participants))

    def cohort_classwise_report(self, ci: int, participants=None):
        c = self.cohorts[ci]
        return c.classwise_means(part=self._part_for(c, participants))

    def cohort_report(self, ci: int, px, powner, participants=None):
        """Returns (logits (m, t, K), masks (m, t)) for cohort ``ci``."""
        c = self.cohorts[ci]
        part = self._part_for(c, participants)
        logits = np.asarray(c.proxy_logits(px, part=part), np.float32)
        masks = np.asarray(c.filter_masks(px, powner, part=part), bool)
        return logits, masks

    def cohort_distill(self, ci: int, px, teacher, weight, epochs: int,
                       batch_size: int, participants=None) -> List[float]:
        c = self.cohorts[ci]
        return c.distill(px, teacher, weight, epochs, batch_size,
                         part=self._part_for(c, participants))

    def cohort_distill_private(self, ci: int, teacher_by_class,
                               valid_by_class, epochs: int, batch_size: int,
                               participants=None) -> List[float]:
        c = self.cohorts[ci]
        return c.distill_private(teacher_by_class, valid_by_class, epochs,
                                 batch_size,
                                 part=self._part_for(c, participants))

    def evaluate(self, x_test, y_test) -> List[float]:
        """Fleet-wide accuracies in client order (the eval node is never
        per cohort)."""
        return self._scatter([c.evaluate(x_test, y_test)
                              for c in self.cohorts])

    def sync_to_clients(self) -> None:
        for cohort in self.cohorts:
            cohort.sync_to_clients()

    # ------------------------------------------------- resumable service
    def state_dict(self) -> Dict:
        """Per-client mutable state in the shared engine checkpoint format
        (``repro.fed.state``): the stacked/host-master training state is
        synced back onto the ``Client`` objects first, so the emitted
        checkpoint is identical in layout to the loop engine's and
        restores under any engine/mesh/wave configuration."""
        from repro.fed.state import clients_state_dict
        self.sync_to_clients()
        return clients_state_dict(self.clients)

    def load_state_dict(self, sd: Dict) -> None:
        from repro.fed.state import load_clients_state_dict
        load_clients_state_dict(self.clients, sd)
        for cohort in self.cohorts:
            cohort.adopt_member_state()
