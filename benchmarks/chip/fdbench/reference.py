"""Plain reference of the EdgeFD rounds a cell runs, independent of ``src/``.

It follows the protocol as the configuration and traffic state it, from
the same seed and the same benchmark-made data, and imports nothing of the
program:

* the non-IID partition (strong: one class per client; IID: a uniform
  split) and the proxy set (a fraction of each client's data, shuffled),
  with NumPy generators seeded as the protocol seeds them;
* each client's model, as the configuration's model kind declares it
  (``models/<kind>.py``), initialized from ``PRNGKey(seed)`` split once
  per client;
* the KMeans-DRE filter: k-means++ seeding from ``fold_in(key, client)``,
  50 Lloyd iterations with a 1e-6 shift tolerance, the threshold at the
  0.95 quantile of the client's own distances; a proxy row is kept when
  its owner is the client or it lies within the threshold;
* one round: local SGD (momentum 0.9) over one shuffled epoch of full
  batches on cross-entropy, the server's proxy-batch draw, eval-mode
  logits on it, the masked-mean teacher, distillation by
  ``T^2 KL(teacher_T || student_T)`` weighted by the teacher's validity,
  and test accuracy.

Client ``cid`` lives on ``devices[cid % len(devices)]``: its parameters,
momentum, private data and filter are committed there and its calls run
there, each update in place of the state it replaces (the state is
donated). The teacher is formed on ``devices[0]``. So a cohort whose
state no one chip holds is followed on the chips that the cell asks for.

All of it runs in ``dtype`` with matmuls and convolutions at ``precision``:
float32 at ``highest`` is the reference; bfloat16 is the control.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fdbench import kinds

MOMENTUM = 0.9
KMEANS_ITERS = 50
KMEANS_TOL = 1e-6
CALIBRATION_Q = 0.95
EVAL_BATCH = 512
FAULTS = ("half_batch", "answer_altered", "threshold_altered")
THRESHOLD_FAULT = 0.5   # the threshold_altered fault scales every threshold


# ----------------------------------------------------------------- data split
def partition(x, y, num_clients: int, num_classes: int, scenario: str,
              seed: int):
    rng = np.random.default_rng(seed)
    by_label = [np.where(y == c)[0] for c in range(num_classes)]
    out = []
    if scenario == "strong":
        perm = rng.permutation(num_classes)
        for chunk in np.array_split(perm, num_clients):
            idx = np.concatenate([by_label[c] for c in np.sort(chunk)])
            rng.shuffle(idx)
            out.append((x[idx], y[idx]))
    elif scenario == "iid":
        for part in np.array_split(rng.permutation(len(y)), num_clients):
            out.append((x[part], y[part]))
    else:
        raise ValueError(f"reference has no {scenario!r} partition")
    return out


def proxy_set(clients, fraction: float, seed: int):
    rng = np.random.default_rng(seed)
    xs, owners = [], []
    for cid, (x, _) in enumerate(clients):
        take = max(1, int(round(fraction * len(x))))
        idx = rng.choice(len(x), size=take, replace=False)
        xs.append(x[idx])
        owners.append(np.full(take, cid, np.int32))
    x = np.concatenate(xs)
    owner = np.concatenate(owners)
    perm = rng.permutation(len(owner))
    return x[perm], owner[perm]


# ---------------------------------------------------------------- the models
def init_params(key, config: dict, cid: int):
    """Client ``cid``'s initial parameters, as its model kind makes them."""
    return kinds.load(config).init_params(key, config, cid)


# ------------------------------------------------------------------ the filter
def _sq_dists(x, c):
    return jnp.sum(jnp.square(x[:, None, :] - c[None, :, :]), axis=-1)


def _kmeans_pp(key, x, k: int):
    n = x.shape[0]
    k0, key = jax.random.split(key)
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(
        x[jax.random.randint(k0, (), 0, n)])
    min_d2 = jnp.full((n,), jnp.inf, x.dtype)
    for i in range(1, k):
        min_d2 = jnp.minimum(min_d2,
                             jnp.sum(jnp.square(x - cents[i - 1]), axis=-1))
        key, sub = jax.random.split(key)
        probs = min_d2 / jnp.maximum(jnp.sum(min_d2), 1e-12)
        cents = cents.at[i].set(x[jax.random.choice(sub, n, p=probs)])
    return cents


def _fit_filter(key, x, k: int):
    x = x.reshape(x.shape[0], -1).astype(jnp.float32)
    cents = _kmeans_pp(key, x, k)

    def lloyd(carry, _):
        cents, done = carry
        d2 = _sq_dists(x, cents)
        one_hot = jax.nn.one_hot(jnp.argmin(d2, axis=-1), k,
                                 dtype=jnp.float32)
        counts = jnp.sum(one_hot, axis=0)
        sums = jnp.einsum("nk,nd->kd", one_hot, x,
                          precision=jax.lax.Precision.HIGHEST)
        new = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(counts[:, None], 1.0), cents)
        shift = jnp.sum(jnp.square(new - cents))
        return (jnp.where(done, cents, new), done | (shift < KMEANS_TOL)), None

    (cents, _), _ = jax.lax.scan(lloyd, (cents, jnp.bool_(False)), None,
                                 length=KMEANS_ITERS)
    dmin = jnp.sqrt(jnp.min(_sq_dists(x, cents), axis=-1))
    return cents, jnp.quantile(dmin, CALIBRATION_Q)


_fit_filter_jit = jax.jit(_fit_filter, static_argnums=2)


@jax.jit
def _filter_mask(cents, thr, px_flat, powner, cid):
    d = jnp.sqrt(jnp.min(_sq_dists(px_flat.astype(jnp.float32), cents),
                         axis=-1))
    return (powner == cid) | (d <= thr)


# ------------------------------------------------------------------ the steps
def _make_fns(apply, lr: float, temperature: float, dtype,
              fault: Optional[str]):
    """The jitted train, distill, logits and correct-count functions of one
    forward function ``apply(params, x, train)``."""

    def sgd_scan(loss_fn, params, mu, batches):
        def step(carry, batch):
            p, m = carry
            loss, g = jax.value_and_grad(loss_fn)(p, *batch)
            m = jax.tree.map(lambda a, b: (MOMENTUM * a + b).astype(dtype),
                             m, g)
            p = jax.tree.map(lambda a, b: (a - lr * b).astype(dtype), p, m)
            return (p, m), loss
        (params, mu), losses = jax.lax.scan(step, (params, mu), batches)
        return params, mu, jnp.mean(losses)

    def rows(xb):
        return xb[: xb.shape[0] // 2] if fault == "half_batch" else xb

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train(params, mu, x, y, idx):
        def loss_fn(p, ib):
            xb, yb = rows(jnp.take(x, ib, axis=0)), rows(jnp.take(y, ib,
                                                                  axis=0))
            logp = jax.nn.log_softmax(apply(p, xb, True).astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))
        return sgd_scan(loss_fn, params, mu, (idx,))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def distill(params, mu, px, teacher, w, idx):
        t = temperature

        def loss_fn(p, ib):
            s = apply(p, rows(jnp.take(px, ib, axis=0)), True)
            s = s.astype(jnp.float32)
            tb = rows(jnp.take(teacher, ib, axis=0))
            wb = rows(jnp.take(w, ib, axis=0))
            tlogp = jax.nn.log_softmax(tb / t)
            kl = jnp.sum(jnp.exp(tlogp) * (tlogp - jax.nn.log_softmax(s / t)),
                         axis=-1) * (t * t)
            return jnp.sum(kl * wb) / jnp.maximum(jnp.sum(wb), 1.0)
        return sgd_scan(loss_fn, params, mu, (idx,))

    @jax.jit
    def logits(params, x):
        out = apply(params, x, False).astype(jnp.float32)
        if fault == "answer_altered":
            out = out.at[: out.shape[0] // 4].multiply(-1.0)
        return out

    @jax.jit
    def correct(params, x, y):
        return jnp.sum(jnp.argmax(apply(params, x, False), -1) == y)

    return train, distill, logits, correct


def _inputs(x, dtype) -> jax.Array:
    """Model inputs on the device: features and images in ``dtype``;
    integer inputs, such as token ids, as they are."""
    x = np.asarray(x)
    return jnp.asarray(x, dtype if np.issubdtype(x.dtype, np.floating)
                       else None)


def _leaves(params) -> List[jax.Array]:
    """Leaves in ``jax.tree_util``'s order, the harness's too: for a list
    of per-layer dicts, layer by layer with the keys sorted."""
    return jax.tree_util.tree_leaves(params)


def _norms(leaves) -> np.ndarray:
    return np.asarray(jax.device_get(
        [jnp.linalg.norm(jnp.ravel(a).astype(jnp.float32)) for a in leaves]),
        np.float64)


def _on_each(x, devices) -> List[jax.Array]:
    """``x`` committed to each of ``devices``, in their order."""
    return [jax.device_put(x, d) for d in devices]


def run(config: dict, traffic: dict, data, seed: int, *,
        devices: Optional[Sequence] = None, rounds: int = 3,
        dtype=jnp.float32, precision="highest",
        fault: Optional[str] = None) -> Dict:
    """Follow the first ``rounds`` rounds on ``devices`` (the first device
    alone by default); return the readings the comparison reads (see
    ``compare.readings_keys``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    devices = list(devices or jax.devices()[:1])
    n_dev = len(devices)
    k_cls = config["num_classes"]
    n_cl = traffic["num_clients"]
    batch = traffic["batch_size"]
    x_all, y_all = np.asarray(data.x), np.asarray(data.y)
    clients = partition(x_all, y_all, n_cl, k_cls, traffic["scenario"], seed)
    px_all, powner_all = proxy_set(clients, traffic["proxy_fraction"], seed)
    n_centroids = 1 if traffic["scenario"] == "strong" else k_cls

    model = kinds.load(config)
    key = jax.random.PRNGKey(seed)
    params, mus, fns, xs, ys, rngs, filters = [], [], [], [], [], [], []
    fn_cache: Dict = {}
    for cid, (x, y) in enumerate(clients):
        key, sub = jax.random.split(key)
        dev = devices[cid % n_dev]
        with jax.default_device(dev):       # made there, not moved there
            p = jax.tree.map(lambda a: a.astype(dtype),
                             model.init_params(sub, config, cid))
            params.append(jax.device_put(p, dev))
            mus.append(jax.device_put(jax.tree.map(jnp.zeros_like, p), dev))
            xs.append(jax.device_put(_inputs(x, dtype), dev))
            ys.append(jax.device_put(jnp.asarray(y), dev))
        arch = model.arch_key(config, cid)
        if arch not in fn_cache:
            fn_cache[arch] = _make_fns(
                model.make_apply(config, cid, precision), traffic["lr"],
                traffic["temperature"], dtype, fault)
        fns.append(fn_cache[arch])
        rngs.append(np.random.default_rng(seed + 1000 * cid))
    dre_key = jax.random.PRNGKey(seed)
    for cid, (x, _) in enumerate(clients):
        dev = devices[cid % n_dev]
        with jax.default_device(dev):
            cents, thr = _fit_filter_jit(jax.random.fold_in(dre_key, cid),
                                         jnp.asarray(x), n_centroids)
            if fault == "threshold_altered":
                thr = thr * THRESHOLD_FAULT
            filters.append(jax.device_put((cents, thr), dev))
    server_rng = np.random.default_rng(seed + 7)
    # the starting point, copied to the host: the device's is donated
    p0 = [[np.array(a, copy=True) for a in _leaves(p)] for p in params]
    x_test = np.asarray(data.x_test)
    n_test = len(data.y_test)
    tests = _on_each([(_inputs(x_test[s:s + EVAL_BATCH], dtype),
                       jnp.asarray(data.y_test[s:s + EVAL_BATCH]))
                      for s in range(0, n_test, EVAL_BATCH)], devices)

    out = {"local_loss": [], "distill_loss": [], "accs": [], "id_fracs": []}
    for r in range(rounds):
        local = []
        for cid in range(n_cl):
            n = xs[cid].shape[0]
            nb = max(1, n // batch)
            perm = rngs[cid].permutation(n)
            idx = perm[: nb * batch].reshape(nb, batch) if n >= batch \
                else perm[None]
            params[cid], mus[cid], loss = fns[cid][0](
                params[cid], mus[cid], xs[cid], ys[cid], jnp.asarray(idx))
            local.append(loss)
        sel = server_rng.choice(len(powner_all),
                                size=min(traffic["proxy_batch"],
                                         len(powner_all)), replace=False)
        px = _on_each(_inputs(px_all[sel], dtype), devices)
        powner = _on_each(jnp.asarray(powner_all[sel]), devices)
        px_flat = _on_each(jnp.asarray(px_all[sel].reshape(len(sel), -1)),
                           devices)
        lg, mk = [], []
        for cid in range(n_cl):
            d = cid % n_dev
            lg.append(fns[cid][2](params[cid], px[d]))
            mk.append(_filter_mask(*filters[cid], px_flat[d], powner[d],
                                   cid))
        lg = jnp.stack(jax.device_put(lg, devices[0]))
        mk = jnp.stack(jax.device_put(mk, devices[0]))
        m = mk.astype(jnp.float32)[..., None]
        cnt = jnp.sum(m, axis=0)
        teacher = _on_each(jnp.sum(lg * m, axis=0) / jnp.maximum(cnt, 1.0),
                           devices)
        w = _on_each((cnt[..., 0] > 0).astype(jnp.float32), devices)
        dist = []
        t = len(sel)
        for cid in range(n_cl):
            nb = max(1, t // batch)
            perm = rngs[cid].permutation(t)
            idx = perm[: nb * batch].reshape(nb, batch) if t >= batch \
                else perm[None]
            d = cid % n_dev
            params[cid], mus[cid], loss = fns[cid][1](
                params[cid], mus[cid], px[d], teacher[d], w[d],
                jnp.asarray(idx))
            dist.append(loss)
        accs = []
        for cid in range(n_cl):
            c = 0
            for xb, yb in tests[cid % n_dev]:
                c += fns[cid][3](params[cid], xb, yb)
            accs.append(c)
        local, dist, accs, fr = jax.device_get(
            (local, dist, accs, jnp.mean(mk.astype(jnp.float32), axis=1)))
        out["local_loss"].append(float(np.mean(np.asarray(local, np.float64))))
        out["distill_loss"].append(float(np.mean(np.asarray(dist,
                                                            np.float64))))
        out["accs"].append([int(a) / n_test for a in accs])
        out["id_fracs"].append([float(v) for v in fr])
        if r == 0:
            out["grad_norms"] = np.concatenate(
                [_norms(_leaves(mu)) for mu in mus]).tolist()
    out["change_norms"] = np.concatenate(
        [_norms([a - b for a, b in zip(_leaves(p), l0)])
         for p, l0 in zip(params, p0)]).tolist()
    out["leaf_shapes"] = [list(a.shape) for p in params for a in _leaves(p)]
    return out
