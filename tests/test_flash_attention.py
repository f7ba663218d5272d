"""kernels/flash_attention: pallas(interpret) ≡ jnp oracle, forward and
gradient, under jit(vmap); plus the dispatch wiring into
``models.layers.attention_forward`` (precedence + trace stability,
mirroring tests/test_kernel_dispatch.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.flash_attention import ops, ref


def _qkv(key, b=2, n=4, nkv=4, s=48, h=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, n, s, h), dtype)
    k = jax.random.normal(kk, (b, nkv, s, h), dtype)
    v = jax.random.normal(kv, (b, nkv, s, h), dtype)
    return q, k, v


def _expand(x, rep):
    return jnp.repeat(x, rep, axis=1) if rep > 1 else x


# ------------------------------------------------------------- forward

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("nkv", [4, 2, 1])
def test_forward_matches_ref(causal, nkv):
    """Kernel ≡ oracle for full/causal attention and every GQA ratio,
    including a sequence length that is not a block multiple (padding +
    kv_len masking)."""
    q, k, v = _qkv(jax.random.PRNGKey(0), nkv=nkv, s=70)
    out = ops.attention(q, k, v, causal=causal, interpret=True)
    want = ref.attention(q, _expand(k, 4 // nkv), _expand(v, 4 // nkv),
                         causal=causal)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_forward_under_jit_vmap():
    """An extra leading batch axis via jit(vmap) — the cohort engine's
    execution shape — must agree with per-slice calls."""
    q, k, v = _qkv(jax.random.PRNGKey(1), nkv=2, s=64)
    bq, bk, bv = (jnp.stack([t, t * 0.5]) for t in (q, k, v))
    out = jax.jit(jax.vmap(
        lambda a, b_, c: ops.attention(a, b_, c, causal=True,
                                       interpret=True)))(bq, bk, bv)
    for i, scale in enumerate((1.0, 0.5)):
        want = ops.attention(q * scale, k * scale, v * scale,
                             causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(want),
                                   atol=2e-5)


# ------------------------------------------------------------- gradient

@pytest.mark.parametrize("nkv", [4, 2])
def test_gradient_matches_ref(nkv):
    """custom_vjp backward (oracle recompute) ≡ differentiating the oracle
    directly, for q, k and v — including the GQA grouped-kv cotangent
    sum."""
    rep = 4 // nkv
    q, k, v = _qkv(jax.random.PRNGKey(2), nkv=nkv, s=40)

    def loss_kernel(q_, k_, v_):
        return jnp.sum(ops.attention(q_, k_, v_, causal=True,
                                     interpret=True) ** 2)

    def loss_ref(q_, k_, v_):
        o = ref.attention(q_, _expand(k_, rep), _expand(v_, rep),
                          causal=True)
        return jnp.sum(o.astype(q_.dtype) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"grad wrt {name}")


def test_gradient_under_jit_vmap():
    q, k, v = _qkv(jax.random.PRNGKey(3), nkv=2, s=32)
    bq, bk, bv = (jnp.stack([t, t + 0.1]) for t in (q, k, v))

    def loss(q_, k_, v_):
        return jnp.sum(ops.attention(q_, k_, v_, causal=True,
                                     interpret=True) ** 2)

    got = jax.jit(jax.vmap(jax.grad(loss)))(bq, bk, bv)
    for i in range(2):
        want = jax.grad(loss)(bq[i], bk[i], bv[i])
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   atol=1e-4)


# ------------------------------------------------------------- dispatch

def _layers_qkv(key, b=2, s=48, n=4, h=16):
    """(B, S, N, h) — the models.layers layout dispatch.flash_attention
    takes (kv already GQA-expanded)."""
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, s, n, h)),
            jax.random.normal(kk, (b, s, n, h)),
            jax.random.normal(kv, (b, s, n, h)))


def test_dispatch_backends_agree():
    q, k, v = _layers_qkv(jax.random.PRNGKey(4))
    base = dispatch.flash_attention(q, k, v, causal=True, backend="jnp")
    with dispatch.kernel_backend("pallas"):
        pal = dispatch.flash_attention(q, k, v, causal=True)
    assert base.shape == pal.shape == q.shape
    np.testing.assert_allclose(np.asarray(base), np.asarray(pal), atol=2e-5)


def test_dispatch_jnp_is_the_historical_sequence():
    """The jnp route must be op-for-op layers' make_mask + attention_scores
    (the default-backend bit-for-bit guarantee)."""
    from repro.models import layers as L
    q, k, v = _layers_qkv(jax.random.PRNGKey(5))
    got = dispatch.flash_attention(q, k, v, causal=True, window=0,
                                   backend="jnp")
    mask = L.make_mask(q.shape[1], k.shape[1], causal=True, window=0)
    want = L.attention_scores(q, k, v, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dispatch_window_always_takes_reference_path():
    """The kernel has no sliding-window support: window>0 must hit the
    reference sequence on EVERY backend."""
    from repro.models import layers as L
    q, k, v = _layers_qkv(jax.random.PRNGKey(6))
    mask = L.make_mask(q.shape[1], k.shape[1], causal=True, window=8)
    want = np.asarray(L.attention_scores(q, k, v, mask))
    for backend in ("jnp", "pallas"):
        got = dispatch.flash_attention(q, k, v, causal=True, window=8,
                                       backend=backend)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_dispatch_counts_the_route_taken():
    """``route_counts`` names the reference route a windowed call takes
    under the Pallas backend, so a chip run shows it."""
    q, k, v = _layers_qkv(jax.random.PRNGKey(6))
    dispatch.route_counts.clear()
    dispatch.flash_attention(q, k, v, causal=True, backend="pallas")
    dispatch.flash_attention(q, k, v, causal=True, window=8,
                             backend="pallas")
    dispatch.flash_attention(q, k, v, causal=True, backend="jnp")
    assert dispatch.route_counts == {("flash_attention", "pallas"): 1,
                                     ("flash_attention", "jnp (window)"): 1,
                                     ("flash_attention", "jnp"): 1}


def test_explicit_backend_beats_context():
    q, k, v = _layers_qkv(jax.random.PRNGKey(7))
    from repro.models import layers as L
    mask = L.make_mask(q.shape[1], k.shape[1], causal=True, window=0)
    want = np.asarray(L.attention_scores(q, k, v, mask))
    with dispatch.kernel_backend("pallas"):
        got = dispatch.flash_attention(q, k, v, causal=True, backend="jnp")
    np.testing.assert_array_equal(np.asarray(got), want)


def test_attention_forward_wiring_backend_parity():
    """models.layers.attention_forward (the transformer hot path) agrees
    across backends now that its non-chunked branch is dispatched."""
    from repro.configs import get_arch, reduced
    from repro.models import transformer as T
    cfg = reduced(get_arch("granite-8b"), layers=2, d_model=64, vocab=32)
    params = T.init_params(cfg, jax.random.PRNGKey(8))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 24), 0,
                                cfg.vocab_size)
    base, _ = T.forward(params, cfg, tokens)
    with dispatch.kernel_backend("pallas"):
        pal, _ = T.forward(params, cfg, tokens)
    np.testing.assert_allclose(np.asarray(base), np.asarray(pal), atol=2e-4)


def test_trace_stability_across_backend_flips():
    """Resolution bakes at trace time: a jitted forward compiled under one
    ambient backend must not retrace when the ambient flips."""
    traces = []

    @jax.jit
    def fwd(q, k, v):
        traces.append(q.shape)
        return dispatch.flash_attention(q, k, v, causal=True)

    q, k, v = _layers_qkv(jax.random.PRNGKey(10))
    fwd(q, k, v)
    first = len(traces)
    assert first == 1
    for ambient in ("pallas", "jnp", "auto"):
        with dispatch.kernel_backend(ambient):
            fwd(q, k, v)
    assert len(traces) == first, (
        f"ambient backend flip retraced flash_attention: {traces}")
