"""Compile-only checks of the wired Pallas kernels for a described TPU v5e.

Interpret-mode tests run a kernel's body as jnp ops and cannot see what
Mosaic refuses: block shapes that break the (8, 128) tiling rule, 1-D
blocks whose layout differs from XLA's, vector reshapes it has no layout
for. Here each kernel the federated round calls is compiled with
``interpret=False`` against a ``v5e:2x2`` topology description, with no
chip attached, at the widths the main path gives it, and the compiled
program must contain the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and test workers
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.distill_kl import ops as kl_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.kmeans_dist import ops as kd_ops
from repro.kernels.kulsif_rbf import ops as rbf_ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to a persistent
    # cache but cannot be read back without one: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("c, t, d, k", [
    (1, 4000, 3072, 1),      # CIFAR-10 CNN slot, strong non-IID
    (1, 4000, 3072, 10),     # CIFAR-10 CNN slot, IID (one per class)
    (10, 400, 50, 1),        # stacked mnist_feat cohort
    (10, 4000, 3072, 10),    # stacked cohort at image width
])
def test_lloyd_step_compiles(one_chip, c, t, d, k):
    text = _compiled_text(
        lambda x, cents: kd_ops.lloyd_step(x, cents, interpret=False),
        [(c, t, d), (c, k, d)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [64, 1024])
def test_distill_kl_forward_compiles(one_chip, n):
    text = _compiled_text(
        lambda s, t: kl_ops.kd_kl_per_sample(s, t, 3.0, interpret=False),
        [(n, 10), (n, 10)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [64, 1024])
def test_distill_kl_backward_compiles(one_chip, n):
    def loss_and_grads(s, t):
        return jax.value_and_grad(
            lambda s_, t_: kl_ops.kd_kl_per_sample_vjp(
                s_, t_, 3.0, interpret=False).sum(), argnums=(0, 1))(s, t)

    text = _compiled_text(loss_and_grads, [(n, 10), (n, 10)], one_chip)
    # the forward kernel and both backward kernels
    assert text.count("tpu_custom_call") >= 3


def test_distill_kl_vmapped_backward_compiles(one_chip):
    """The cohort engine's distill step: per-client KL under the client
    vmap, differentiated through the custom VJP."""
    def grads(s, t):
        return jax.vmap(jax.grad(
            lambda s_, t_: kl_ops.kd_kl_per_sample_vjp(
                s_, t_, 3.0, interpret=False).sum()))(s, t)

    text = _compiled_text(grads, [(10, 64, 10), (10, 64, 10)], one_chip)
    assert "tpu_custom_call" in text


def test_kulsif_rbf_compiles(one_chip):
    text = _compiled_text(
        lambda a, b: rbf_ops.rbf_matrix(a, b, 1.0, interpret=False),
        [(1000, 3072), (1000, 3072)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b, n, s, h", [
    (2, 4, 16, 16),          # the lm_tokens transformer client
    (1, 8, 512, 128),
])
def test_flash_attention_forward_compiles(one_chip, b, n, s, h):
    text = _compiled_text(
        lambda q, k, v: fa_ops.attention(q, k, v, interpret=False),
        [(b, n, s, h)] * 3, one_chip)
    assert "tpu_custom_call" in text
