"""The benchmark's own synthetic data, made on the device from the seed.

A copy of the program's class-clustered generator (per-class Gaussian
clusters in a latent space, rendered to flat features or to images through
a fixed random decoder) with one change: labels are class-balanced exactly,
``n / K`` of each class in a shuffled order. Under the strong partition a
client's private set is one class, so with balanced labels every client,
every proxy share and every compiled shape is the same for every seed.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Dataset(NamedTuple):
    x: np.ndarray
    y: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    num_classes: int
    name: str


@partial(jax.jit, static_argnames=("n", "n_test", "num_classes", "latent_dim",
                                   "image_hw", "channels", "feature_dim",
                                   "separation", "within_std"))
def _generate(key, *, n, n_test, num_classes, latent_dim, image_hw, channels,
              feature_dim, separation, within_std):
    k_means, k_tr, k_te, k_dec = jax.random.split(key, 4)
    means = jax.random.normal(k_means, (num_classes, latent_dim))
    means = means / jnp.linalg.norm(means, axis=-1, keepdims=True) * separation

    def sample(k, m):
        ky, kz = jax.random.split(k)
        y = jax.random.permutation(
            ky, jnp.repeat(jnp.arange(num_classes, dtype=jnp.int32),
                           m // num_classes))
        z = means[y] + within_std * jax.random.normal(kz, (m, latent_dim))
        return z, y

    z_tr, y_tr = sample(k_tr, n)
    z_te, y_te = sample(k_te, n_test)
    out_dim = image_hw * image_hw * channels if image_hw else feature_dim
    dec = jax.random.normal(k_dec, (latent_dim, out_dim)) / jnp.sqrt(latent_dim)

    def render(z):
        if image_hw:
            img = jnp.tanh(z @ dec)
            return img.reshape(-1, image_hw, image_hw, channels)
        return z @ dec

    return render(z_tr), y_tr, render(z_te), y_te


def make_dataset(spec: dict, n_train: int, n_test: int, seed: int) -> Dataset:
    """``spec`` is a configuration's ``dataset`` group; sizes come from the
    traffic. Both sizes must split evenly over the classes."""
    k = int(spec["num_classes"])
    for what, m in (("n_train", n_train), ("n_test", n_test)):
        if m % k:
            raise ValueError(f"{what}={m} does not split evenly over {k} classes")
    with jax.default_matmul_precision("highest"):
        x, y, xt, yt = _generate(
            jax.random.PRNGKey(seed), n=n_train, n_test=n_test, num_classes=k,
            latent_dim=int(spec["latent_dim"]),
            image_hw=int(spec.get("image_hw", 0)),
            channels=int(spec.get("channels", 1)),
            feature_dim=int(spec.get("feature_dim", 0)),
            separation=float(spec["separation"]),
            within_std=float(spec["within_std"]))
    return Dataset(np.asarray(x), np.asarray(y), np.asarray(xt), np.asarray(yt),
                   k, spec["name"])
