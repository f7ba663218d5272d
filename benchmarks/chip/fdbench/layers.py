"""Client models declared as a list of layers, for the model kinds that are
such lists (``models/mlp.py``, ``models/cnn_zoo.py``).

Layer kinds: ``["conv", c_out, k, pool, pad]`` (a k x k convolution, ReLU,
and a 2 x 2 max-pool when ``pool``), ``["bn"]`` (batch statistics in
training, stored statistics in inference; the stored ones never move)
and ``["linear", d_out]`` (ReLU unless ``d_out`` is the number of
classes). ``inp`` is a configuration's ``input`` group: ``image_hw`` and
``channels`` for images, ``feature_dim`` for flat features.

Parameters are a list with one dict per layer, in the program's layout:
conv ``w`` (k, k, c_in, c_out), ``b`` (c_out,); BN ``scale``/``bias``/
``mean``/``var`` (c,); linear ``w`` (d_in, d_out), ``b`` (d_out,). The
reference initializes them from one key split once per layer: He-normal
convs and LeCun-normal linears with zero biases, BN scale 1, bias 0 and
stored statistics 0 and 1.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def _start(inp: dict):
    """(height, channels, flat width) of the input."""
    if "image_hw" in inp:
        return inp["image_hw"], inp["channels"], None
    return 0, inp["feature_dim"], inp["feature_dim"]


def filter_dim(inp: dict) -> int:
    """The width of one flattened input row, which the KMeans-DRE filter
    reads."""
    if "image_hw" in inp:
        return inp["image_hw"] ** 2 * inp["channels"]
    return inp["feature_dim"]


def param_shapes(layers: List[list], inp: dict
                 ) -> List[Dict[str, Tuple[int, ...]]]:
    """Per-layer parameter shapes the declared layers imply."""
    h, c, flat = _start(inp)
    out = []
    for layer in layers:
        kind = layer[0]
        if kind == "conv":
            _, cout, k, pool, pad = layer
            out.append({"w": (k, k, c, cout), "b": (cout,)})
            if pad != "SAME":
                h = h - k + 1
            if pool:
                h //= 2
            c = cout
            flat = h * h * c
        elif kind == "bn":
            out.append({n: (c,) for n in ("bias", "mean", "scale", "var")})
        elif kind == "linear":
            d_out = layer[1]
            out.append({"w": (flat, d_out), "b": (d_out,)})
            flat = d_out
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return out


def forward_flops(layers: List[list], inp: dict) -> int:
    """Multiply-adds x 2 of one sample's forward pass through the convs and
    linears (activations, pooling and BN are not counted)."""
    h = inp.get("image_hw", 0)
    total = 0
    for layer, shapes in zip(layers, param_shapes(layers, inp)):
        if layer[0] == "conv":
            _, cout, k, pool, pad = layer
            h_out = h if pad == "SAME" else h - k + 1
            k_, _, cin, _ = shapes["w"]
            total += 2 * h_out * h_out * k_ * k_ * cin * cout
            h = h_out // 2 if pool else h_out
        elif layer[0] == "linear":
            d_in, d_out = shapes["w"]
            total += 2 * d_in * d_out
    return total


def init_params(key, layers: List[list], inp: dict):
    h, c, flat = _start(inp)
    params = []
    for layer in layers:
        key, sub = jax.random.split(key)
        if layer[0] == "conv":
            _, cout, k, pool, pad = layer
            std = math.sqrt(2.0 / (c * k * k))
            params.append({"w": jax.random.normal(sub, (k, k, c, cout)) * std,
                           "b": jnp.zeros((cout,))})
            h = h if pad == "SAME" else h - k + 1
            h = h // 2 if pool else h
            c = cout
            flat = h * h * c
        elif layer[0] == "bn":
            params.append({"scale": jnp.ones((c,)), "bias": jnp.zeros((c,)),
                           "mean": jnp.zeros((c,)), "var": jnp.ones((c,))})
        else:
            d_out = layer[1]
            params.append({"w": jax.random.normal(sub, (flat, d_out))
                           * (1.0 / math.sqrt(flat)),
                           "b": jnp.zeros((d_out,))})
            flat = d_out
    return params


def make_apply(layers: List[list], num_classes: int, precision):
    """``apply(params, x, train)``: the forward pass, (n, K) logits."""
    def apply(params, x, train: bool):
        flat = False
        for layer, p in zip(layers, params):
            if layer[0] == "conv":
                x = jax.lax.conv_general_dilated(
                    x, p["w"], (1, 1), layer[4],
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=precision) + p["b"]
                x = jax.nn.relu(x)
                if layer[3]:
                    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                              (1, 2, 2, 1), (1, 2, 2, 1),
                                              "VALID")
            elif layer[0] == "bn":
                if train:
                    mean = jnp.mean(x, axis=(0, 1, 2))
                    var = jnp.var(x, axis=(0, 1, 2))
                else:
                    mean, var = p["mean"], p["var"]
                x = (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] \
                    + p["bias"]
            else:
                if not flat:
                    x = x.reshape(x.shape[0], -1)
                    flat = True
                x = jnp.dot(x, p["w"], precision=precision) + p["b"]
                if layer[1] != num_classes:
                    x = jax.nn.relu(x)
        return x
    return apply
