"""Operations and bytes counted from the configuration's declared shapes.

Nothing here reads the program or the compiler's cost analysis: a model's
FLOPs come from the layer lists in ``configs/*.json``, and the distill-KL
kernel's FLOPs and bytes from each call's ``(n, K)``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def arch_layers(config: dict, arch: int) -> List[list]:
    """The declared layer list of one client architecture."""
    if config["model"] == "cnn_zoo":
        return config["archs"][arch % len(config["archs"])]
    dims = [config["input"]["feature_dim"], *config["hidden"],
            config["num_classes"]]
    return [["linear", d] for d in dims[1:]]


def param_shapes(config: dict, arch: int) -> List[Dict[str, Tuple[int, ...]]]:
    """Per-layer parameter shapes the declared layers imply, in the
    program's layout: conv ``w`` (k, k, c_in, c_out), ``b`` (c_out,); BN
    ``scale``/``bias``/``mean``/``var`` (c,); linear ``w`` (d_in, d_out),
    ``b`` (d_out,)."""
    inp = config["input"]
    if config["model"] == "cnn_zoo":
        h = inp["image_hw"]
        c = inp["channels"]
        flat = None
    else:
        h, c, flat = 0, inp["feature_dim"], inp["feature_dim"]
    out = []
    for layer in arch_layers(config, arch):
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


def forward_flops(config: dict, arch: int) -> int:
    """Multiply-adds x 2 of one sample's forward pass through the convs and
    linears (activations, pooling and BN are not counted)."""
    inp = config["input"]
    h = inp.get("image_hw", 0)
    total = 0
    for layer, shapes in zip(arch_layers(config, arch),
                             param_shapes(config, arch)):
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


def round_flops(config: dict, traffic: dict, cohort_sizes: List[int],
                dre_centroids: int) -> float:
    """Model FLOPs one round of the protocol requires.

    Training and distill steps count 3x the forward pass, report and eval
    1x. Participating clients train on their full batches (the program
    drops a ragged tail), report on the proxy batch and distill over it;
    every client evaluates the test set. The report also counts the
    KMeans-DRE filter's distances (2 t k d per client). Gated no-op lanes
    and padding rows do not count.
    """
    k_batch = traffic["batch_size"]
    n = traffic["samples_per_client"]
    train_rows = (n // k_batch) * k_batch if n >= k_batch else n
    t = traffic["proxy_batch"]
    distill_rows = (t // k_batch) * k_batch if t >= k_batch else t
    part = traffic.get("participation_fraction", 1.0)
    inp = config["input"]
    d = (inp["image_hw"] ** 2 * inp["channels"] if "image_hw" in inp
         else inp["feature_dim"])
    total = 0.0
    cid = 0
    for size in cohort_sizes:
        for _ in range(size):
            f = forward_flops(config, cid)
            total += part * (3 * f * train_rows + f * t
                             + 2 * t * dre_centroids * d
                             + 3 * f * distill_rows)
            total += f * traffic["n_test"]
            cid += 1
    return total


def distill_kl_cost(n: int, k: int, kind: str) -> Tuple[float, float]:
    """FLOPs and HBM bytes of one distill-KL kernel call over ``n`` rows of
    ``K`` f32 logits, counted per row from what the kernel must compute.

    ``fwd``: two max-shifted log-softmaxes at temperature T (scale,
    subtract, exp, sum, log: 5K + 2 each) and sum_k p_t (log p_t - log p_s)
    (3K); reads student and teacher (2nK x 4 bytes), writes n x 4.
    ``bwd_ds`` / ``bwd_dt``: each backward kernel recomputes both
    log-softmaxes (10K + 4), exponentiates what its gradient needs (2K) and
    forms the gradient row (3K); reads student, teacher and the cotangent
    (2nK x 4 + n x 4), writes nK x 4.
    """
    if kind == "fwd":
        return float(n * (13 * k + 4)), float(n * k * 8 + n * 4)
    if kind in ("bwd_ds", "bwd_dt"):
        return float(n * (15 * k + 4)), float(n * k * 12 + n * 4)
    raise ValueError(f"unknown distill-KL kernel kind {kind!r}")


def roofline_seconds(flops: float, bytes_: float, peaks: dict
                     ) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
