"""Operations and bytes counted from the configuration's declared shapes.

Nothing here reads the program or the compiler's cost analysis: a model's
FLOPs come from its model kind's module (``models/<kind>.py``) and the
shapes ``configs/*.json`` declare, and the distill-KL kernel's FLOPs and
bytes from each call's ``(n, K)``.
"""
from __future__ import annotations

from typing import List, Tuple

from fdbench import kinds


def param_shapes(config: dict, arch: int):
    """Client ``arch``'s parameter shapes in the program's layout, as its
    model kind declares them (``models/<kind>.py``)."""
    return kinds.load(config).param_shapes(config, arch)


def forward_flops(config: dict, arch: int) -> int:
    """FLOPs of one sample's forward pass through client ``arch``'s model,
    as its model kind counts them."""
    return kinds.load(config).forward_flops(config, arch)


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
    d = kinds.load(config).filter_dim(config)
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
