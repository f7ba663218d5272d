"""Knowledge-distillation losses (Hinton et al.; Algorithm 1 line 41).

Clients distill from the server's aggregated ensemble logits ȳ over proxy
samples. Temperature-scaled KL is the standard FD objective; MSE-on-logits
is provided for the DS-FL-style variants. A per-sample weight vector lets
callers mask out proxy samples with no valid teacher (zero ID contributors).

``kd_kl_loss`` dispatches its per-sample KL to the fused Pallas kernel
(``repro.kernels.distill_kl`` — custom-VJP, so it is differentiable
through both the forward and the fused backward kernel) when the resolved
``kernel_backend`` is "pallas" and the class axis K fills at least one
128-lane tile (K ≥ 128; ``dispatch.kd_kl_route`` holds the rule). At
K < 128, whatever the backend, and on the jnp backend, it takes the jnp
path below, kept inline and op-for-op unchanged (default-backend
bit-for-bit guarantee).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch


def kd_kl_loss(student_logits, teacher_logits, temperature: float = 3.0,
               sample_weight=None, *, backend: Optional[str] = None):
    """KL(teacher_T ∥ student_T) · T², mean over weighted samples.

    student_logits/teacher_logits: (..., K). Scaled by T² so gradient
    magnitudes match the CE loss (Hinton et al. 2014). ``backend`` routes
    the per-sample KL through ``repro.kernels.dispatch`` (None/"auto" =
    ambient policy).
    """
    t = temperature
    k = student_logits.shape[-1]
    if dispatch.kd_kl_route(k, backend) == "pallas":
        lead = student_logits.shape[:-1]
        kl = dispatch.kd_kl_pallas(student_logits.reshape(-1, k),
                                   teacher_logits.reshape(-1, k),
                                   t).reshape(lead)
    else:
        sp = jax.nn.log_softmax(student_logits.astype(jnp.float32) / t, axis=-1)
        tp = jax.nn.softmax(teacher_logits.astype(jnp.float32) / t, axis=-1)
        tlogp = jax.nn.log_softmax(teacher_logits.astype(jnp.float32) / t, axis=-1)
        kl = jnp.sum(tp * (tlogp - sp), axis=-1) * (t * t)
    if sample_weight is None:
        return jnp.mean(kl)
    w = sample_weight.astype(jnp.float32)
    return jnp.sum(kl * w) / jnp.maximum(jnp.sum(w), 1.0)


def kd_mse_loss(student_logits, teacher_logits, sample_weight=None):
    """Mean-squared error on raw logits (FedMD-style digest matching)."""
    se = jnp.mean(jnp.square(student_logits.astype(jnp.float32)
                             - teacher_logits.astype(jnp.float32)), axis=-1)
    if sample_weight is None:
        return jnp.mean(se)
    w = sample_weight.astype(jnp.float32)
    return jnp.sum(se * w) / jnp.maximum(jnp.sum(w), 1.0)


def ce_loss(logits, labels):
    """Plain classification CE (local training, Algorithm 1 line 40)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)
