"""Loss functions: cross-entropy and the MDD distillation objective.

The distillation loss here is the pure-jnp reference; the fused Pallas
kernel (repro.kernels.kd_loss) computes the same quantity without
materializing full softmaxes over large vocabularies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def cross_entropy_loss(logits, labels, *, mask=None):
    """Mean CE. logits: (..., C); labels: (...) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def kd_kl_loss(student_logits, teacher_logits, temperature: float = 2.0, *, mask=None):
    """KL(teacher_T || student_T) * T^2 (Hinton scaling)."""
    t = temperature
    sl = student_logits.astype(jnp.float32) / t
    tl = teacher_logits.astype(jnp.float32) / t
    log_p_s = jax.nn.log_softmax(sl, axis=-1)
    log_p_t = jax.nn.log_softmax(tl, axis=-1)
    p_t = jnp.exp(log_p_t)
    kl = jnp.sum(p_t * (log_p_t - log_p_s), axis=-1)  # (...)
    if mask is not None:
        kl = jnp.sum(kl * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        kl = jnp.mean(kl)
    return kl * (t * t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_distillation_loss(student_logits, teacher_logits, labels,
                            alpha=0.5, temperature=2.0):
    """Mean distillation loss through the fused per-row kernel path.

    Forward dispatches via :func:`repro.kernels.ops.kd_loss` — the Pallas
    kernel on TPU, the XLA-fused jnp reference on CPU — so neither softmax
    is materialized in HBM on the accelerated path.  Backward is the
    analytic gradient w.r.t. the student logits

        d/ds = [alpha (p1 - onehot) + (1-alpha) T (p_T - q_T)] / N

    (one softmax each, no autodiff through the online accumulators).  The
    teacher is treated as a constant, standard KD semantics: its cotangent
    is zero, so do not differentiate this loss w.r.t. teacher params.

    Numerically identical to :func:`distillation_loss` (same decomposition,
    see tests), but usable inside vmapped/jitted population-scale steps.
    ``alpha``/``temperature`` are static (nondiff) arguments — pass them
    positionally.
    """
    from repro.kernels import ops

    rows = ops.kd_loss(student_logits, teacher_logits, labels,
                       alpha=alpha, temperature=temperature)
    return jnp.mean(rows)


def _fused_fwd(student_logits, teacher_logits, labels, alpha, temperature):
    out = fused_distillation_loss(student_logits, teacher_logits, labels,
                                  alpha, temperature)
    return out, (student_logits, teacher_logits, labels)


def _fused_bwd(alpha, temperature, residuals, g):
    student_logits, teacher_logits, labels = residuals
    sl = student_logits.astype(jnp.float32)
    tl = teacher_logits.astype(jnp.float32)
    n = sl.shape[0]
    p1 = jax.nn.softmax(sl, axis=-1)
    onehot = jax.nn.one_hot(labels, sl.shape[-1], dtype=jnp.float32)
    p_t = jax.nn.softmax(sl / temperature, axis=-1)
    q_t = jax.nn.softmax(tl / temperature, axis=-1)
    ds = (alpha * (p1 - onehot)
          + (1.0 - alpha) * temperature * (p_t - q_t)) * (g / n)
    # labels are integers: their cotangent space is float0
    labels_ct = np.zeros(labels.shape, jax.dtypes.float0)
    return (ds.astype(student_logits.dtype), jnp.zeros_like(teacher_logits),
            labels_ct)


fused_distillation_loss.defvjp(_fused_fwd, _fused_bwd)


def distillation_loss(
    student_logits,
    teacher_logits,
    labels,
    *,
    alpha: float = 0.5,
    temperature: float = 2.0,
    mask=None,
):
    """alpha * CE(student, labels) + (1-alpha) * T^2 KL(teacher || student).

    This is the MDD integration objective (paper §IV): the requester blends
    supervised signal from its own data with the discovered model's
    knowledge.
    """
    ce = cross_entropy_loss(student_logits, labels, mask=mask)
    kd = kd_kl_loss(student_logits, teacher_logits, temperature, mask=mask)
    return alpha * ce + (1.0 - alpha) * kd, {"ce": ce, "kd": kd}
