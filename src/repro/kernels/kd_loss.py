"""Fused distillation-loss Pallas TPU kernel.

Computes, per row, alpha*CE(student,label) + (1-alpha)*T^2*KL(teacher_T ||
student_T) while streaming the vocab axis through VMEM in tiles — neither
softmax is ever materialized in HBM.  This is the MDD hot spot for large
vocabs (teacher+student logits at vocab 256k are ~2×512KB per token in bf16;
the fused kernel reads each tile once and keeps only O(block_n) accumulator
state).

Decomposition (all accumulated online with running max m and rescaled sums):
  KL = E_t[tl/T] - logZ_t + logZ_s - E_t[sl/T]
     = (s_tt - s_ts)/l_t - (m_t + log l_t) + (m_s + log l_s)
  CE = (m_s1 + log l_s1) - sl[label]            (T=1 scale)

Grid: (row_blocks, vocab_blocks) with the vocab axis innermost/sequential;
accumulators live in VMEM scratch across vocab steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kd_kernel(
    s_ref, t_ref, lab_ref, out_ref,
    m_s1, l_s1, gold, m_s, l_s, m_t, l_t, s_tt, s_ts,
    *, alpha, inv_t, block_n, block_v, v_steps, vocab,
):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_s1[...] = jnp.full_like(m_s1, NEG_INF)
        l_s1[...] = jnp.zeros_like(l_s1)
        gold[...] = jnp.zeros_like(gold)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        m_t[...] = jnp.full_like(m_t, NEG_INF)
        l_t[...] = jnp.zeros_like(l_t)
        s_tt[...] = jnp.zeros_like(s_tt)
        s_ts[...] = jnp.zeros_like(s_ts)

    sl = s_ref[...].astype(jnp.float32)  # (bn, bv)
    tl = t_ref[...].astype(jnp.float32)
    labels = lab_ref[...]  # (bn, 1)
    cols = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1)
    valid = cols < vocab
    sl = jnp.where(valid, sl, NEG_INF)
    tl = jnp.where(valid, tl, NEG_INF)

    # ---- student, T=1 (CE) ----
    m_new = jnp.maximum(m_s1[...], jnp.max(sl, -1, keepdims=True))
    corr = jnp.exp(m_s1[...] - m_new)
    l_s1[...] = l_s1[...] * corr + jnp.sum(jnp.exp(sl - m_new), -1,
                                           keepdims=True)
    m_s1[...] = m_new
    gold[...] += jnp.sum(jnp.where(cols == labels, sl, 0.0), -1, keepdims=True)

    # ---- student at T (KL) ----
    sl_t = sl * inv_t
    m_new = jnp.maximum(m_s[...], jnp.max(sl_t, -1, keepdims=True))
    corr = jnp.exp(m_s[...] - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(jnp.exp(sl_t - m_new), -1,
                                         keepdims=True)
    m_s[...] = m_new

    # ---- teacher at T: weights + weighted sums of tl_t and sl_t ----
    tl_t = tl * inv_t
    m_new = jnp.maximum(m_t[...], jnp.max(tl_t, -1, keepdims=True))
    corr = jnp.exp(m_t[...] - m_new)
    p = jnp.exp(tl_t - m_new)
    p = jnp.where(valid, p, 0.0)
    l_t[...] = l_t[...] * corr + jnp.sum(p, -1, keepdims=True)
    s_tt[...] = s_tt[...] * corr + jnp.sum(p * tl_t, -1, keepdims=True)
    s_ts[...] = s_ts[...] * corr + jnp.sum(
        p * jnp.where(valid, sl_t, 0.0), -1, keepdims=True)
    m_t[...] = m_new

    @pl.when(vi == v_steps - 1)
    def _finish():
        logz_s1 = m_s1[...] + jnp.log(l_s1[...])
        ce = logz_s1 - gold[...]
        logz_s = m_s[...] + jnp.log(l_s[...])
        logz_t = m_t[...] + jnp.log(l_t[...])
        kl = (s_tt[...] - s_ts[...]) / l_t[...] - logz_t + logz_s
        t2 = 1.0 / (inv_t * inv_t)
        out_ref[...] = alpha * ce + (1.0 - alpha) * t2 * kl


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "temperature", "block_n", "block_v", "interpret"),
)
def kd_loss(
    student_logits,
    teacher_logits,
    labels,
    *,
    alpha=0.5,
    temperature=2.0,
    block_n=128,
    block_v=2048,
    interpret=False,
):
    """Per-row fused distillation loss. (N,V),(N,V),(N,) -> (N,) f32.

    Labels, output and the per-row accumulators are carried as ``(rows, 1)``
    columns: Mosaic rejects 1-D row blocks (their layout differs from
    XLA's), and once ``vmap`` adds a party axis a 1-D block's last two
    dimensions would no longer be tile-aligned.
    """
    N, V = student_logits.shape
    block_n = min(block_n, N)
    assert N % block_n == 0, (N, block_n)
    block_v = min(block_v, V)
    v_steps = -(-V // block_v)
    grid = (N // block_n, v_steps)

    kernel = functools.partial(
        _kd_kernel,
        alpha=alpha,
        inv_t=1.0 / temperature,
        block_n=block_n,
        block_v=block_v,
        v_steps=v_steps,
        vocab=V,
    )
    def scr(shape):
        return pltpu.VMEM(shape, jnp.float32)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda ni, vi: (ni, vi)),
            pl.BlockSpec((block_n, block_v), lambda ni, vi: (ni, vi)),
            pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        scratch_shapes=[scr((block_n, 1)) for _ in range(9)],
        interpret=interpret,
    )(student_logits, teacher_logits, labels.reshape(N, 1))
    return out[:, 0]
