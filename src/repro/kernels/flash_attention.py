"""Flash attention Pallas TPU kernel — GQA, causal, optional sliding window.

TPU adaptation: Q/KV tiles are (block_q × head_dim) /
(block_kv × head_dim) VMEM blocks with MXU-aligned dims; the KV axis is the
innermost sequential grid dimension with online-softmax accumulators
(m, l, acc) held in VMEM scratch across KV steps.  GQA is expressed in the
BlockSpec index maps (kv_head = q_head // group) so KV is never replicated
in HBM.

Layouts: q (B, H, S, hd); k, v (B, KV, S, hd); out (B, H, S, hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, block_q, block_kv, causal, window, kv_steps,
):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)  # (bkv, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq,bkv)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), bool)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    # rows with no valid key yet: keep everything zeroed
    alive = m_new > NEG_INF / 2
    p = jnp.where(alive[:, None], p, 0.0)
    corr = jnp.where(alive, jnp.exp(m_prev - m_new), 1.0)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == kv_steps - 1)
    def _finish():
        lsum = l_scr[...]
        safe = jnp.where(lsum > 0, lsum, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q, k, v, *, causal=True, window=None, block_q=128, block_kv=128, interpret=False
):
    """q: (B,H,S,hd); k,v: (B,KV,S,hd) -> (B,H,S,hd)."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    kv_steps = S // block_kv
    scale = 1.0 / (hd**0.5)

    grid = (B, H, S // block_q, kv_steps)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        causal=causal,
        window=window,
        kv_steps=kv_steps,
    )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
