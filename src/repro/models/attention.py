"""Grouped-query attention with full / sliding-window causal masking and a
ring-buffer KV cache for decode.

Layouts:
  activations  (B, S, D)
  q            (B, S, H, hd)
  k, v         (B, S, KV, hd)
  cache.k/v    (B, KV, T, hd)   T = seq_len (full) or window (sliding);
                                head-major, so a layer's slice is what the
                                decode attention reads, with no relayout
  cache.pos    (B, T) int32     absolute position per slot, -1 = empty

Causal self-attention over a long sequence runs as query blocks, each
scored only against its key prefix (``causal_blocks``).  Decode reads each
layer's cache where it lies and returns only the new token's K/V;
``write_cache`` puts those into the stacked caches after the layer scan,
in place when the cache is donated.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common import spans
from repro.common.types import AXIS_EMBED, AXIS_HEADS, AXIS_KV, ParamSpec
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope

NEG_INF = -1e30
# rows per query block of causal self-attention (``causal_blocks``)
CAUSAL_BLOCK = 512


def attention_spec(cfg: ModelConfig, *, cross: bool = False):
    hd = cfg.resolved_head_dim
    spec = {
        "wq": ParamSpec((cfg.d_model, cfg.num_heads * hd), (AXIS_EMBED, AXIS_HEADS)),
        "wk": ParamSpec((cfg.d_model, cfg.num_kv_heads * hd), (AXIS_EMBED, AXIS_KV)),
        "wv": ParamSpec((cfg.d_model, cfg.num_kv_heads * hd), (AXIS_EMBED, AXIS_KV)),
        "wo": ParamSpec((cfg.num_heads * hd, cfg.d_model), (AXIS_HEADS, AXIS_EMBED)),
    }
    if cfg.qkv_bias and not cross:
        spec["bq"] = ParamSpec((cfg.num_heads * hd,), (AXIS_HEADS,), init="zeros")
        spec["bk"] = ParamSpec((cfg.num_kv_heads * hd,), (AXIS_KV,), init="zeros")
        spec["bv"] = ParamSpec((cfg.num_kv_heads * hd,), (AXIS_KV,), init="zeros")
    return spec


def _project_qkv(params, cfg: ModelConfig, x, kv_input=None):
    hd = cfg.resolved_head_dim
    kv_src = x if kv_input is None else kv_input
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    k = jnp.einsum("bsd,dh->bsh", kv_src, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", kv_src, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(*q.shape[:-1], cfg.num_heads, hd)
    k = k.reshape(*k.shape[:-1], cfg.num_kv_heads, hd)
    v = v.reshape(*v.shape[:-1], cfg.num_kv_heads, hd)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B,S,H,hd), k: (B,T,KV,hd) -> scores (B,KV,G,S,T)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k) / jnp.sqrt(hd).astype(q.dtype)


def _gqa_out(weights, v, out_dtype):
    """weights: (B,KV,G,S,T), v: (B,T,KV,hd) -> (B,S,H*hd)."""
    B, KV, G, S, T = weights.shape
    hd = v.shape[-1]
    o = jnp.einsum("bkgst,btkd->bskgd", weights, v)
    return o.reshape(B, S, KV * G * hd).astype(out_dtype)


def _softmax(scores):
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1)


def causal_blocks(S: int) -> int:
    """The query blocks causal self-attention over ``S`` rows splits into:
    ``S // CAUSAL_BLOCK`` where that is two or more and exact, else 1."""
    n, rest = divmod(S, CAUSAL_BLOCK)
    return n if n >= 2 and rest == 0 else 1


def _attend_dense(q, k, v, q_pos, k_pos, *, causal, window, out_dtype):
    """Masked softmax attention over all of ``k``: scores in q's dtype, an
    f32 softmax, weights in ``out_dtype`` into the P·V einsum.  q roped
    (B,S,H,hd), positions (B,S); k, v roped (B,T,KV,hd), positions (B,T)."""
    scores = _gqa_scores(q, k)  # (B,KV,G,S,T)
    i = q_pos[:, None, None, :, None]
    j = k_pos[:, None, None, None, :]
    mask = jnp.ones(scores.shape[-2:], dtype=bool)[None, None, None]
    if causal:
        mask = mask & (j <= i)
    if window is not None:
        mask = mask & (i - j < window)
    scores = jnp.where(mask, scores, NEG_INF)
    weights = _softmax(scores).astype(out_dtype)
    return _gqa_out(weights, v, out_dtype)


def _attend_causal_blocks(q, k, v, positions, n, *, window, out_dtype):
    """Causal attention as ``n`` query blocks, each over its key prefix.

    Block ``i`` scores its rows against keys ``[0, (i+1)·S/n)`` only, with
    one exact softmax there: the keys after it, all masked, are never
    computed, so the work is ``(n+1)/(2n)`` of the dense path's.  With
    ``positions`` ascending from 0 along each row, as every caller's are,
    the result is the dense one.
    """
    b = q.shape[1] // n
    outs = []
    for i in range(n):
        lo, hi = i * b, (i + 1) * b
        outs.append(_attend_dense(
            q[:, lo:hi], k[:, :hi], v[:, :hi], positions[:, lo:hi],
            positions[:, :hi], causal=True, window=window,
            out_dtype=out_dtype))
    return jnp.concatenate(outs, axis=1)


def attend_full(
    params,
    cfg: ModelConfig,
    x,
    positions,
    *,
    causal: bool = True,
    window: Optional[int] = None,
):
    """Self-attention over a contiguous sequence (train / prefill).

    Causal attention over two or more ``CAUSAL_BLOCK``-row blocks computes
    only the blocks on and below the diagonal (``causal_blocks``); any
    other attention is one dense pass (``_attend_dense``).  Each trace
    notes its path and the share of the S × S scores it computes in
    ``spans.attention_paths()``.
    """
    q, k, v = _project_qkv(params, cfg, x)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    n = causal_blocks(q.shape[1]) if causal else 1
    if n > 1:
        spans.note_attention("causal_blocks", (n + 1) / (2 * n))
        out = _attend_causal_blocks(q, k, v, positions, n, window=window,
                                    out_dtype=x.dtype)
    else:
        spans.note_attention("dense", 1.0)
        out = _attend_dense(q, k, v, positions, positions, causal=causal,
                            window=window, out_dtype=x.dtype)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"]), (k, v)


def attend_cross(params, cfg: ModelConfig, x, memory):
    """Cross-attention (decoder query -> encoder memory); no RoPE, no mask.

    Returns (out, (k, v)) so prefill can cache the memory projections.
    """
    q, k, v = _project_qkv(params, cfg, x, kv_input=memory)
    scores = _gqa_scores(q, k)
    weights = _softmax(scores).astype(x.dtype)
    out = _gqa_out(weights, v, x.dtype)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"]), (k, v)


def attend_cross_cached(params, cfg: ModelConfig, x, xk, xv):
    """Cross-attention against precomputed memory K/V (decode path)."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(*q.shape[:-1], cfg.num_heads, hd)
    scores = _gqa_scores(q, xk)
    weights = _softmax(scores).astype(x.dtype)
    out = _gqa_out(weights, xv, x.dtype)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------


def _cache_len(cfg: ModelConfig, seq_len: int) -> int:
    return seq_len if cfg.sliding_window is None else min(cfg.sliding_window, seq_len)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    """Cache for one attention layer. T = window size when sliding."""
    T = _cache_len(cfg, seq_len)
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, T, hd), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, T, hd), dtype),
        "pos": jnp.full((batch, T), -1, jnp.int32),
    }


def cache_abstract(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    T = _cache_len(cfg, seq_len)
    hd = cfg.resolved_head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, cfg.num_kv_heads, T, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, cfg.num_kv_heads, T, hd), dtype),
        "pos": jax.ShapeDtypeStruct((batch, T), jnp.int32),
    }


def cache_slot(cfg: ModelConfig, pos, T: int):
    """The slot position ``pos`` takes in a cache of ``T`` slots: a ring
    index under a sliding window, else ``pos`` clamped to the last slot
    as ``dynamic_update_slice`` clamps it."""
    pos = jnp.asarray(pos, jnp.int32)
    if cfg.sliding_window is None:
        return jnp.minimum(pos, T - 1)
    return pos % T


def decode_step(params, cfg: ModelConfig, cache, x, pos):
    """One-token decode against one layer's cache, which it only reads.

    x: (B,1,D); pos: scalar int32 absolute position.  The query attends to
    the cache's valid entries and to the token's own K/V, joined in one
    f32 softmax.  The slot the token will take is left out: in a ring
    smaller than the window it still holds position ``pos - T``.

    Returns (out (B,1,D), {"k", "v"} each (B,KV,1,hd)): the token's
    entries, for ``write_cache``.
    """
    q, k, v = _project_qkv(params, cfg, x)
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    B, _, H, hd = q.shape
    KV, T = cache["k"].shape[1], cache["k"].shape[2]
    qg = q.reshape(B, KV, H // KV, hd)
    k = k.reshape(B, KV, 1, hd)
    v = v.reshape(B, KV, 1, hd)

    scale = jnp.sqrt(hd).astype(q.dtype)
    s_cache = jnp.einsum("bkgd,bktd->bkgt", qg, cache["k"]) / scale
    s_self = jnp.einsum("bkgd,bkud->bkgu", qg, k) / scale
    cpos = cache["pos"]
    valid = ((cpos >= 0) & (cpos < pos)
             & (jnp.arange(T) != cache_slot(cfg, pos, T)))
    if cfg.sliding_window is not None:
        valid = valid & (pos - cpos < cfg.sliding_window)
    s_cache = jnp.where(valid[:, None, None, :], s_cache, NEG_INF)
    weights = _softmax(jnp.concatenate([s_cache, s_self], axis=-1))
    weights = weights.astype(x.dtype)
    out = (jnp.einsum("bkgt,bktd->bkgd", weights[..., :T], cache["v"],
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgu,bkud->bkgd", weights[..., T:], v,
                        preferred_element_type=jnp.float32))
    out = out.reshape(B, 1, H * hd).astype(x.dtype)
    out = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    return out, {"k": k, "v": v}


def write_cache(cfg: ModelConfig, cache, new, pos):
    """Write one token's K/V into stacked caches at ``pos``'s slot.

    cache: "k", "v" (..., B, KV, T, hd) and "pos" (..., B, T); new: "k",
    "v" (..., B, KV, 1, hd), as the layer scan stacks ``decode_step``'s.
    One ``dynamic_update_slice`` per array, in place on a donated cache.
    """
    ck, cpos = cache["k"], cache["pos"]
    slot = cache_slot(cfg, pos, ck.shape[-2])
    at = (0,) * (ck.ndim - 2) + (slot, 0)
    token_pos = jnp.full(cpos.shape[:-1] + (1,), pos, jnp.int32)
    return {
        "k": jax.lax.dynamic_update_slice(ck, new["k"].astype(ck.dtype), at),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], new["v"].astype(cache["v"].dtype), at),
        "pos": jax.lax.dynamic_update_slice(
            cpos, token_pos, (0,) * (cpos.ndim - 1) + (slot,)),
    }


def fill_cache_from_prefill(cfg: ModelConfig, kv, positions, seq_len: int):
    """Build a decode cache from prefill K/V (already roped).

    kv: (k, v) each (B,S,KV,hd), stored head-major.  When sliding-window
    attention keeps only the trailing ``T`` positions, position ``p`` lands
    in ring slot ``p % T``, the slot its decode successors evict.
    """
    k, v = (jnp.swapaxes(a, 1, 2) for a in kv)  # (B,KV,S,hd)
    S = k.shape[2]
    T = _cache_len(cfg, seq_len)
    if S >= T:
        shift = S % T
        k_t = jnp.roll(k[:, :, S - T:], shift, axis=2)
        v_t = jnp.roll(v[:, :, S - T:], shift, axis=2)
        pos_t = jnp.roll(positions[:, S - T:], shift, axis=1)
    else:
        pad = T - S
        k_t = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_t = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        pos_t = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    return {"k": k_t, "v": v_t, "pos": pos_t.astype(jnp.int32)}
