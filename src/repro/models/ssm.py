"""Mamba2 (SSD) block — TPU-native chunked formulation.

The GPU reference implementation leans on warp-level parallel scans; here the
intra-chunk work is dense (Q×Q) matmuls that map onto the MXU, and only the
O(S/chunk) inter-chunk state recurrence is a (log-depth associative) scan.

Layouts:
  x_in    (B, S, D)
  x_ssm   (B, S, H, P)   H = ssm_heads, P = ssm_head_dim
  B_, C_  (B, S, N)      N = ssm_state (single group, broadcast over heads)
  dt      (B, S, H)
  state   (B, H, P, N)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.types import AXIS_EMBED, AXIS_INNER, ParamSpec
from repro.models.config import ModelConfig
from repro.models.layers import rmsnorm


def mamba2_spec(cfg: ModelConfig):
    d, inner = cfg.d_model, cfg.ssm_inner
    n, h, w = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv_width
    conv_ch = inner + 2 * n
    return {
        "w_z": ParamSpec((d, inner), (AXIS_EMBED, AXIS_INNER)),
        "w_xbc": ParamSpec((d, conv_ch), (AXIS_EMBED, AXIS_INNER)),
        "w_dt": ParamSpec((d, h), (AXIS_EMBED, None)),
        "conv_w": ParamSpec((w, conv_ch), (None, AXIS_INNER), init="lecun"),
        "conv_b": ParamSpec((conv_ch,), (AXIS_INNER,), init="zeros"),
        "A_log": ParamSpec((h,), (None,), init="zeros"),
        "D": ParamSpec((h,), (None,), init="ones"),
        "dt_bias": ParamSpec((h,), (None,), init="zeros"),
        "norm_scale": ParamSpec((inner,), (AXIS_INNER,), init="ones"),
        "out_proj": ParamSpec((inner, d), (AXIS_INNER, AXIS_EMBED)),
    }


def _causal_conv(params, xbc):
    """Depthwise causal conv, width W. xbc: (B,S,C)."""
    w = params["conv_w"]  # (W, C)
    W = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1], :] * w[i][None, None, :] for i in range(W)
    )
    return jax.nn.silu(out + params["conv_b"])


def _split_xbc(cfg: ModelConfig, xbc):
    inner, n = cfg.ssm_inner, cfg.ssm_state
    x = xbc[..., :inner]
    B_ = xbc[..., inner : inner + n]
    C_ = xbc[..., inner + n :]
    return x, B_, C_


def ssd_chunked(x, dt, A, B_, C_, chunk: int):
    """Chunked SSD scan.

    x: (B,S,H,P) fp32; dt: (B,S,H) fp32 (post-softplus); A: (H,) negative;
    B_, C_: (B,S,N) fp32.  Returns y: (B,S,H,P), final state (B,H,P,N).
    """
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nc = S // chunk
    xc = x.reshape(Bsz, nc, chunk, H, P)
    dtc = dt.reshape(Bsz, nc, chunk, H)
    Bc = B_.reshape(Bsz, nc, chunk, N)
    Cc = C_.reshape(Bsz, nc, chunk, N)

    dA = dtc * A[None, None, None, :]  # (B,nc,Q,H) negative
    cum = jnp.cumsum(dA, axis=2)  # inclusive within-chunk cumulative decay

    # intra-chunk: Y[i] = sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # (B,nc,Q,Q)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,i,j,H)
    ii = jnp.arange(chunk)
    causal = (ii[:, None] >= ii[None, :])[None, None, :, :, None]
    L = jnp.where(causal, jnp.exp(seg), 0.0)  # (B,nc,i,j,H)
    M = G[..., None] * L * dtc[:, :, None, :, :]  # (B,nc,i,j,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xc)

    # chunk-final states: S_c = sum_j exp(cum_last - cum_j) dt_j B_j x_j^T
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,Q,H)
    wj = decay_to_end * dtc  # (B,nc,Q,H)
    s_c = jnp.einsum("bcjh,bcjn,bcjhp->bchpn", wj, Bc, xc)  # (B,nc,H,P,N)

    # inter-chunk recurrence via associative scan over transforms
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H)

    def combine(a, b):
        da, sa = a
        db, sb = b
        return da * db, db[..., None, None] * sa + sb

    dscan, sscan = jax.lax.associative_scan(
        combine, (chunk_decay, s_c), axis=1
    )
    # state entering chunk c = scanned state of chunk c-1 (zero for c=0)
    s_prev = jnp.concatenate(
        [jnp.zeros_like(sscan[:, :1]), sscan[:, :-1]], axis=1
    )  # (B,nc,H,P,N)

    decay_from_start = jnp.exp(cum)  # (B,nc,Q,H)
    y_inter = jnp.einsum(
        "bcqh,bcqn,bchpn->bcqhp", decay_from_start, Cc, s_prev
    )
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, sscan[:, -1]  # final carried state (B,H,P,N)


def mamba2_apply(params, cfg: ModelConfig, x_in):
    """Full-sequence Mamba2 block.

    x_in: (B,S,D) -> (y (B,S,D), cache {"state", "conv"}) — the cache entry
    lets a prefill hand off directly to ``mamba2_step`` decode.
    """
    dt_f = jnp.float32
    z = jnp.einsum("bsd,di->bsi", x_in, params["w_z"])
    xbc_pre = jnp.einsum("bsd,dc->bsc", x_in, params["w_xbc"])
    w = cfg.ssm_conv_width
    conv_tail = jnp.pad(xbc_pre, ((0, 0), (w - 1, 0), (0, 0)))[:, -(w - 1):, :]
    xbc = _causal_conv(params, xbc_pre)
    x, B_, C_ = _split_xbc(cfg, xbc)
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    x = x.reshape(*x.shape[:2], H, P).astype(dt_f)
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x_in, params["w_dt"]).astype(dt_f)
        + params["dt_bias"].astype(dt_f)
    )
    A = -jnp.exp(params["A_log"].astype(dt_f))
    chunk = min(cfg.ssm_chunk, x.shape[1])
    y, state = ssd_chunked(x, dt, A, B_.astype(dt_f), C_.astype(dt_f), chunk)
    y = y + params["D"].astype(dt_f)[None, None, :, None] * x
    y = y.reshape(*y.shape[:2], cfg.ssm_inner).astype(x_in.dtype)
    y = rmsnorm({"scale": params["norm_scale"]}, y * jax.nn.silu(z))
    out = jnp.einsum("bsi,id->bsd", y, params["out_proj"])
    return out, {"state": state, "conv": conv_tail}


# ---------------------------------------------------------------------------
# Decode (single-token recurrent step)
# ---------------------------------------------------------------------------


def mamba2_cache_init(cfg: ModelConfig, batch: int, dtype):
    conv_ch = cfg.ssm_inner + 2 * cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_ch), dtype),
        "state": jnp.zeros(
            (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32
        ),
    }


def mamba2_cache_abstract(cfg: ModelConfig, batch: int, dtype):
    conv_ch = cfg.ssm_inner + 2 * cfg.ssm_state
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.ssm_conv_width - 1, conv_ch), dtype),
        "state": jax.ShapeDtypeStruct(
            (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32
        ),
    }


def mamba2_step(params, cfg: ModelConfig, cache, x_in):
    """Single-token step. x_in: (B,1,D) -> (B,1,D), new cache."""
    dt_f = jnp.float32
    z = jnp.einsum("bsd,di->bsi", x_in, params["w_z"])[:, 0]
    xbc_t = jnp.einsum("bsd,dc->bsc", x_in, params["w_xbc"])[:, 0]  # (B,C)
    # causal conv over ring of last W-1 inputs + current
    window = jnp.concatenate([cache["conv"], xbc_t[:, None, :]], axis=1)  # (B,W,C)
    w = params["conv_w"]  # (W,C)
    conv_out = jax.nn.silu(jnp.einsum("bwc,wc->bc", window, w) + params["conv_b"])
    new_conv = window[:, 1:]
    x, B_, C_ = _split_xbc(cfg, conv_out)
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    x = x.reshape(-1, H, P).astype(dt_f)
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x_in, params["w_dt"])[:, 0].astype(dt_f)
        + params["dt_bias"].astype(dt_f)
    )  # (B,H)
    A = -jnp.exp(params["A_log"].astype(dt_f))
    decay = jnp.exp(dt * A[None, :])  # (B,H)
    state = cache["state"] * decay[:, :, None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt, B_.astype(dt_f), x
    )
    y = jnp.einsum("bn,bhpn->bhp", C_.astype(dt_f), state)
    y = y + params["D"].astype(dt_f)[None, :, None] * x
    y = y.reshape(-1, cfg.ssm_inner).astype(x_in.dtype)
    y = rmsnorm({"scale": params["norm_scale"]}, y * jax.nn.silu(z))
    out = jnp.einsum("bi,id->bd", y, params["out_proj"])[:, None, :]
    return out, {"conv": new_conv, "state": state}
