"""xLSTM blocks: mLSTM (matrix memory, parallel train form) and sLSTM
(scalar memory, exponential gating, recurrent via lax.scan).

Follows arXiv:2405.04517.  The mLSTM parallel form is attention-shaped
(Q·Kᵀ ⊙ gate-decay matrix) and maps onto the MXU; the sLSTM is inherently
sequential (recurrent gate dependence on h_{t-1}) and uses lax.scan — the
paper's own CUDA kernel is sequential per-head too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.types import AXIS_EMBED, AXIS_HEADS, AXIS_INNER, ParamSpec
from repro.models.config import ModelConfig
from repro.models.layers import rmsnorm

NEG_INF = -1e30


def _inner(cfg: ModelConfig) -> int:
    # mLSTM up-projection width (multiple of heads)
    u = int(cfg.xlstm_proj_factor * cfg.d_model)
    return -(-u // cfg.num_heads) * cfg.num_heads


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------


def mlstm_spec(cfg: ModelConfig):
    d, u, h = cfg.d_model, _inner(cfg), cfg.num_heads
    return {
        "norm_scale": ParamSpec((d,), (AXIS_EMBED,), init="ones"),
        "w_up": ParamSpec((d, 2 * u), (AXIS_EMBED, AXIS_INNER)),
        "wq": ParamSpec((u, u), (AXIS_INNER, AXIS_HEADS)),
        "wk": ParamSpec((u, u), (AXIS_INNER, AXIS_HEADS)),
        "wv": ParamSpec((u, u), (AXIS_INNER, AXIS_HEADS)),
        "w_i": ParamSpec((u, h), (AXIS_INNER, None), init="small"),
        "w_f": ParamSpec((u, h), (AXIS_INNER, None), init="small"),
        "b_i": ParamSpec((h,), (None,), init="zeros"),
        "b_f": ParamSpec((h,), (None,), init="ones"),
        "out_norm_scale": ParamSpec((u,), (AXIS_INNER,), init="ones"),
        "w_down": ParamSpec((u, d), (AXIS_INNER, AXIS_EMBED)),
    }


def mlstm_parallel(q, k, v, i_pre, f_pre):
    """Stabilized parallel mLSTM.

    q,k,v: (B,S,H,dh) fp32; i_pre,f_pre: (B,S,H) pre-activation gates.
    Returns h: (B,S,H,dh).
    """
    B, S, H, dh = q.shape
    logf = jax.nn.log_sigmoid(f_pre)  # (B,S,H)
    a = jnp.cumsum(logf, axis=1)  # inclusive
    # Dtil[t,s] = a_t - a_s + i_s  for s<=t
    dtil = a[:, :, None, :] - a[:, None, :, :] + i_pre[:, None, :, :]
    tt = jnp.arange(S)
    causal = (tt[:, None] >= tt[None, :])[None, :, :, None]
    dtil = jnp.where(causal, dtil, NEG_INF)
    m = jnp.max(dtil, axis=2, keepdims=True)  # (B,S,1,H)
    dmat = jnp.exp(dtil - m)  # (B,S,S,H)
    scores = jnp.einsum("bthd,bshd->btsh", q, k) / jnp.sqrt(dh)
    c = scores * dmat
    norm = jnp.maximum(jnp.abs(jnp.sum(c, axis=2)), jnp.exp(-m[:, :, 0, :]))
    h = jnp.einsum("btsh,bshd->bthd", c, v) / (norm[..., None] + 1e-6)
    return h


def mlstm_final_state(q_unused, k, v, i_pre, f_pre):
    """Final (C, n, m) after the whole sequence — matches ``mlstm_step``'s
    stabilized recurrence unrolled (used for prefill→decode handoff)."""
    dh = k.shape[-1]
    logf = jax.nn.log_sigmoid(f_pre)
    a = jnp.cumsum(logf, axis=1)  # (B,S,H)
    w_log = a[:, -1:, :] - a + i_pre  # (B,S,H): a_T - a_s + i_s
    m = jnp.max(w_log, axis=1)  # (B,H)
    w = jnp.exp(w_log - m[:, None, :])  # (B,S,H)
    k_s = k / jnp.sqrt(dh)
    C = jnp.einsum("bsh,bshd,bshe->bhde", w, v, k_s)
    n = jnp.einsum("bsh,bshd->bhd", w, k_s)
    return {"C": C, "n": n, "m": m}


def mlstm_apply(params, cfg: ModelConfig, x, *, return_state: bool = False):
    """x: (B,S,D) -> (B,S,D) (residual applied by caller)."""
    B, S, D = x.shape
    u, H = _inner(cfg), cfg.num_heads
    dh = u // H
    xn = rmsnorm({"scale": params["norm_scale"]}, x)
    up = jnp.einsum("bsd,du->bsu", xn, params["w_up"])
    x_in, z = jnp.split(up, 2, axis=-1)
    f32 = jnp.float32
    q = jnp.einsum("bsu,uv->bsv", x_in, params["wq"]).reshape(B, S, H, dh).astype(f32)
    k = jnp.einsum("bsu,uv->bsv", x_in, params["wk"]).reshape(B, S, H, dh).astype(f32)
    v = jnp.einsum("bsu,uv->bsv", x_in, params["wv"]).reshape(B, S, H, dh).astype(f32)
    i_pre = (jnp.einsum("bsu,uh->bsh", x_in, params["w_i"]) + params["b_i"]).astype(f32)
    f_pre = (jnp.einsum("bsu,uh->bsh", x_in, params["w_f"]) + params["b_f"]).astype(f32)
    h = mlstm_parallel(q, k, v, i_pre, f_pre).reshape(B, S, u).astype(x.dtype)
    h = rmsnorm({"scale": params["out_norm_scale"]}, h)
    h = h * jax.nn.silu(z)
    out = jnp.einsum("bsu,ud->bsd", h, params["w_down"])
    if return_state:
        return out, mlstm_final_state(q, k, v, i_pre, f_pre)
    return out


def mlstm_cache_init(cfg: ModelConfig, batch: int, dtype):
    u, H = _inner(cfg), cfg.num_heads
    dh = u // H
    return {
        "C": jnp.zeros((batch, H, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, H, dh), jnp.float32),
        "m": jnp.zeros((batch, H), jnp.float32),
    }


def mlstm_cache_abstract(cfg: ModelConfig, batch: int, dtype):
    u, H = _inner(cfg), cfg.num_heads
    dh = u // H
    return {
        "C": jax.ShapeDtypeStruct((batch, H, dh, dh), jnp.float32),
        "n": jax.ShapeDtypeStruct((batch, H, dh), jnp.float32),
        "m": jax.ShapeDtypeStruct((batch, H), jnp.float32),
    }


def mlstm_step(params, cfg: ModelConfig, cache, x):
    """Single-token recurrent mLSTM. x: (B,1,D)."""
    B = x.shape[0]
    u, H = _inner(cfg), cfg.num_heads
    dh = u // H
    f32 = jnp.float32
    xn = rmsnorm({"scale": params["norm_scale"]}, x)[:, 0]
    up = jnp.einsum("bd,du->bu", xn, params["w_up"])
    x_in, z = jnp.split(up, 2, axis=-1)
    q = jnp.einsum("bu,uv->bv", x_in, params["wq"]).reshape(B, H, dh).astype(f32)
    k = jnp.einsum("bu,uv->bv", x_in, params["wk"]).reshape(B, H, dh).astype(f32)
    v = jnp.einsum("bu,uv->bv", x_in, params["wv"]).reshape(B, H, dh).astype(f32)
    i_pre = (jnp.einsum("bu,uh->bh", x_in, params["w_i"]) + params["b_i"]).astype(f32)
    f_pre = (jnp.einsum("bu,uh->bh", x_in, params["w_f"]) + params["b_f"]).astype(f32)
    logf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(logf + cache["m"], i_pre)
    i_s = jnp.exp(i_pre - m_new)
    f_s = jnp.exp(logf + cache["m"] - m_new)
    k_s = k / jnp.sqrt(dh)
    C = cache["C"] * f_s[..., None, None] + i_s[..., None, None] * jnp.einsum(
        "bhd,bhe->bhde", v, k_s
    )
    n = cache["n"] * f_s[..., None] + i_s[..., None] * k_s
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, q)), jnp.exp(-m_new))
    h = jnp.einsum("bhde,bhe->bhd", C, q) / (denom[..., None] + 1e-6)
    h = h.reshape(B, u).astype(x.dtype)
    h = rmsnorm({"scale": params["out_norm_scale"]}, h)
    h = h * jax.nn.silu(z)
    out = jnp.einsum("bu,ud->bd", h, params["w_down"])[:, None]
    return out, {"C": C, "n": n, "m": m_new}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


def slstm_spec(cfg: ModelConfig):
    d, h = cfg.d_model, cfg.num_heads
    dh = d // h
    gates = {}
    for g in ("z", "i", "f", "o"):
        gates[f"w_{g}"] = ParamSpec((d, d), (AXIS_EMBED, AXIS_HEADS))
        gates[f"r_{g}"] = ParamSpec((h, dh, dh), (None, None, None), init="small")
        gates[f"b_{g}"] = ParamSpec(
            (d,), (AXIS_HEADS,), init="ones" if g == "f" else "zeros"
        )
    ff = int(4 / 3 * d)
    return {
        "norm_scale": ParamSpec((d,), (AXIS_EMBED,), init="ones"),
        **gates,
        "out_norm_scale": ParamSpec((d,), (AXIS_EMBED,), init="ones"),
        "ff_gate": ParamSpec((d, ff), (AXIS_EMBED, AXIS_INNER)),
        "ff_up": ParamSpec((d, ff), (AXIS_EMBED, AXIS_INNER)),
        "ff_down": ParamSpec((ff, d), (AXIS_INNER, AXIS_EMBED)),
    }


def _slstm_cell(params, cfg: ModelConfig, carry, pre):
    """One sLSTM timestep. pre: dict of gate pre-activations (B,H,dh)."""
    c, n, m, h_prev = carry
    H = cfg.num_heads

    def rec(g):
        return jnp.einsum("bhd,hde->bhe", h_prev, params[f"r_{g}"])

    z = jnp.tanh(pre["z"] + rec("z"))
    o = jax.nn.sigmoid(pre["o"] + rec("o"))
    i_pre = pre["i"] + rec("i")
    f_pre = pre["f"] + rec("f")
    logf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(logf + m, i_pre)
    i_s = jnp.exp(i_pre - m_new)
    f_s = jnp.exp(logf + m - m_new)
    c_new = f_s * c + i_s * z
    n_new = f_s * n + i_s
    h_new = o * c_new / (n_new + 1e-6)
    return (c_new, n_new, m_new, h_new), h_new


def slstm_apply(params, cfg: ModelConfig, x, *, return_state: bool = False):
    """x: (B,S,D) -> (B,S,D). Sequential scan over time."""
    B, S, D = x.shape
    H = cfg.num_heads
    dh = D // H
    f32 = jnp.float32
    xn = rmsnorm({"scale": params["norm_scale"]}, x)
    pre = {
        g: (
            jnp.einsum("bsd,de->bse", xn, params[f"w_{g}"]) + params[f"b_{g}"]
        ).reshape(B, S, H, dh).astype(f32)
        for g in ("z", "i", "f", "o")
    }
    carry = (
        jnp.zeros((B, H, dh), f32),
        jnp.zeros((B, H, dh), f32),
        jnp.zeros((B, H, dh), f32),
        jnp.zeros((B, H, dh), f32),
    )

    def step(carry, pre_t):
        return _slstm_cell(params, cfg, carry, pre_t)

    pre_t = jax.tree_util.tree_map(lambda a: jnp.moveaxis(a, 1, 0), pre)
    (c, n, m, h_last), hs = jax.lax.scan(step, carry, pre_t)
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, D).astype(x.dtype)
    h = rmsnorm({"scale": params["out_norm_scale"]}, h)
    g = jnp.einsum("bsd,df->bsf", h, params["ff_gate"])
    u = jnp.einsum("bsd,df->bsf", h, params["ff_up"])
    out = jnp.einsum("bsf,fd->bsd", jax.nn.gelu(g) * u, params["ff_down"])
    if return_state:
        return out, {"c": c, "n": n, "m": m, "h": h_last}
    return out


def slstm_cache_init(cfg: ModelConfig, batch: int, dtype):
    H = cfg.num_heads
    dh = cfg.d_model // H
    z = jnp.zeros((batch, H, dh), jnp.float32)
    return {"c": z, "n": z, "m": z, "h": z}


def slstm_cache_abstract(cfg: ModelConfig, batch: int, dtype):
    H = cfg.num_heads
    dh = cfg.d_model // H
    s = jax.ShapeDtypeStruct((batch, H, dh), jnp.float32)
    return {"c": s, "n": s, "m": s, "h": s}


def slstm_step(params, cfg: ModelConfig, cache, x):
    """Single-token sLSTM step. x: (B,1,D)."""
    B, _, D = x.shape
    H = cfg.num_heads
    dh = D // H
    f32 = jnp.float32
    xn = rmsnorm({"scale": params["norm_scale"]}, x)[:, 0]
    pre = {
        g: (
            jnp.einsum("bd,de->be", xn, params[f"w_{g}"]) + params[f"b_{g}"]
        ).reshape(B, H, dh).astype(f32)
        for g in ("z", "i", "f", "o")
    }
    carry = (cache["c"], cache["n"], cache["m"], cache["h"])
    (c, n, m, h_carry), h = _slstm_cell(params, cfg, carry, pre)
    h = h.reshape(B, D).astype(x.dtype)
    h = rmsnorm({"scale": params["out_norm_scale"]}, h)
    g = jnp.einsum("bd,df->bf", h, params["ff_gate"])
    u = jnp.einsum("bd,df->bf", h, params["ff_up"])
    out = jnp.einsum("bf,fd->bd", jax.nn.gelu(g) * u, params["ff_down"])[:, None]
    return out, {"c": c, "n": n, "m": m, "h": h_carry}
