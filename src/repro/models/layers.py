"""Shared layer primitives: norms, embeddings, output head, MLP variants,
RoPE."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.types import (
    AXIS_EMBED,
    AXIS_FF,
    AXIS_VOCAB,
    ParamSpec,
)

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(dim: int):
    return {"scale": ParamSpec((dim,), (AXIS_EMBED,), init="ones")}


def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def layernorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(dtype)


def layernorm1p_spec(dim: int):
    # the stored weight is zero-centred: the scale applied is 1 + weight
    return {
        "weight": ParamSpec((dim,), (AXIS_EMBED,), init="zeros"),
        "bias": ParamSpec((dim,), (AXIS_EMBED,), init="zeros"),
    }


def layernorm1p(params, x, eps: float = 1e-5):
    """LayerNorm with a bias whose scale is ``1 + weight`` (Nemotron)."""
    scale = 1.0 + params["weight"].astype(jnp.float32)
    return layernorm({"scale": scale, "bias": params["bias"]}, x, eps)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embedding_spec(vocab: int, dim: int):
    return {"table": ParamSpec((vocab, dim), (AXIS_VOCAB, AXIS_EMBED), init="small")}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x):
    # tied output head: logits = x @ table.T
    return jnp.einsum("...d,vd->...v", x, params["table"])


def head_spec(dim: int, vocab: int):
    """An output head of its own, stored (dim, vocab): input dim first."""
    return {"w": ParamSpec((dim, vocab), (AXIS_EMBED, AXIS_VOCAB))}


def head(params, x):
    return jnp.einsum("...d,dv->...v", x, params["w"])


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def mlp_spec(cfg_mlp_type: str, d_model: int, d_ff: int):
    if cfg_mlp_type == "swiglu":
        return {
            "wi_gate": ParamSpec((d_model, d_ff), (AXIS_EMBED, AXIS_FF)),
            "wi_up": ParamSpec((d_model, d_ff), (AXIS_EMBED, AXIS_FF)),
            "wo": ParamSpec((d_ff, d_model), (AXIS_FF, AXIS_EMBED)),
        }
    if cfg_mlp_type in ("squared_relu", "gelu"):
        return {
            "wi": ParamSpec((d_model, d_ff), (AXIS_EMBED, AXIS_FF)),
            "wo": ParamSpec((d_ff, d_model), (AXIS_FF, AXIS_EMBED)),
        }
    raise ValueError(f"unknown mlp type {cfg_mlp_type}")


def mlp_apply(mlp_type: str, params, x):
    if mlp_type == "swiglu":
        g = jnp.einsum("...d,df->...f", x, params["wi_gate"])
        u = jnp.einsum("...d,df->...f", x, params["wi_up"])
        h = jax.nn.silu(g) * u
    elif mlp_type == "squared_relu":
        h = jnp.einsum("...d,df->...f", x, params["wi"])
        h = jnp.square(jax.nn.relu(h))
    elif mlp_type == "gelu":
        h = jnp.einsum("...d,df->...f", x, params["wi"])
        h = jax.nn.gelu(h)
    else:
        raise ValueError(mlp_type)
    return jnp.einsum("...f,fd->...d", h, params["wo"])


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float, fraction: float = 1.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq).

    With ``fraction`` < 1 only the leading ``fraction * head_dim`` dims of
    each head are rotated, as a head of that size; the rest pass through.
    """
    head_dim = x.shape[-1]
    rot = int(head_dim * fraction)
    if rot < head_dim:
        assert rot % 2 == 0, (head_dim, fraction)
        return jnp.concatenate(
            [apply_rope(x[..., :rot], positions, theta), x[..., rot:]], axis=-1)
    freqs = rope_freqs(head_dim, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
