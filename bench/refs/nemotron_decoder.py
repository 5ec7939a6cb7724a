"""Plain reference of a Nemotron decoder (Minitron: arXiv:2407.14679;
Nemotron-4: arXiv:2402.16819).

Written from the published description and the keys of a Hugging Face
``config.json`` (``model_type: nemotron``), in float32 ``jax.numpy`` at
``highest`` matmul precision, with no kernel, no cache and no batching
across requests:

    x = embed[tokens]
    each layer:  h = layernorm1p(x; w1, b1)
                 q, k, v = h Wq, h Wk, h Wv           (GQA, partial RoPE)
                 x = x + softmax(q k^T / sqrt(hd), causal) v  Wo
                 h = layernorm1p(x; w2, b2)
                 x = x + relu(h Wup)^2 Wdown
    logits = layernorm1p(x; wf, bf) Whead               (untied head)

    layernorm1p(x; w, b) = (x - mean) / sqrt(var + eps) * (1 + w) + b

RoPE rotates only the first ``partial_rotary_factor * head_dim`` dims of
each head, with Hugging Face's ``rotate_half`` inside them and the
frequencies of a head that size; the other dims pass through.  Weights
are regenerated layer by layer from the seed (``weights.py``) and rounded
to the served dtype first, so the reference sees the weights the program
served.  It imports nothing of the program.

Departures from the published model: random weights; LayerNorm1P's bias
and zero-centred scale are taken from the Nemotron-4 description (the
config names only ``norm_eps``); the computation is float32 throughout,
where the published model runs in bfloat16.

``lowp="fp8"`` is the control: every matmul's operands are rounded to
float8 e4m3 with a per-tensor scale, as an fp8 serving path would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _scale(x):
    """The per-tensor scale that maps ``x`` onto float8 e4m3's range."""
    fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)
    return jnp.maximum(jnp.max(jnp.abs(x)).astype(F32), 1e-30) / fmax


def _round(x, lowp, s=None):
    """``x`` through float8 e4m3 under the control, with the scale of the
    whole tensor (``s``, where ``x`` is a part of it)."""
    if lowp is None:
        return x
    if lowp != "fp8":
        raise ValueError(lowp)
    s = _scale(x) if s is None else s
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, lowp):
    return jnp.matmul(_round(a, lowp), _round(w.astype(F32), lowp),
                      precision=HIGHEST)


def _layernorm1p(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * (1.0 + w) + b


def _rope(x, theta, fraction):
    """x: (S, heads, hd), positions 0..S-1; rotates the first
    ``fraction * hd`` dims (Hugging Face ``rotate_half``)."""
    S, _, hd = x.shape
    rot = int(hd * fraction)
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * jnp.cos(ang) + half * jnp.sin(ang), xp], -1)


def _dims(cfg):
    d = cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, H, KV, cfg.get("head_dim") or d // H


def _layer(cfg, lowp):
    _, H, KV, hd = _dims(cfg)
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    frac = cfg["partial_rotary_factor"]

    def one(x, p):
        S = x.shape[0]
        h = _layernorm1p(x, p["ln1_w"], p["ln1_b"], eps)
        q = _rope(_mm(h, p["wq"], lowp).reshape(S, H, hd), theta, frac)
        k = _rope(_mm(h, p["wk"], lowp).reshape(S, KV, hd), theta, frac)
        v = _mm(h, p["wv"], lowp).reshape(S, KV, hd)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", _round(q, lowp), _round(k, lowp),
                       precision=HIGHEST) / np.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", _round(jax.nn.softmax(s, -1), lowp),
                       _round(v, lowp), precision=HIGHEST)
        x = x + _mm(a.reshape(S, H * hd), p["wo"], lowp)
        h = _layernorm1p(x, p["ln2_w"], p["ln2_b"], eps)
        u = jnp.square(jax.nn.relu(_mm(h, p["w_up"], lowp)))
        return x + _mm(u, p["w_down"], lowp)

    return jax.jit(jax.vmap(one, in_axes=(0, None)))


# the program's leaf paths, as the benchmark's weight maker names them
_LAYER_LEAVES = {
    "ln1_w": "blocks/ln1/weight", "ln1_b": "blocks/ln1/bias",
    "ln2_w": "blocks/ln2/weight", "ln2_b": "blocks/ln2/bias",
    "wq": "blocks/attn/wq", "wk": "blocks/attn/wk", "wv": "blocks/attn/wv",
    "wo": "blocks/attn/wo", "w_up": "blocks/mlp/wi", "w_down": "blocks/mlp/wo",
}


def layer_shapes(cfg: dict) -> dict:
    """The shape of each weight of one layer."""
    d, H, KV, hd = _dims(cfg)
    ff = cfg["intermediate_size"]
    return {"ln1_w": (d,), "ln1_b": (d,), "ln2_w": (d,), "ln2_b": (d,),
            "wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd),
            "wo": (H * hd, d), "w_up": (d, ff), "w_down": (ff, d)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, path, shape, dtype):
    return weights.leaf_value(key, path, shape).astype(dtype)


def _served(seed, path, shape, dtype, layer=None):
    """One weight as the program served it: in float32, except the
    embedding and the head, which stay in the served dtype (0.79 B values
    each) and are widened where they are used."""
    key = weights.leaf_key(seed, path)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    x = _make(key, path, tuple(shape), dtype)
    return x if layer is None and len(shape) == 2 else x.astype(F32)


@functools.partial(jax.jit, static_argnums=(3,))
def _head_cols(h, w_cols, s, lowp):
    return jnp.matmul(_round(h, lowp), _round(w_cols.astype(F32), lowp, s),
                      precision=HIGHEST)


def _head(h, w_head, lowp, cols=16_000):
    """``h @ w_head`` a slice of the vocabulary at a time, so the head is
    never widened to float32 whole; the control rounds each slice with
    the scale of the whole head."""
    s = _scale(w_head) if lowp else None
    vocab = w_head.shape[1]
    return jnp.concatenate([_head_cols(h, w_head[:, i:i + cols], s, lowp)
                            for i in range(0, vocab, cols)], axis=-1)


def logits_at(cfg: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              *, lowp=None, block: int = 1) -> np.ndarray:
    """Reference logits of each sequence in ``tokens`` (n, S) at the
    positions ``rows`` (n, r); returns (n, r, vocab) float32 on the host.

    Each layer's weights are made once and applied to ``block`` sequences
    at a time, and the head only at ``rows``, so it fits on one chip
    beside the program's weights.
    """
    dtype = jnp.dtype(cfg["torch_dtype"])
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    shapes = layer_shapes(cfg)
    step = _layer(cfg, lowp)
    table = _served(seed, "embed/table", (vocab, d), dtype)
    xs = [table[jnp.asarray(tokens[b0:b0 + block])].astype(F32)
          for b0 in range(0, len(tokens), block)]
    del table
    for layer in range(cfg["num_hidden_layers"]):
        p = {k: _served(seed, path, shapes[k], dtype, layer)
             for k, path in _LAYER_LEAVES.items()}
        xs = [step(x, p) for x in xs]
    final = [_served(seed, f"final_norm/{k}", (d,), dtype)
             for k in ("weight", "bias")]
    h = jnp.concatenate([
        jnp.take_along_axis(x, jnp.asarray(rows[b0:b0 + block])[..., None],
                            axis=1)
        for b0, x in zip(range(0, len(tokens), block), xs)])
    h = _layernorm1p(h, *final, cfg["norm_eps"])
    w_head = _served(seed, "head/w", (d, vocab), dtype)
    return np.asarray(_head(h, w_head, lowp))


def served_gaps(cfg: dict, seed: int, prompts, served, *, lowp=None,
                block: int = 1) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position: (n, new) float32, 0 where the
    served token is the reference's choice.

    With ``lowp``, the served token is replaced by the token the
    lower-precision reference puts first at the same position (the
    control reads the gap of its own choice under the float32 reference).
    """
    prompts = np.asarray(prompts)
    served = np.asarray(served)
    n, P = prompts.shape
    new = served.shape[1]
    seq = np.concatenate([prompts, served[:, :-1]], axis=1)
    rows = np.broadcast_to(np.arange(P - 1, P - 1 + new), (n, new))
    ref = logits_at(cfg, seed, seq, rows, block=block)
    pick = served
    if lowp is not None:
        pick = logits_at(cfg, seed, seq, rows, lowp=lowp,
                         block=block).argmax(-1)
    chosen = np.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    return ref.max(-1) - chosen
