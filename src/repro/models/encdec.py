"""Encoder-decoder (Whisper-style) assembly.

The audio frontend (mel spectrogram + conv feature extractor) is a STUB per
the assignment carve-out: ``batch["frames"]`` carries precomputed frame
embeddings (B, num_frames, d_model).  This module implements the transformer
backbone: bidirectional encoder + causal decoder with cross-attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.scan import maybe_scan
from repro.common.types import init_params, stack_specs
from repro.models import attention as attn
from repro.models.config import ModelConfig
from repro.models.layers import (
    embed,
    embedding_spec,
    mlp_apply,
    mlp_spec,
    rmsnorm,
    rmsnorm_spec,
    unembed,
)
from repro.models.transformer import Model


def _enc_block_spec(cfg: ModelConfig):
    return {
        "ln1": rmsnorm_spec(cfg.d_model),
        "attn": attn.attention_spec(cfg),
        "ln2": rmsnorm_spec(cfg.d_model),
        "mlp": mlp_spec(cfg.mlp_type, cfg.d_model, cfg.d_ff),
    }


def _dec_block_spec(cfg: ModelConfig):
    return {
        "ln1": rmsnorm_spec(cfg.d_model),
        "attn": attn.attention_spec(cfg),
        "lnx": rmsnorm_spec(cfg.d_model),
        "xattn": attn.attention_spec(cfg, cross=True),
        "ln2": rmsnorm_spec(cfg.d_model),
        "mlp": mlp_spec(cfg.mlp_type, cfg.d_model, cfg.d_ff),
    }


def encdec_param_specs(cfg: ModelConfig):
    return {
        "encoder": {
            "blocks": stack_specs(_enc_block_spec(cfg), cfg.encoder_layers),
            "final_norm": rmsnorm_spec(cfg.d_model),
        },
        "decoder": {
            "embed": embedding_spec(cfg.vocab_size, cfg.d_model),
            "blocks": stack_specs(_dec_block_spec(cfg), cfg.num_layers),
            "final_norm": rmsnorm_spec(cfg.d_model),
        },
    }


def encode(params, cfg: ModelConfig, frames):
    """frames: (B, F, D) frontend-stub embeddings -> memory (B, F, D)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    B, F = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))

    def body(x, bp):
        h, _ = attn.attend_full(
            bp["attn"], cfg, rmsnorm(bp["ln1"], x), positions, causal=False
        )
        x = x + h
        x = x + mlp_apply(cfg.mlp_type, bp["mlp"], rmsnorm(bp["ln2"], x))
        return x, {}

    body = jax.checkpoint(body)
    x, _ = maybe_scan(body, x, params["encoder"]["blocks"])
    return rmsnorm(params["encoder"]["final_norm"], x)


def encdec_forward(
    params, cfg: ModelConfig, batch, *, collect_cache=False, last_logit_only=False
):
    memory = encode(params, cfg, batch["frames"])
    dec = params["decoder"]
    x = embed(dec["embed"], batch["tokens"]).astype(jnp.dtype(cfg.dtype))
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, bp):
        h, kv = attn.attend_full(
            bp["attn"], cfg, rmsnorm(bp["ln1"], x), positions,
            window=cfg.sliding_window,
        )
        x = x + h
        h, xkv = attn.attend_cross(bp["xattn"], cfg, rmsnorm(bp["lnx"], x), memory)
        x = x + h
        x = x + mlp_apply(cfg.mlp_type, bp["mlp"], rmsnorm(bp["ln2"], x))
        entry = {"kv": kv, "xkv": xkv} if collect_cache else {}
        return x, entry

    body = jax.checkpoint(body)
    x, entries = maybe_scan(body, x, dec["blocks"])
    if last_logit_only:
        x = x[:, -1:]
    x = rmsnorm(dec["final_norm"], x)
    logits = unembed(dec["embed"], x)
    aux = {"moe_aux": jnp.zeros((), jnp.float32), "moe_z": jnp.zeros((), jnp.float32)}
    if collect_cache:
        return logits, aux, (entries, positions)
    return logits, aux


def encdec_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype, *, abstract=False):
    n = cfg.num_layers
    hd = cfg.resolved_head_dim
    F = cfg.num_frames
    self_cache = (
        attn.cache_abstract(cfg, batch, seq_len, dtype)
        if abstract
        else attn.init_cache(cfg, batch, seq_len, dtype)
    )
    if abstract:
        xk = jax.ShapeDtypeStruct((batch, F, cfg.num_kv_heads, hd), dtype)
        per = {"kv": self_cache, "xk": xk, "xv": xk}
        blocks = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype), per
        )
        return {"blocks": blocks, "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    xk = jnp.zeros((batch, F, cfg.num_kv_heads, hd), dtype)
    per = {"kv": self_cache, "xk": xk, "xv": xk}
    blocks = jax.tree_util.tree_map(
        lambda x: jnp.array(jnp.broadcast_to(x, (n,) + x.shape)), per
    )
    return {"blocks": blocks, "pos": jnp.zeros((), jnp.int32)}


def encdec_prefill(params, cfg: ModelConfig, batch, cache_len=None):
    B, S = batch["tokens"].shape
    # cache_len > S so decode writes never clamp onto the last prompt slot
    cache_len = 2 * S if cache_len is None else int(cache_len)
    if cache_len <= S:
        raise ValueError(f"cache_len {cache_len} leaves no room to decode "
                         f"past the {S}-token prompt")
    logits, aux, (entries, positions) = encdec_forward(
        params, cfg, batch, collect_cache=True, last_logit_only=True
    )

    def fill(one_k, one_v):
        return attn.fill_cache_from_prefill(
            cfg, (one_k, one_v), positions, cache_len
        )

    k, v = entries["kv"]
    xk, xv = entries["xkv"]
    blocks = {"kv": jax.vmap(fill)(k, v), "xk": xk, "xv": xv}
    return logits, aux, {"blocks": blocks, "pos": jnp.asarray(S, jnp.int32)}


def encdec_decode(params, cfg: ModelConfig, cache, batch):
    """One-token decode; the layer scan only reads the self-attention cache
    and the cross-attention ``xk``/``xv``, and the token's K/V go in after."""
    dec = params["decoder"]
    x = embed(dec["embed"], batch["token"]).astype(jnp.dtype(cfg.dtype))
    pos = cache["pos"]

    def body(x, scanned):
        bp, bc = scanned
        h, token_kv = attn.decode_step(
            bp["attn"], cfg, bc["kv"], rmsnorm(bp["ln1"], x), pos
        )
        x = x + h
        h = attn.attend_cross_cached(
            bp["xattn"], cfg, rmsnorm(bp["lnx"], x), bc["xk"], bc["xv"]
        )
        x = x + h
        x = x + mlp_apply(cfg.mlp_type, bp["mlp"], rmsnorm(bp["ln2"], x))
        return x, token_kv

    x, token_kv = maybe_scan(body, x, (dec["blocks"], cache["blocks"]))
    blocks = {**cache["blocks"],
              "kv": attn.write_cache(cfg, cache["blocks"]["kv"], token_kv, pos)}
    x = rmsnorm(dec["final_norm"], x)
    logits = unembed(dec["embed"], x)
    return logits, {"blocks": blocks, "pos": pos + 1}


def build_encdec_model(cfg: ModelConfig) -> Model:
    specs = functools.partial(encdec_param_specs, cfg)

    def init(key, dtype=None):
        dt = dtype or jnp.dtype(cfg.dtype)
        return init_params(specs(), key, dtype=dt)

    return Model(
        cfg=cfg,
        param_specs=specs,
        init=init,
        forward=lambda params, batch: encdec_forward(params, cfg, batch),
        prefill=lambda params, batch, cache_len=None: encdec_prefill(
            params, cfg, batch, cache_len
        ),
        decode=lambda params, cache, batch: encdec_decode(params, cfg, cache, batch),
        init_cache=lambda batch, seq_len, dtype=None: encdec_cache(
            cfg, batch, seq_len, dtype or jnp.dtype(cfg.dtype)
        ),
        cache_abstract=lambda batch, seq_len, dtype=None: encdec_cache(
            cfg, batch, seq_len, dtype or jnp.dtype(cfg.dtype), abstract=True
        ),
    )
