"""Decoder-only model assembly for dense / moe / vlm / hybrid / xlstm
families, with scan-over-layers (stacked params), remat, KV-cache
prefill and single-token decode.

Layer organization: the stack is grouped into ``n_super`` scanned
"super-blocks":

  dense/moe/vlm : 1 block per super-block (n_super = num_layers)
  hybrid(zamba2): ``attn_every`` Mamba2 blocks + one application of a
                  SHARED attention+MLP block (weights reused across
                  super-blocks, separate KV cache per application)
  xlstm         : (slstm_every-1) mLSTM blocks + 1 sLSTM block
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.common.scan import maybe_scan
from repro.common.types import (
    init_params,
    stack_specs,
)
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.config import ModelConfig
from repro.models.layers import (
    embed,
    embedding_spec,
    head,
    head_spec,
    layernorm1p,
    layernorm1p_spec,
    mlp_apply,
    mlp_spec,
    rmsnorm,
    rmsnorm_spec,
    unembed,
)


class Model(NamedTuple):
    cfg: ModelConfig
    param_specs: Callable[[], Any]
    init: Callable[..., Any]
    forward: Callable[..., Any]  # (params, batch) -> (logits, aux)
    prefill: Callable[..., Any]  # (params, batch) -> (logits, cache)
    decode: Callable[..., Any]  # (params, cache, batch) -> (logits, cache)
    init_cache: Callable[..., Any]
    cache_abstract: Callable[..., Any]


# ---------------------------------------------------------------------------
# Norms and output head, as the config names them
# ---------------------------------------------------------------------------


def _norm_spec(cfg: ModelConfig):
    if cfg.norm == "layernorm1p":
        return layernorm1p_spec(cfg.d_model)
    if cfg.norm == "rmsnorm":
        return rmsnorm_spec(cfg.d_model)
    raise ValueError(f"unknown norm {cfg.norm!r}")


def _norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layernorm1p":
        return layernorm1p(p, x, cfg.norm_eps)
    return rmsnorm(p, x, cfg.norm_eps)


def _logits(cfg: ModelConfig, params, x):
    """The final norm, then the tied head or the model's own."""
    x = _norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    return head(params["head"], x)


# ---------------------------------------------------------------------------
# Block specs
# ---------------------------------------------------------------------------


def _attn_block_spec(cfg: ModelConfig):
    spec = {
        "ln1": _norm_spec(cfg),
        "attn": attn.attention_spec(cfg),
        "ln2": _norm_spec(cfg),
    }
    if cfg.is_moe:
        spec["moe"] = moe_lib.moe_spec(cfg)
    else:
        spec["mlp"] = mlp_spec(cfg.mlp_type, cfg.d_model, cfg.d_ff)
    return spec


def _super_block_spec(cfg: ModelConfig):
    if cfg.family in ("dense", "moe", "vlm"):
        return _attn_block_spec(cfg)
    if cfg.family == "hybrid":
        return {
            "mamba": stack_specs(
                {
                    "ln": rmsnorm_spec(cfg.d_model),
                    "mixer": ssm_lib.mamba2_spec(cfg),
                },
                cfg.attn_every,
            )
        }
    if cfg.family == "ssm" and cfg.block_type == "xlstm":
        k = cfg.slstm_every
        return {
            "mlstm": stack_specs(xlstm_lib.mlstm_spec(cfg), k - 1),
            "slstm": xlstm_lib.slstm_spec(cfg),
        }
    raise ValueError(f"unsupported family {cfg.family}")


def _n_super(cfg: ModelConfig) -> int:
    if cfg.family in ("dense", "moe", "vlm"):
        return cfg.num_layers
    if cfg.family == "hybrid":
        assert cfg.num_layers % cfg.attn_every == 0
        return cfg.num_layers // cfg.attn_every
    if cfg.family == "ssm":
        assert cfg.num_layers % cfg.slstm_every == 0
        return cfg.num_layers // cfg.slstm_every
    raise ValueError(cfg.family)


def decoder_param_specs(cfg: ModelConfig):
    specs = {
        "embed": embedding_spec(cfg.vocab_size, cfg.d_model),
        "final_norm": _norm_spec(cfg),
        "blocks": stack_specs(_super_block_spec(cfg), _n_super(cfg)),
    }
    if not cfg.tie_embeddings:
        specs["head"] = head_spec(cfg.d_model, cfg.vocab_size)
    if cfg.family == "hybrid":
        specs["shared_attn"] = _attn_block_spec(
            cfg.replace(num_experts=0)  # shared block is dense attn+mlp
        )
    return specs


# ---------------------------------------------------------------------------
# Block application (full sequence)
# ---------------------------------------------------------------------------


def _attn_block_apply(p, cfg: ModelConfig, x, positions, *, window):
    h, kv = attn.attend_full(
        p["attn"], cfg, _norm(cfg, p["ln1"], x), positions, window=window
    )
    x = x + h
    losses = {}
    if "moe" in p:
        y, losses = moe_lib.moe_apply(p["moe"], cfg, _norm(cfg, p["ln2"], x))
    else:
        y = mlp_apply(cfg.mlp_type, p["mlp"], _norm(cfg, p["ln2"], x))
    x = x + y
    return x, kv, losses


def _zero_losses():
    return {"moe_aux": jnp.zeros((), jnp.float32), "moe_z": jnp.zeros((), jnp.float32)}


def _super_apply(cfg: ModelConfig, shared, p, x, positions, *, window, collect: bool):
    """Apply one super-block (full sequence). Returns (x, cache_entry, losses)."""
    if cfg.family in ("dense", "moe", "vlm"):
        x, kv, losses = _attn_block_apply(p, cfg, x, positions, window=window)
        losses = {**_zero_losses(), **losses}
        return x, ({"kv": kv} if collect else {}), losses

    if cfg.family == "hybrid":

        def mamba_body(carry, mp):
            h, state = ssm_lib.mamba2_apply(mp["mixer"], cfg, rmsnorm(mp["ln"], carry))
            return carry + h, (state if collect else 0.0)

        x, states = maybe_scan(mamba_body, x, p["mamba"])
        x, kv, _ = _attn_block_apply(shared, cfg, x, positions, window=window)
        entry = {"kv": kv, "ssm": states} if collect else {}
        return x, entry, _zero_losses()

    if cfg.family == "ssm":

        def mlstm_body(carry, mp):
            if collect:
                h, st = xlstm_lib.mlstm_apply(mp, cfg, carry, return_state=True)
                return carry + h, st
            return carry + xlstm_lib.mlstm_apply(mp, cfg, carry), 0.0

        x, mstates = maybe_scan(mlstm_body, x, p["mlstm"])
        if collect:
            h, sstate = xlstm_lib.slstm_apply(p["slstm"], cfg, x, return_state=True)
            x = x + h
            return x, {"mlstm": mstates, "slstm": sstate}, _zero_losses()
        x = x + xlstm_lib.slstm_apply(p["slstm"], cfg, x)
        return x, {}, _zero_losses()

    raise ValueError(cfg.family)


def _fuse_inputs(cfg: ModelConfig, params, batch):
    """Token embedding + (VLM) early-fusion patch override."""
    x = embed(params["embed"], batch["tokens"])
    if cfg.num_patches and "patches" in batch:
        p = batch["patches"].astype(x.dtype)  # (B, P, D) frontend-stub output
        npatch = min(cfg.num_patches, x.shape[1])
        x = jnp.concatenate([p[:, :npatch], x[:, npatch:]], axis=1)
    return x


def decoder_forward(
    params,
    cfg: ModelConfig,
    batch,
    *,
    collect_cache: bool = False,
    last_logit_only: bool = False,
):
    """Full-sequence forward. Returns (logits, aux) or (logits, aux, cache_kv)."""
    x = _fuse_inputs(cfg, params, batch).astype(jnp.dtype(cfg.dtype))
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    window = cfg.sliding_window
    shared = params.get("shared_attn")

    def body(carry, bp):
        x, aux, z = carry
        x, entry, losses = _super_apply(
            cfg, shared, bp, x, positions, window=window, collect=collect_cache
        )
        return (x, aux + losses["moe_aux"], z + losses["moe_z"]), entry

    body = jax.checkpoint(body)

    (x, aux, z), kvs = maybe_scan(
        body, (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), params["blocks"]
    )
    if last_logit_only:
        x = x[:, -1:]
    logits = _logits(cfg, params, x)
    aux_out = {"moe_aux": aux, "moe_z": z}
    if collect_cache:
        return logits, aux_out, (kvs, positions)
    return logits, aux_out


# ---------------------------------------------------------------------------
# Cache init / prefill / decode
# ---------------------------------------------------------------------------


def _super_cache_init(cfg: ModelConfig, batch: int, seq_len: int, dtype, abstract: bool):
    a = abstract
    if cfg.family in ("dense", "moe", "vlm"):
        f = attn.cache_abstract if a else attn.init_cache
        return {"kv": f(cfg, batch, seq_len, dtype)}
    if cfg.family == "hybrid":
        fa = attn.cache_abstract if a else attn.init_cache
        fm = ssm_lib.mamba2_cache_abstract if a else ssm_lib.mamba2_cache_init

        def stack(tree, n):
            if a:
                return jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype), tree
                )
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree
            )

        return {
            "kv": fa(cfg, batch, seq_len, dtype),
            "ssm": stack(fm(cfg, batch, dtype), cfg.attn_every),
        }
    if cfg.family == "ssm":
        fm = xlstm_lib.mlstm_cache_abstract if a else xlstm_lib.mlstm_cache_init
        fs = xlstm_lib.slstm_cache_abstract if a else xlstm_lib.slstm_cache_init

        def stack(tree, n):
            if a:
                return jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype), tree
                )
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree
            )

        return {
            "mlstm": stack(fm(cfg, batch, dtype), cfg.slstm_every - 1),
            "slstm": fs(cfg, batch, dtype),
        }
    raise ValueError(cfg.family)


def decoder_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype, *, abstract=False):
    n = _n_super(cfg)
    per = _super_cache_init(cfg, batch, seq_len, dtype, abstract)
    if abstract:
        blocks = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype), per
        )
        return {
            "blocks": blocks,
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
    blocks = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), per)
    blocks = jax.tree_util.tree_map(jnp.array, blocks)  # materialize broadcast
    return {"blocks": blocks, "pos": jnp.zeros((), jnp.int32)}


def decoder_prefill(params, cfg: ModelConfig, batch, cache_len=None):
    """Run the full sequence and return (last-token logits, aux, decode cache).

    ``cache_len`` sizes the decode KV cache (default ``2 * S``).  It must
    exceed the prompt length: a cache sized exactly ``S`` has no slot for
    generated tokens, and ``dynamic_update_slice`` would silently clamp the
    first decode write onto the last prompt token's K/V.
    """
    B, S = batch["tokens"].shape
    cache_len = 2 * S if cache_len is None else int(cache_len)
    if cache_len <= S:
        raise ValueError(f"cache_len {cache_len} leaves no room to decode "
                         f"past the {S}-token prompt")
    logits, aux, (kvs, positions) = decoder_forward(
        params, cfg, batch, collect_cache=True, last_logit_only=True
    )

    def to_cache(entry):
        out = dict(entry)
        if "kv" in entry:
            k, v = entry["kv"]

            def fill(one_k, one_v):
                return attn.fill_cache_from_prefill(
                    cfg, (one_k, one_v), positions, cache_len
                )

            out["kv"] = jax.vmap(fill)(k, v)
        return out

    blocks = to_cache(kvs)
    return logits, aux, {"blocks": blocks, "pos": jnp.asarray(S, jnp.int32)}


def _super_decode(cfg: ModelConfig, shared, p, cache, x, pos):
    """Single-token decode through one super-block.

    Returns (x, new): each attention block's token K/V under "kv", and
    the recurrent states, which a step replaces whole.
    """
    if cfg.family in ("dense", "moe", "vlm"):
        h, token_kv = attn.decode_step(
            p["attn"], cfg, cache["kv"], _norm(cfg, p["ln1"], x), pos
        )
        x = x + h
        if "moe" in p:
            y, _ = moe_lib.moe_apply(p["moe"], cfg, _norm(cfg, p["ln2"], x))
        else:
            y = mlp_apply(cfg.mlp_type, p["mlp"], _norm(cfg, p["ln2"], x))
        return x + y, {"kv": token_kv}

    if cfg.family == "hybrid":

        def mamba_body(carry, scanned):
            mp, mc = scanned
            h, new_c = ssm_lib.mamba2_step(mp["mixer"], cfg, mc, rmsnorm(mp["ln"], carry))
            return carry + h, new_c

        x, new_ssm = maybe_scan(mamba_body, x, (p["mamba"], cache["ssm"]))
        h, token_kv = attn.decode_step(
            shared["attn"], cfg, cache["kv"], _norm(cfg, shared["ln1"], x), pos
        )
        x = x + h
        y = mlp_apply(cfg.mlp_type, shared["mlp"], _norm(cfg, shared["ln2"], x))
        return x + y, {"kv": token_kv, "ssm": new_ssm}

    if cfg.family == "ssm":

        def mlstm_body(carry, scanned):
            mp, mc = scanned
            h, new_c = xlstm_lib.mlstm_step(mp, cfg, mc, carry)
            return carry + h, new_c

        x, new_m = maybe_scan(mlstm_body, x, (p["mlstm"], cache["mlstm"]))
        h, new_s = xlstm_lib.slstm_step(p["slstm"], cfg, cache["slstm"], x)
        return x + h, {"mlstm": new_m, "slstm": new_s}

    raise ValueError(cfg.family)


def decoder_decode(params, cfg: ModelConfig, cache, batch):
    """One-token decode. batch: {"token": (B,1)}. Returns (logits, cache).

    The layer scan reads the cache; the token's K/V go in after it.
    """
    x = embed(params["embed"], batch["token"]).astype(jnp.dtype(cfg.dtype))
    pos = cache["pos"]
    shared = params.get("shared_attn")

    def body(x, scanned):
        bp, bc = scanned
        return _super_decode(cfg, shared, bp, bc, x, pos)

    x, new = maybe_scan(body, x, (params["blocks"], cache["blocks"]))
    blocks = {**cache["blocks"], **new}
    if "kv" in new:
        blocks["kv"] = attn.write_cache(cfg, cache["blocks"]["kv"], new["kv"], pos)
    logits = _logits(cfg, params, x)
    return logits, {"blocks": blocks, "pos": pos + 1}


# ---------------------------------------------------------------------------
# Public constructor
# ---------------------------------------------------------------------------


def build_decoder_model(cfg: ModelConfig) -> Model:
    specs = functools.partial(decoder_param_specs, cfg)

    def init(key, dtype=None):
        dt = dtype or jnp.dtype(cfg.dtype)
        return init_params(specs(), key, dtype=dt)

    return Model(
        cfg=cfg,
        param_specs=specs,
        init=init,
        forward=lambda params, batch: decoder_forward(params, cfg, batch),
        prefill=lambda params, batch, cache_len=None: decoder_prefill(
            params, cfg, batch, cache_len
        ),
        decode=lambda params, cache, batch: decoder_decode(params, cfg, cache, batch),
        init_cache=lambda batch, seq_len, dtype=None: decoder_cache(
            cfg, batch, seq_len, dtype or jnp.dtype(cfg.dtype)
        ),
        cache_abstract=lambda batch, seq_len, dtype=None: decoder_cache(
            cfg, batch, seq_len, dtype or jnp.dtype(cfg.dtype), abstract=True
        ),
    )
