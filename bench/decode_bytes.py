"""Bytes one decode step has to read from HBM, computed from shapes alone.

Like ``flops.py``, this counts what the mathematics needs and nothing a
program adds: every weight of the layers, the norms and the output head
once, the embedding rows of the step's tokens (a tied head reads the whole
table anyway), and the K/V of the positions already cached for each row.
No program reads less, so a share of the HBM peak built on it cannot pass
100%.

Decoder shapes use the keys of a Hugging Face ``config.json``.  A config
with ``norm_eps`` (Nemotron's LayerNorm1P) has a weight and a bias per
norm, one with ``rms_norm_eps`` a scale; the query, key and value
projections carry biases unless ``attention_bias`` is false (Qwen2's
config has no such key and its bias is assumed in its file).
"""
from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, heads, kv, cfg.get("head_dim") or d // heads


def _itemsize(cfg: dict) -> int:
    return _ITEMSIZE[cfg["torch_dtype"]]


def weight_params(cfg: dict) -> int:
    """Every weight a step reads once: layers, norms and output head."""
    d, heads, kv, hd = _dims(cfg)
    ff = cfg["intermediate_size"]
    norm = 2 * d if "norm_eps" in cfg else d
    mlp = 3 * d * ff if cfg.get("hidden_act", "silu") == "silu" else 2 * d * ff
    attn = 2 * d * heads * hd + 2 * d * kv * hd
    if cfg.get("attention_bias", True):
        attn += (heads + 2 * kv) * hd
    layer = attn + mlp + 2 * norm
    return (cfg["num_hidden_layers"] * layer + norm
            + d * cfg["vocab_size"])


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one cached position of one row, over all layers."""
    _, _, kv, hd = _dims(cfg)
    return 2 * cfg["num_hidden_layers"] * kv * hd * _itemsize(cfg)


def step_bytes(cfg: dict, rows: int, pos: int) -> int:
    """One decode step of ``rows`` rows whose token goes to cache position
    ``pos``: positions ``0..pos-1`` are read from the cache."""
    embed_rows = 0 if cfg["tie_word_embeddings"] else rows * cfg["hidden_size"]
    return ((weight_params(cfg) + embed_rows) * _itemsize(cfg)
            + rows * pos * kv_bytes_per_position(cfg))
