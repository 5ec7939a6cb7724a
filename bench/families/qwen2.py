"""The program's ``ModelConfig`` for a Hugging Face Qwen2 ``config.json``
(``model_type: qwen2``, arXiv:2407.10671).

The serving driver picks this file by the config's ``model_type``; a new
decoder family brings a file of its own beside it.
"""
from __future__ import annotations


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the published config ``cfg``."""
    from repro.models.config import ModelConfig

    if not cfg["tie_word_embeddings"]:
        raise ValueError("the program's Qwen2 decoder ties its embedding "
                         "and output head")
    if cfg["rms_norm_eps"] != 1e-6 or cfg["hidden_act"] != "silu":
        raise ValueError("the program's decoder fixes rmsnorm eps 1e-6 "
                         "and SwiGLU")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // heads,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mlp_type="swiglu", qkv_bias=True, rope_theta=cfg["rope_theta"],
        dtype=cfg["torch_dtype"],
    )
