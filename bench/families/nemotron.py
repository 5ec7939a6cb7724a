"""The program's ``ModelConfig`` for a Hugging Face Nemotron ``config.json``
(``model_type: nemotron``; Minitron, arXiv:2407.14679).

Nemotron's block: LayerNorm1P (a biased LayerNorm whose scale is
``1 + weight``), RoPE over the leading ``partial_rotary_factor`` of each
head, a non-gated squared-ReLU MLP, no biases, and an output head apart
from the embedding.  Any key this file cannot map is an error.
"""
from __future__ import annotations

# keys that describe the file or the deployment, not the program's model
_ABOUT = {"name", "source", "paper", "reference", "architectures",
          "assumed", "deployment", "reduced", "max_position_embeddings"}
# keys the program has one value for
_FIXED = {"model_type": "nemotron", "hidden_act": "relu2",
          "attention_bias": False, "mlp_bias": False}
_MAPPED = {"hidden_size", "intermediate_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads", "head_dim",
           "norm_eps", "partial_rotary_factor", "rope_theta",
           "tie_word_embeddings", "torch_dtype", "vocab_size"}


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the published config ``cfg``."""
    from repro.models.config import ModelConfig

    unknown = sorted(set(cfg) - _ABOUT - set(_FIXED) - _MAPPED)
    if unknown:
        raise ValueError(f"no mapping of Nemotron config keys {unknown}")
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's Nemotron block has {key} "
                             f"{want!r}, the config {cfg[key]!r}")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // heads,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mlp_type="squared_relu", rope_theta=cfg["rope_theta"],
        rope_fraction=cfg["partial_rotary_factor"], norm="layernorm1p",
        norm_eps=cfg["norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
    )
