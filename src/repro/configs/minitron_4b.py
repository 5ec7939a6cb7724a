"""Minitron 4B — pruned and distilled Nemotron-4 [arXiv:2407.14679].

Nemotron's block (nvidia/Minitron-4B-Base ``config.json``): GQA 24:8,
non-gated squared-ReLU MLP, LayerNorm1P (a biased LayerNorm whose scale is
``1 + weight``), RoPE over the first half of each head, and an output head
apart from the embedding.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="minitron-4b",
    family="dense",
    num_layers=32,
    d_model=3072,
    num_heads=24,
    num_kv_heads=8,
    head_dim=128,
    d_ff=9216,
    vocab_size=256000,
    mlp_type="squared_relu",
    rope_theta=10_000.0,
    rope_fraction=0.5,
    norm="layernorm1p",
    norm_eps=1e-5,
    tie_embeddings=False,
)

SMOKE = CONFIG.replace(
    name="minitron-4b-smoke",
    num_layers=2,
    d_model=256,
    num_heads=8,
    num_kv_heads=2,
    head_dim=32,
    d_ff=1024,
    vocab_size=512,
)
