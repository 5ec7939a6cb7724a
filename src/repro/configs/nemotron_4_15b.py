"""Nemotron-4 15B — dense, GQA, squared-ReLU MLP [arXiv:2402.16819].

The block Minitron 4B was pruned from: LayerNorm1P, RoPE over the first
half of each head, untied input and output embeddings, no biases.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="nemotron-4-15b",
    family="dense",
    num_layers=32,
    d_model=6144,
    num_heads=48,
    num_kv_heads=8,
    head_dim=128,
    d_ff=24576,
    vocab_size=256000,
    mlp_type="squared_relu",
    rope_theta=10_000.0,
    rope_fraction=0.5,
    norm="layernorm1p",
    norm_eps=1e-5,
    tie_embeddings=False,
)

SMOKE = CONFIG.replace(
    name="nemotron-4-15b-smoke",
    num_layers=2,
    d_model=256,
    num_heads=8,
    num_kv_heads=2,
    head_dim=32,
    d_ff=1024,
    vocab_size=512,
)
