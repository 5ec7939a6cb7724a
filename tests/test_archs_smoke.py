"""Per-architecture smoke tests: reduced configs (<=2-4 layers, d_model<=512,
<=4 experts) run one forward + one train step + one decode step on CPU and
assert output shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.steps import make_serve_step, make_train_step
from repro.models import build_model
from repro.models.config import INPUT_SHAPES, ShapeConfig

# minutes of per-arch compilation on CPU; excluded from the fast tier-1 loop
pytestmark = pytest.mark.slow


def _batch(cfg, B, S, key):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    if cfg.num_patches:
        batch["patches"] = jax.random.normal(
            key, (B, cfg.num_patches, cfg.d_model), jnp.bfloat16
        )
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.num_frames, cfg.d_model), jnp.bfloat16
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_limits(arch):
    cfg = get_smoke_config(arch)
    assert cfg.num_layers <= 4
    assert cfg.d_model <= 512
    assert cfg.num_experts <= 4
    assert cfg.family == get_config(arch).family


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_finite(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, S = 2, 32
    batch = _batch(cfg, B, S, key)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert np.isfinite(float(aux["moe_aux"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step(arch):
    cfg = get_smoke_config(arch)
    shape = ShapeConfig("t", 16, 4, "train", microbatches=2)
    step, model, opt = make_train_step(cfg, shape)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    opt_state = opt.init(params)
    batch = _batch(cfg, shape.global_batch, shape.seq_len, key)
    new_params, new_opt, metrics = jax.jit(step)(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    before = jax.tree_util.tree_leaves(params)[1]
    after = jax.tree_util.tree_leaves(new_params)[1]
    assert not np.allclose(np.asarray(before, np.float32), np.asarray(after, np.float32))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch):
    """Greedy decode after prefill matches teacher-forced forward logits."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(2)
    params = model.init(key)
    B, S = 2, 16
    batch = _batch(cfg, B, S, key)

    logits_all, _ = jax.jit(model.forward)(params, batch)
    last, _, cache = jax.jit(model.prefill)(params, batch)
    np.testing.assert_allclose(
        np.asarray(last[:, -1], np.float32),
        np.asarray(logits_all[:, -1], np.float32),
        rtol=2e-2, atol=2e-2,
    )
    tok = {"token": jnp.argmax(last[:, -1], -1).astype(jnp.int32)[:, None]}
    logits2, cache2 = jax.jit(model.decode)(params, cache, tok)
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32))))
    assert int(cache2["pos"]) == S + 1


@pytest.mark.parametrize("arch", ["qwen2_1_5b", "zamba2_2_7b", "xlstm_1_3b"])
def test_serve_step(arch):
    cfg = get_smoke_config(arch)
    step, model = make_serve_step(cfg)
    params = model.init(jax.random.PRNGKey(3))
    cache = model.init_cache(2, 16)
    tok, logits, cache = jax.jit(step)(
        params, cache, {"token": jnp.ones((2, 1), jnp.int32)}
    )
    assert tok.shape == (2,)
    assert int(cache["pos"]) == 1


def test_assigned_configs_exact():
    """The full configs match the assignment table exactly."""
    expect = {
        "nemotron_4_15b": (32, 6144, 48, 8, 24576, 256000),
        "deepseek_coder_33b": (62, 7168, 56, 8, 19200, 32256),
        "zamba2_2_7b": (54, 2560, 32, 32, 10240, 32000),
        "qwen3_moe_235b_a22b": (94, 4096, 64, 4, 1536, 151936),
        "chameleon_34b": (48, 8192, 64, 8, 22016, 65536),
        "llama4_scout_17b_a16e": (48, 5120, 40, 8, 8192, 202048),
        "whisper_base": (6, 512, 8, 8, 2048, 51865),
        "qwen2_1_5b": (28, 1536, 12, 2, 8960, 151936),
        "xlstm_1_3b": (48, 2048, 4, 4, 0, 50304),
        "minitron_4b": (32, 3072, 24, 8, 9216, 256000),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
               cfg.d_ff, cfg.vocab_size)
        assert got == (L, d, h, kv, ff, v), (arch, got)
    assert get_config("qwen3_moe_235b_a22b").num_experts == 128
    assert get_config("qwen3_moe_235b_a22b").experts_per_token == 8
    assert get_config("llama4_scout_17b_a16e").num_experts == 16
    assert get_config("llama4_scout_17b_a16e").experts_per_token == 1
    assert get_config("zamba2_2_7b").ssm_state == 64
    assert get_config("qwen2_1_5b").qkv_bias
    assert get_config("nemotron_4_15b").mlp_type == "squared_relu"
    # Nemotron's block: LayerNorm1P, rotary over half of each head, untied
    for arch in ("minitron_4b", "nemotron_4_15b"):
        cfg = get_config(arch)
        assert (cfg.norm, cfg.norm_eps, cfg.rope_fraction, cfg.tie_embeddings,
                cfg.rope_theta) == ("layernorm1p", 1e-5, 0.5, False, 10_000.0)
    qwen2 = get_config("qwen2_1_5b")
    assert (qwen2.norm, qwen2.norm_eps, qwen2.rope_fraction,
            qwen2.tie_embeddings) == ("rmsnorm", 1e-6, 1.0, True)


def test_input_shapes_table():
    t = INPUT_SHAPES
    assert (t["train_4k"].seq_len, t["train_4k"].global_batch) == (4096, 256)
    assert (t["prefill_32k"].seq_len, t["prefill_32k"].global_batch) == (32768, 32)
    assert (t["decode_32k"].seq_len, t["decode_32k"].global_batch) == (32768, 128)
    assert (t["long_500k"].seq_len, t["long_500k"].global_batch) == (524288, 1)
