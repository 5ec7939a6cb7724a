"""Nemotron's block in the program: LayerNorm1P, rotary over part of each
head, and an output head apart from the embedding.  The defaults of the
new ``ModelConfig`` fields leave every other model as it was."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.common.types import ParamSpec
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.models.layers import apply_rope, layernorm1p, rope_freqs
from repro.sharding.rules import param_pspecs


def _old_apply_rope(x, positions, theta):
    """``apply_rope`` as it was before it took a fraction."""
    freqs = rope_freqs(x.shape[-1], theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _qk(dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 3, 32), dtype)
    positions = jnp.broadcast_to(jnp.arange(7) + 5, (2, 7))
    return x, positions


def test_layernorm1p_scales_by_one_plus_its_weight_and_adds_its_bias():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = 3.0 + 2.0 * jax.random.normal(k1, (4, 64))
    w = 0.1 * jax.random.normal(k2, (64,))
    b = 0.1 * jax.random.normal(k3, (64,))
    xn = np.asarray(x, np.float64)
    ln = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
        xn.var(-1, keepdims=True) + 1e-5)
    want = (1.0 + np.asarray(w, np.float64)) * ln + np.asarray(b, np.float64)
    got = layernorm1p({"weight": w, "bias": b}, x, 1e-5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_half_rotary_leaves_the_second_half_of_each_head_untouched():
    x, positions = _qk()
    got = apply_rope(x, positions, 10_000.0, 0.5)
    np.testing.assert_array_equal(np.asarray(got[..., 16:]),
                                  np.asarray(x[..., 16:]))
    # the first half is rotated as a head of 16 dims, its own frequencies
    np.testing.assert_array_equal(
        np.asarray(got[..., :16]),
        np.asarray(apply_rope(x[..., :16], positions, 10_000.0)))
    assert not np.allclose(np.asarray(got[..., :16]), np.asarray(x[..., :16]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_full_rotary_is_bit_identical_to_the_former_rope(dtype):
    x, positions = _qk(dtype)
    want = np.asarray(_old_apply_rope(x, positions, 1e6), np.float32)
    for got in (apply_rope(x, positions, 1e6),
                apply_rope(x, positions, 1e6, 1.0)):
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)


def _leaves(specs):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec))
    return {"/".join(k.key for k in path): s for path, s in flat}


def test_a_tied_config_has_no_head_leaf_and_keeps_its_rmsnorm_scales():
    leaves = _leaves(build_model(get_smoke_config("qwen2_1_5b")).param_specs())
    assert not any(p.startswith("head") for p in leaves)
    assert {"final_norm/scale", "blocks/ln1/scale", "blocks/ln2/scale"} \
        <= set(leaves)


def test_the_untied_head_is_its_own_leaf_input_dim_first():
    cfg = get_smoke_config("minitron_4b")
    specs = build_model(cfg).param_specs()
    leaves = _leaves(specs)
    head = leaves["head/w"]
    assert head.shape == (cfg.d_model, cfg.vocab_size)
    assert leaves["embed/table"].shape == (cfg.vocab_size, cfg.d_model)
    # LayerNorm1P: a zero-centred weight and a bias, no leaf named scale
    for norm in ("final_norm", "blocks/ln1", "blocks/ln2"):
        assert leaves[f"{norm}/weight"].init == "zeros"
        assert f"{norm}/bias" in leaves and f"{norm}/scale" not in leaves
    # the head's vocabulary dim shards over the model axis, as the table's
    pspecs = param_pspecs(specs, cfg.family)
    assert pspecs["head"]["w"] == P(None, "model")
    assert pspecs["embed"]["table"] == P("model", None)


def test_the_untied_head_is_what_the_logits_read():
    cfg = get_smoke_config("minitron_4b").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1,
                                cfg.vocab_size)
    logits, _ = model.forward(params, {"tokens": tokens})
    zeroed = dict(params, head={"w": jnp.zeros_like(params["head"]["w"])})
    assert float(jnp.abs(logits).max()) > 0
    assert float(jnp.abs(model.forward(zeroed, {"tokens": tokens})[0]).max()) \
        == 0.0
