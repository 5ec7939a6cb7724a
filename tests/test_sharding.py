"""Sharding rules: logical-axis mapping, divisibility guards, cache
heuristics, and 1-device lowering of the dry-run step machinery."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.common.types import (
    AXIS_EMBED,
    AXIS_EXPERTS,
    AXIS_HEADS,
    AXIS_INNER,
    AXIS_KV,
    AXIS_LAYERS,
    AXIS_MOE_FF,
    AXIS_VOCAB,
)
from repro.configs import get_smoke_config
from repro.launch.hlo_analysis import cost_analysis_dict
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import input_specs
from repro.models.config import ShapeConfig
from repro.sharding import (
    cache_pspecs,
    evenly,
    opt_state_pspec,
    pspec_for_axes,
    rules_for,
)


def test_pspec_dense_rules():
    r = rules_for("dense")
    assert pspec_for_axes((AXIS_EMBED, AXIS_HEADS), r) == P(None, "model")
    assert pspec_for_axes((AXIS_VOCAB, AXIS_EMBED), r) == P("model", None)
    assert pspec_for_axes((AXIS_LAYERS, AXIS_EMBED, AXIS_KV), r) == P(None, None, "model")


def test_pspec_dedup_one_mesh_axis():
    """xLSTM wq has (inner, heads) -> both map to model; only first kept."""
    r = rules_for("ssm")
    assert pspec_for_axes((AXIS_INNER, AXIS_HEADS), r) == P("model", None)


def test_pspec_moe_rules():
    r = rules_for("moe")
    assert pspec_for_axes((AXIS_EXPERTS, AXIS_EMBED, AXIS_MOE_FF), r) == P(
        "data", None, "model"
    )


def test_evenly_guard():
    mesh = make_host_mesh()
    # 1-sized axes divide everything
    assert evenly(P("model"), (7,), mesh) == P("model")


def test_opt_state_pspec_adds_data_axis():
    mesh = make_host_mesh()
    ps = opt_state_pspec(P(None, "model"), (64, 32), mesh)
    assert ps == P("data", "model")
    # already data-sharded params stay unchanged
    ps2 = opt_state_pspec(P("data", None, "model"), (4, 64, 32), mesh)
    assert ps2 == P("data", None, "model")


def test_cache_pspec_heuristics():
    mesh = make_host_mesh()
    cfg = get_smoke_config("qwen2_1_5b")
    # kv-cache-like leaf: (layers, B, T, KV, hd)
    tree = {"k": jax.ShapeDtypeStruct((2, 16, 64, cfg.num_kv_heads, 32), jnp.bfloat16)}
    sh = cache_pspecs(tree, cfg, mesh)
    assert sh["k"].spec == P(None, "data", None, "model", None)
    # batch=1 long-context: time dim takes the data axis
    tree = {"k": jax.ShapeDtypeStruct((2, 1, 64, cfg.num_kv_heads, 32), jnp.bfloat16)}
    sh = cache_pspecs(tree, cfg, mesh)
    assert sh["k"].spec[2] == "data"


@pytest.mark.parametrize("arch", ["qwen2_1_5b", "qwen3_moe_235b_a22b", "zamba2_2_7b",
                                  "xlstm_1_3b", "whisper_base"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_host_mesh_lowering(arch, kind):
    """input_specs + step lowering works on the 1-device host mesh for the
    reduced configs — validates the whole dry-run path without 512 devices."""
    mesh = make_host_mesh()
    cfg = get_smoke_config(arch)
    shape = ShapeConfig("t", 64, 4, kind, microbatches=2 if kind == "train" else 1)
    step, args = input_specs(cfg, shape, mesh)
    lowered = jax.jit(step).lower(*args)
    compiled = lowered.compile()
    assert cost_analysis_dict(compiled).get("flops", 0) > 0


def test_host_mesh_lowering_long_context():
    """long_500k path (sliding window swap) lowers on the host mesh."""
    mesh = make_host_mesh()
    cfg = get_smoke_config("qwen2_1_5b")
    shape = ShapeConfig("long_500k", 2048, 1, "decode")
    step, args = input_specs(cfg, shape, mesh)
    compiled = jax.jit(step).lower(*args).compile()
    # the cache is windowed, not full-length
    cache_arg = args[1]
    k_leaf = jax.tree_util.tree_leaves(cache_arg)[0]
    assert k_leaf.shape[2] <= 2048


def test_distill_step_host_lowering():
    """The MDD distill step (paper's technique as a pjit program) lowers."""
    from repro.launch.steps import distill_input_specs

    mesh = make_host_mesh()
    s = get_smoke_config("minitron_4b")
    t = get_smoke_config("nemotron_4_15b")
    shape = ShapeConfig("t", 64, 4, "train", microbatches=2)
    step, args = distill_input_specs(s, t, shape, mesh)
    compiled = jax.jit(step).lower(*args).compile()
    assert cost_analysis_dict(compiled).get("flops", 0) > 0


def test_distill_step_trains_student():
    """One distill step moves the student toward the teacher distribution."""
    import jax.numpy as jnp
    from repro.launch.steps import make_distill_step
    from repro.models import build_model

    s_cfg = get_smoke_config("qwen2_1_5b")
    t_cfg = get_smoke_config("qwen2_1_5b")
    shape = ShapeConfig("t", 32, 4, "train", microbatches=2)
    step, student, teacher, opt = make_distill_step(s_cfg, t_cfg, shape)
    sp = student.init(jax.random.PRNGKey(0))
    tp = teacher.init(jax.random.PRNGKey(42))
    st = opt.init(sp)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                     s_cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0,
                                     s_cfg.vocab_size),
    }
    losses = []
    for _ in range(3):
        sp, st, metrics = jax.jit(step)(sp, st, tp, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
