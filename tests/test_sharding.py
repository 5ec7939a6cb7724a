"""Sharding rules: logical-axis mapping, divisibility guards, cache
heuristics, and 1-device lowering of the sharded step machinery."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
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
    # head-major kv cache (layers, B, KV, T, hd), as attention stores it:
    # batch to data, KV heads to model; at batch 1, T to data
    kv = cfg.num_kv_heads
    tree = {"k": jax.ShapeDtypeStruct((2, 16, kv, 64, 32), jnp.bfloat16)}
    sh = cache_pspecs(tree, cfg, mesh)
    assert sh["k"].spec == P(None, "data", "model", None, None)
    tree = {"k": jax.ShapeDtypeStruct((2, 1, kv, 64, 32), jnp.bfloat16)}
    sh = cache_pspecs(tree, cfg, mesh)
    assert sh["k"].spec == P(None, None, "model", "data", None)


@pytest.mark.parametrize("arch", ["qwen2_1_5b", "qwen3_moe_235b_a22b", "zamba2_2_7b",
                                  "xlstm_1_3b", "whisper_base"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_host_mesh_lowering(arch, kind):
    """input_specs + step lowering works on the 1-device host mesh for the
    reduced configs: the sharded lowering a multi-device mesh starts from."""
    mesh = make_host_mesh()
    cfg = get_smoke_config(arch)
    shape = ShapeConfig("t", 64, 4, kind, microbatches=2 if kind == "train" else 1)
    step, args = input_specs(cfg, shape, mesh)
    lowered = jax.jit(step).lower(*args)
    compiled = lowered.compile()
    assert cost_analysis_dict(compiled).get("flops", 0) > 0


def test_train_state_keeps_float32_accumulators():
    """A bf16 model keeps float32 AdamW moments and sums its microbatch
    gradients in float32."""
    mesh = make_host_mesh()
    cfg = get_smoke_config("qwen2_1_5b")
    assert cfg.dtype == "bfloat16"
    shape = ShapeConfig("t", 32, 4, "train", microbatches=2)
    step, (params, opt_state, batch) = input_specs(cfg, shape, mesh)
    assert {p.dtype for p in jax.tree_util.tree_leaves(params)} == {jnp.dtype(jnp.bfloat16)}
    moments = jax.tree_util.tree_leaves((opt_state["mu"], opt_state["nu"]))
    assert len(moments) == 2 * len(jax.tree_util.tree_leaves(params))
    assert {m.dtype for m in moments} == {jnp.dtype(jnp.float32)}
    # the microbatch scan carries the gradient sum (one leaf per parameter)
    # and the loss sum
    jaxpr = jax.make_jaxpr(step)(params, opt_state, batch).jaxpr
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    lo = scan.params["num_consts"]
    carry = scan.invars[lo:lo + scan.params["num_carry"]]
    assert len(carry) == len(jax.tree_util.tree_leaves(params)) + 1
    assert {v.aval.dtype for v in carry} == {jnp.dtype(jnp.float32)}


def test_importing_the_program_leaves_xla_flags_alone():
    """No module of the model, kernel, sharding or launch packages sets
    ``XLA_FLAGS`` (a forced host device count) when it is imported."""
    code = """
import importlib, json, os, pkgutil
import repro.kernels, repro.launch, repro.models, repro.sharding
before = os.environ.get("XLA_FLAGS")
names = []
for pkg in (repro.launch, repro.models, repro.kernels, repro.sharding):
    for m in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + "."):
        importlib.import_module(m.name)
        names.append(m.name)
print(json.dumps([len(names), before, os.environ.get("XLA_FLAGS")]))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [p for p in [env.get("PYTHONPATH")] if p])
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout
    n, before, after = json.loads(out.strip().splitlines()[-1])
    assert n >= 20
    assert before == after == os.environ.get("XLA_FLAGS")


def test_host_mesh_lowering_long_context():
    """long_500k path (sliding window swap) lowers on the host mesh."""
    mesh = make_host_mesh()
    cfg = get_smoke_config("qwen2_1_5b")
    shape = ShapeConfig("long_500k", 2048, 1, "decode")
    step, args = input_specs(cfg, shape, mesh)
    compiled = jax.jit(step).lower(*args).compile()
    # the cache is windowed, not full-length: k is (layers, B, KV, T, hd)
    cache_arg = args[1]
    k_leaf = cache_arg["blocks"]["kv"]["k"]
    assert k_leaf.shape[3] <= 2048


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
