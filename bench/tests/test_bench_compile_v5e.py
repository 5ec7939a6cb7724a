"""Compile every program a cell warms, at its largest shape, for a
described TPU v5e chip (no chip attached), and check that each fits in
the chip's 16 GB.

Every cell of ``BENCHMARK.json`` that the serving driver runs warms the
prefill and decode steps of ``launch/serve.build_engine``, at most
``max_batch`` rows of ``prompt_tokens`` (the chat cell's largest slot is
16 rows of 1,024 tokens, the docs cell's 16 of 2,048).  The market cell's
largest program is the distill cycle of its largest bucket: all 8,000 LR
parties as students of MLP teachers.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import peaks  # noqa: E402

HBM = peaks.PEAKS["TPU v5 lite"]["hbm_bytes"]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
SERVED = [w["name"] for w in SPEC["workloads"]
          if json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                        .read_text())["driver"] == "serve"]


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described ``v5e:2x2``; compilation cache off meanwhile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("cell,kind", [(c, k) for c in SERVED
                                       for k in ("prefill", "decode")])
def test_serving_program_fits_one_v5e(one_chip, cell, kind):
    from repro.launch.serve import build_engine

    c = harness.load_cell(cell)
    drv = harness.driver_for(c)
    tr = c.traffic
    mcfg = drv.program_config(c.config)
    model, prefill, decode = build_engine(mcfg, tr["prompt_tokens"],
                                          tr["max_new"])
    params = _on(one_chip, jax.eval_shape(
        lambda: drv.program_params(model, 0, jnp.dtype(mcfg.dtype))))
    b = tr["max_batch"]
    if kind == "prefill":
        tokens = jax.ShapeDtypeStruct((b, tr["prompt_tokens"]), jnp.int32)
        compiled = prefill.lower(params, _on(one_chip, {"tokens": tokens}))
    else:
        cache = model.cache_abstract(b, tr["prompt_tokens"] + tr["max_new"])
        token = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        compiled = decode.lower(params, _on(one_chip, cache),
                                _on(one_chip, {"token": token}))
    assert _bytes(compiled.compile()) < HBM


def test_market_largest_distill_bucket_fits_one_v5e(one_chip):
    from repro.models.small import make_lr, make_mlp
    from repro.runtime.population import PartyPopulation

    c = json.loads((BENCH / "configs" / "market-10k.json").read_text())
    n_lr = c["parties"] - int(c["parties"] * c["mlp_share"])
    f, k, m = c["features"], c["classes"], c["samples_per_party"]
    lr = PartyPopulation(make_lr(num_features=f, num_classes=k),
                         np.zeros((n_lr, m, f), np.float32),
                         np.zeros((n_lr, m), np.int32), task="t",
                         batch_size=c["batch_size"])
    mlp = make_mlp(num_features=f, num_classes=k, hidden=c["mlp_hidden"])
    bucket = n_lr  # buckets are powers of two capped at the cohort's size
    cycle = lr._distill_cycle(mlp.apply, 0, c["exchange"]["alpha"],
                              c["exchange"]["temperature"], subset=True)
    teachers = jax.eval_shape(jax.vmap(mlp.init),
                              jax.ShapeDtypeStruct((bucket, 2), jnp.uint32))
    args = (jax.eval_shape(lambda: lr.state.params), teachers,
            jax.ShapeDtypeStruct((bucket,), jnp.int32),
            jax.ShapeDtypeStruct((lr._n_blocks,), jnp.int32),
            jax.ShapeDtypeStruct((bucket,), jnp.float32),
            jax.ShapeDtypeStruct(lr._jx.shape, lr._jx.dtype),
            jax.ShapeDtypeStruct(lr._jy.shape, lr._jy.dtype))
    compiled = cycle.lower(*_on(one_chip, args)).compile()
    assert _bytes(compiled) < HBM
