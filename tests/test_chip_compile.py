"""Compile the main path for a described TPU v5e chip, with no chip attached.

What the TPU compiler refuses (a block layout Mosaic rejects, a program
larger than the chip's memory) fails here instead of on the chip.  The
``kd_loss`` kernel compiles at the distill cycle's shape under ``vmap``
and at a 151936-word vocabulary; the full-width Qwen2-1.5B prefill and
decode steps compile within one chip's 16 GB.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.kd_loss import kd_loss
from repro.launch.hlo_analysis import cost_summary
from repro.launch.steps import make_prefill_step, make_serve_step

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described ``v5e:2x2``; compilation cache off meanwhile.

    Compiles for a described chip are written to the persistent cache but
    cannot be read back without one, so the cache stays off around them.
    """
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


@pytest.mark.parametrize("shape,dtype", [
    ((2048, 32, 8), jnp.float32),   # the distill cycle: vmapped over parties
    ((256, 151936), jnp.bfloat16),  # a Qwen2-sized vocabulary
])
def test_kd_loss_compiles_for_v5e(one_chip, shape, dtype):
    fn = jax.vmap(kd_loss) if len(shape) == 3 else kd_loss
    args = _on(one_chip, (jax.ShapeDtypeStruct(shape, dtype),
                          jax.ShapeDtypeStruct(shape, dtype),
                          jax.ShapeDtypeStruct(shape[:-1], jnp.int32)))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_qwen2_1_5b_serving_step_fits_one_v5e(one_chip, kind):
    cfg = get_config("qwen2_1_5b")
    batch, bucket, max_new = 8, 32, 16
    prefill, model = make_prefill_step(cfg, cache_len=bucket + max_new)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if kind == "prefill":
        step = prefill
        tokens = jax.ShapeDtypeStruct((batch, bucket), jnp.int32)
        args = (params, _on(one_chip, {"tokens": tokens}))
    else:
        step, _ = make_serve_step(cfg)
        cache = _on(one_chip, model.cache_abstract(batch, bucket + max_new))
        token = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        args = (params, cache, _on(one_chip, {"token": token}))
    compiled = jax.jit(step).lower(*args).compile()
    assert cost_summary(compiled)["peak_bytes"] < V5E_HBM_BYTES
