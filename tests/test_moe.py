"""MoE layer: grouped-dispatch path vs one-hot oracle, routing invariants."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import init_params
from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig
from repro.models.moe import (
    _expert_ranks,
    moe_apply_dense,
    moe_apply_onehot,
    moe_spec,
    router_topk,
)


def _cfg(**kw):
    base = dict(
        name="moe-test", family="moe", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=2, d_ff=64, vocab_size=128, num_experts=4,
        experts_per_token=2, moe_d_ff=64,
    )
    base.update(kw)
    return ModelConfig(**base)


def _setup(cfg, B=2, S=16, seed=0):
    key = jax.random.PRNGKey(seed)
    params = init_params(moe_spec(cfg), key)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.d_model))
    return params, x


def test_grouped_matches_onehot_oracle():
    cfg = _cfg()
    params, x = _setup(cfg)
    # group_size >= N so grouping is trivial and capacities match exactly
    y1, l1 = moe_apply_dense(params, cfg, x, group_size=32)
    y2, l2 = moe_apply_onehot(params, cfg, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(l1["moe_aux"]), float(l2["moe_aux"]), rtol=1e-6)


def test_grouped_with_groups_still_finite_and_close():
    cfg = _cfg(num_experts=4, experts_per_token=1)
    params, x = _setup(cfg, B=4, S=16)
    y, losses = moe_apply_dense(params, cfg, x, group_size=16)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(losses["moe_aux"]) >= 1.0 - 1e-5  # aux >= 1 (E * sum(me*ce) >= 1)


def test_no_drop_when_capacity_generous():
    """With capacity >= g*k every token is processed; output is a weighted
    average of expert MLPs, so scaling x scales y in the linear regime."""
    cfg = _cfg(experts_per_token=1)
    params, x = _setup(cfg)
    y_lo, _ = moe_apply_dense(params, cfg, x, capacity_factor=8.0, group_size=32)
    # same routing, doubled capacity: identical result (nothing was dropped)
    y_hi, _ = moe_apply_dense(params, cfg, x, capacity_factor=16.0, group_size=32)
    np.testing.assert_allclose(np.asarray(y_lo), np.asarray(y_hi), rtol=1e-6)


def test_expert_ranks_unique_and_dense():
    """Per expert, ranks are exactly 0..count-1 (no gaps, no duplicates)."""
    rng = np.random.RandomState(0)
    flat_e = jnp.asarray(rng.randint(0, 7, size=64), jnp.int32)
    ranks = np.asarray(_expert_ranks(flat_e, 7))
    for e in range(7):
        r = np.sort(ranks[np.asarray(flat_e) == e])
        np.testing.assert_array_equal(r, np.arange(len(r)))


def test_router_topk_weights_normalized():
    cfg = _cfg(num_experts=8, experts_per_token=3)
    params, x = _setup(cfg)
    w, i, aux, z = router_topk(params, cfg, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert int(i.max()) < 8 and int(i.min()) >= 0
    assert float(aux) >= 1.0 - 1e-5  # load-balance lower bound at uniformity
    assert float(z) >= 0.0


def test_shared_expert_path():
    cfg = _cfg(num_shared_experts=1)
    params, x = _setup(cfg)
    y, _ = moe_apply_dense(params, cfg, x, group_size=32)
    # zero out shared expert -> output changes
    p2 = dict(params)
    p2["shared_wo"] = jnp.zeros_like(params["shared_wo"])
    y2, _ = moe_apply_dense(p2, cfg, x, group_size=32)
    assert not np.allclose(np.asarray(y), np.asarray(y2))


def test_grad_flows_through_dispatch():
    cfg = _cfg()
    params, x = _setup(cfg)

    def loss(p):
        y, aux = moe_apply_dense(p, cfg, x, group_size=32)
        return jnp.sum(y**2) + aux["moe_aux"]

    grads = jax.grad(loss)(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0
    # router receives gradient via combine weights and aux loss
    assert float(jnp.sum(jnp.abs(grads["router"]))) > 0


def test_expert_parallel_matches_dense_single_device():
    """shard_map all-to-all schedule == grouped-dispatch path (1-device mesh)."""
    from repro.sharding.expert_parallel import moe_apply_expert_parallel

    cfg = _cfg()
    params, x = _setup(cfg)
    mesh = make_host_mesh()
    y1, l1 = moe_apply_dense(params, cfg, x, capacity_factor=4.0, group_size=32)
    y2, l2 = moe_apply_expert_parallel(params, cfg, x, mesh=mesh,
                                       capacity_factor=4.0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(l1["moe_aux"]), float(l2["moe_aux"]), rtol=1e-5)


def test_expert_parallel_with_shared_expert():
    from repro.sharding.expert_parallel import moe_apply_expert_parallel

    cfg = _cfg(num_shared_experts=1, experts_per_token=1)
    params, x = _setup(cfg)
    mesh = make_host_mesh()
    y, _ = moe_apply_expert_parallel(params, cfg, x, mesh=mesh)
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))
