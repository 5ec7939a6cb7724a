"""pjit-able step functions (train / prefill / decode / distill) and the
abstract, sharding-annotated input specs they lower with on a mesh.

Every function here is pure and mesh-agnostic; shardings are attached to
the ``ShapeDtypeStruct`` stand-ins (AOT pattern), so ``jax.jit(step)
.lower(*input_specs(...))`` works on any mesh without touching real
device memory.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.scan import maybe_scan
from repro.common.types import ParamSpec
from repro.core.losses import cross_entropy_loss, distillation_loss
from repro.models import build_model
from repro.models.config import ModelConfig, ShapeConfig
from repro.optim import adamw, apply_updates, clip_by_global_norm
from repro.sharding import (
    batch_pspec,
    cache_pspecs,
    opt_state_pspec,
    param_pspecs_even,
)

LONG_CONTEXT_WINDOW = 8192  # sliding window used ONLY for long_500k


def resolve_config(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """Shape-specific config adaptation.

    ``long_500k`` requires sub-quadratic attention: attention-bearing archs
    switch to an 8k sliding window (llama4-style chunked-local attention);
    xLSTM (attention-free) is already O(1)-state and unchanged.
    """
    if shape.name == "long_500k" and cfg.family != "ssm":
        return cfg.replace(sliding_window=LONG_CONTEXT_WINDOW)
    if shape.name == "long_500k" and cfg.family == "ssm" and cfg.block_type != "xlstm":
        cfg = cfg.replace(sliding_window=LONG_CONTEXT_WINDOW)
    return cfg


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------


def make_optimizer():
    """AdamW with float32 moments."""
    return adamw(3e-4, weight_decay=0.1)


def make_train_step(cfg: ModelConfig, shape: ShapeConfig):
    """Gradient-accumulated train step: (params, opt_state, batch) -> ...

    Gradients are summed over the microbatches in float32."""
    model = build_model(cfg)
    opt = make_optimizer()
    G = max(shape.microbatches, 1)

    def loss_fn(params, mb):
        logits, aux = model.forward(params, mb)
        ce = cross_entropy_loss(logits, mb["labels"])
        total = ce
        if cfg.is_moe:
            total = total + cfg.router_aux_weight * aux["moe_aux"] + 1e-3 * aux["moe_z"]
        return total, ce

    def train_step(params, opt_state, batch):
        if G == 1:
            (_, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            gsum = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            ce_sum = ce
        else:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape(G, x.shape[0] // G, *x.shape[1:]), batch
            )

            def micro(carry, mb):
                gacc, lacc = carry
                (_, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                gacc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), gacc, grads
                )
                return (gacc, lacc + ce), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, ce_sum), _ = maybe_scan(micro, (zeros, jnp.zeros((), jnp.float32)), mbs)

        grads = jax.tree_util.tree_map(lambda g: g / G, gsum)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        updates, new_opt = opt.update(grads, opt_state, params)
        new_params = apply_updates(params, updates)
        return new_params, new_opt, {"loss": ce_sum / G, "grad_norm": gnorm}

    return train_step, model, opt


def make_prefill_step(cfg: ModelConfig, cache_len=None):
    """(params, batch) -> (last-token logits, cache).

    ``cache_len`` sizes the decode KV cache; pass prompt length + decode
    budget so generation never outgrows the cache (default: 2x prompt).
    """
    model = build_model(cfg)

    def prefill_step(params, batch):
        logits, _aux, cache = model.prefill(params, batch, cache_len=cache_len)
        return logits, cache

    return prefill_step, model


def make_serve_step(cfg: ModelConfig):
    """(params, cache, token) -> (next_token, logits, cache): one decode step."""
    model = build_model(cfg)

    def serve_step(params, cache, batch):
        logits, new_cache = model.decode(params, cache, batch)
        next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token, logits, new_cache

    return serve_step, model


def make_distill_step(
    student_cfg: ModelConfig,
    teacher_cfg: ModelConfig,
    shape: ShapeConfig,
    *,
    alpha: float = 0.5,
    temperature: float = 2.0,
):
    """The paper's MDD integration step as a pjit-sharded train step.

    Student CE on its own labels + temperature-KL against the discovered
    teacher's logits (teacher params frozen).  Teacher and student may be
    different architectures — only the vocab must match.
    """
    assert student_cfg.vocab_size == teacher_cfg.vocab_size
    student = build_model(student_cfg)
    teacher = build_model(teacher_cfg)
    opt = make_optimizer()
    G = max(shape.microbatches, 1)

    def loss_fn(params, teacher_logits, mb):
        logits, aux = student.forward(params, mb)
        loss, parts = distillation_loss(
            logits, teacher_logits, mb["labels"], alpha=alpha,
            temperature=temperature,
        )
        if student_cfg.is_moe:
            loss = loss + student_cfg.router_aux_weight * aux["moe_aux"]
        return loss, parts["ce"]

    def distill_step(params, opt_state, teacher_params, batch):
        mbs = jax.tree_util.tree_map(
            lambda x: x.reshape(G, x.shape[0] // G, *x.shape[1:]), batch
        )

        def micro(carry, mb):
            gacc, lacc = carry
            t_logits, _ = teacher.forward(teacher_params, mb)
            t_logits = jax.lax.stop_gradient(t_logits)
            (loss, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, t_logits, mb
            )
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads
            )
            return (gacc, lacc + loss), None

        zeros = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), _ = maybe_scan(micro, (zeros, jnp.zeros((), jnp.float32)), mbs)
        grads = jax.tree_util.tree_map(lambda g: g / G, gsum)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        updates, new_opt = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), new_opt, {"loss": lsum / G, "gnorm": gnorm}

    return distill_step, student, teacher, opt


# ---------------------------------------------------------------------------
# Abstract, sharding-annotated input specs (lowered without device memory)
# ---------------------------------------------------------------------------


def _sds(shape, dtype, mesh: Mesh, pspec: P):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))


def abstract_sharded_params(model, cfg: ModelConfig, mesh: Mesh):
    specs = model.param_specs()
    pspecs = param_pspecs_even(specs, cfg.family, mesh)
    dt = jnp.dtype(cfg.dtype)

    def leaf(s: ParamSpec, ps: P):
        return _sds(s.shape, dt, mesh, ps)

    return jax.tree_util.tree_map(
        leaf, specs, pspecs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def abstract_opt_state(model, cfg: ModelConfig, mesh: Mesh):
    """AdamW state stand-ins; float32 moments ZeRO-sharded over the data axis."""
    specs = model.param_specs()
    pspecs = param_pspecs_even(specs, cfg.family, mesh)

    def moment(s: ParamSpec, ps: P):
        return _sds(s.shape, jnp.float32, mesh, opt_state_pspec(ps, s.shape, mesh))

    def is_spec(x):
        return isinstance(x, ParamSpec)

    mu = jax.tree_util.tree_map(moment, specs, pspecs, is_leaf=is_spec)
    nu = jax.tree_util.tree_map(moment, specs, pspecs, is_leaf=is_spec)
    step = _sds((), jnp.int32, mesh, P())
    return {"step": step, "mu": mu, "nu": nu}


def abstract_batch(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *, labels: bool):
    B, S = shape.global_batch, shape.seq_len
    bp = batch_pspec(mesh)
    d_ax = bp[0]
    tree = {"tokens": _sds((B, S), jnp.int32, mesh, P(d_ax, None))}
    if labels:
        tree["labels"] = _sds((B, S), jnp.int32, mesh, P(d_ax, None))
    if cfg.num_patches:
        tree["patches"] = _sds(
            (B, cfg.num_patches, cfg.d_model), jnp.bfloat16, mesh, P(d_ax, None, None)
        )
    if cfg.family == "audio":
        tree["frames"] = _sds(
            (B, cfg.num_frames, cfg.d_model), jnp.bfloat16, mesh, P(d_ax, None, None)
        )
    return tree


def abstract_cache(model, cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    cache = model.cache_abstract(shape.global_batch, shape.seq_len)
    shardings = cache_pspecs(cache, cfg, mesh)
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        cache,
        shardings,
    )


def abstract_token_batch(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    B = shape.global_batch
    bp = batch_pspec(mesh)
    ps = P(bp[0], None) if B % _data_size(mesh) == 0 and B > 1 else P(None, None)
    return {"token": _sds((B, 1), jnp.int32, mesh, ps)}


def _data_size(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes.get("data", 1)
    if "pod" in sizes:
        n *= sizes["pod"]
    return n


def distill_input_specs(
    student_cfg: ModelConfig,
    teacher_cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: Mesh,
):
    """(step_fn, args) for the MDD distill step — the paper's technique as a
    pjit-sharded program (student params, opt state, frozen teacher, batch)."""
    s_cfg = resolve_config(student_cfg, shape)
    t_cfg = resolve_config(teacher_cfg, shape)
    step, student, teacher, _ = make_distill_step(s_cfg, t_cfg, shape)
    params = abstract_sharded_params(student, s_cfg, mesh)
    opt_state = abstract_opt_state(student, s_cfg, mesh)
    teacher_params = abstract_sharded_params(teacher, t_cfg, mesh)
    batch = abstract_batch(s_cfg, shape, mesh, labels=True)
    return step, (params, opt_state, teacher_params, batch)


def input_specs(arch_cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """Return (step_fn, args tuple of ShapeDtypeStructs) for one (arch, shape).

    - train shapes  -> train_step(params, opt_state, batch)
    - prefill shapes-> prefill_step(params, batch)
    - decode shapes -> serve_step(params, cache, token)
    """
    cfg = resolve_config(arch_cfg, shape)
    if shape.kind == "train":
        step, model, _ = make_train_step(cfg, shape)
        params = abstract_sharded_params(model, cfg, mesh)
        opt_state = abstract_opt_state(model, cfg, mesh)
        batch = abstract_batch(cfg, shape, mesh, labels=True)
        return step, (params, opt_state, batch)
    if shape.kind == "prefill":
        # cache_len=S+1: the minimum legal decode headroom, so the analyzed
        # KV-cache footprint stays comparable to the exact-S baseline
        # instead of inheriting the serving default of 2*S
        step, model = make_prefill_step(cfg, cache_len=shape.seq_len + 1)
        params = abstract_sharded_params(model, cfg, mesh)
        batch = abstract_batch(cfg, shape, mesh, labels=False)
        # Pin output shardings: the returned KV cache must land in the same
        # layout serve_step consumes (otherwise XLA gathers the full cache —
        # measured 139 GB/device on deepseek prefill_32k).  Recurrent-state
        # caches (xLSTM) lay out better under GSPMD propagation — skip.
        if cfg.family == "ssm":
            return jax.jit(step), (params, batch)
        cache_sh = cache_pspecs(model.cache_abstract(shape.global_batch,
                                                     shape.seq_len), cfg, mesh)
        bp = batch_pspec(mesh)
        from repro.sharding import evenly

        logits_sh = NamedSharding(mesh, evenly(
            P(bp[0], None, "model"),
            (shape.global_batch, 1, cfg.vocab_size), mesh))
        step = jax.jit(step, out_shardings=(logits_sh, cache_sh))
        return step, (params, batch)
    if shape.kind == "decode":
        step, model = make_serve_step(cfg)
        params = abstract_sharded_params(model, cfg, mesh)
        cache = abstract_cache(model, cfg, shape, mesh)
        token = abstract_token_batch(cfg, shape, mesh)
        return step, (params, cache, token)
    raise ValueError(shape.kind)
