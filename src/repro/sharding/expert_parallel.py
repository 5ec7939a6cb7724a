"""Explicit expert-parallel MoE schedule: shard_map + jax.lax.all_to_all.

The GSPMD path (models/moe.py, grouped one-hot einsums) lets the compiler
infer the reshards; this module writes the TPU-native schedule by hand —
the §Perf beyond-paper alternative for collective-bound MoE pairs:

  per data-shard:  route locally → scatter to a local (E, C_loc, D) buffer
  all_to_all       split the expert dim across the data axis (each device
                   keeps its E/Ddev experts, receives every shard's tokens)
  local matmuls    (E_loc, Ddev·C_loc, D) × (E_loc, D, F) on the MXU
  all_to_all back  and a local weighted combine.

Dispatch is by *gather/scatter*, not one-hot matmuls, so the dispatch
FLOPs (~2·N·g·k·cf·D for the einsum path) disappear entirely, and the only
cross-device traffic is 2 × (E·C_loc·D) activation bytes per shard.

The model axis stays in GSPMD "auto" mode inside the shard_map body, so
the per-expert FF dim can still be tensor-parallel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def _local_ranks(flat_e, num_experts):
    nk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts))
    rank_sorted = jnp.arange(nk) - starts[sorted_e]
    return jnp.zeros((nk,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))


def moe_apply_expert_parallel(
    params,
    cfg: ModelConfig,
    x,
    *,
    mesh,
    capacity_factor: float = 1.25,
    axis: str = "data",
):
    """x: (B,S,D) -> (B,S,D), raw aux-loss dict.  Requires E % axis_size == 0
    and (B·S) % axis_size == 0."""
    B, S, D = x.shape
    N = B * S
    e, k = cfg.num_experts, cfg.experts_per_token
    ddev = dict(zip(mesh.axis_names, mesh.axis_sizes))[axis]
    assert e % ddev == 0 and N % ddev == 0, (e, N, ddev)
    e_loc = e // ddev
    n_loc = N // ddev
    cap = max(int(capacity_factor * n_loc * k / e), 1)
    cap = -(-cap // 8) * 8
    cap = min(cap, n_loc * k)

    def body(router, wi_gate, wi_up, wo, xf):
        # xf: (n_loc, D); wi_*: (e_loc, D, F); wo: (e_loc, F, D)
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_i = jax.lax.top_k(probs, k)
        topk_w = topk_p / jnp.clip(topk_p.sum(-1, keepdims=True), 1e-9)
        # aux losses (global means via psum over the data axis)
        me = jax.lax.pmean(probs.mean(0), axis)
        counts = jnp.zeros((e,), jnp.float32).at[topk_i.reshape(-1)].add(1.0)
        ce = jax.lax.pmean(counts / n_loc, axis)
        aux = e * jnp.sum(me * ce)
        zloss = jax.lax.pmean(
            jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1))), axis)

        flat_e = topk_i.reshape(n_loc * k)
        ranks = _local_ranks(flat_e, e)
        keep = ranks < cap
        slot = jnp.where(keep, flat_e * cap + ranks, e * cap)
        x_rep = jnp.repeat(xf, k, axis=0)
        xe = (jnp.zeros((e * cap + 1, D), x.dtype).at[slot]
              .add(x_rep)[: e * cap].reshape(ddev, e_loc, cap, D))
        # expert dim -> devices; received dim 0 indexes the source shard
        xe = jax.lax.all_to_all(xe, axis, split_axis=0, concat_axis=0,
                                tiled=False)
        xe = jnp.moveaxis(xe, 1, 0).reshape(e_loc, ddev * cap, D)

        g = jnp.einsum("ecd,edf->ecf", xe, wi_gate)
        u = jnp.einsum("ecd,edf->ecf", xe, wi_up)
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wo)

        ye = jnp.moveaxis(ye.reshape(e_loc, ddev, cap, D), 0, 1)
        ye = jax.lax.all_to_all(ye, axis, split_axis=0, concat_axis=0,
                                tiled=False)  # back: (ddev=e-chunks, ...)
        ye = ye.reshape(e * cap, D)
        gathered = ye[jnp.where(keep, slot, 0)]
        w = (topk_w.reshape(n_loc * k) * keep).astype(x.dtype)
        y = jnp.sum((gathered * w[:, None]).reshape(n_loc, k, D), axis=1)
        return y, aux, zloss

    P = jax.sharding.PartitionSpec
    shard = functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, None), P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None)),
        out_specs=(P(axis, None), P(), P()),
        axis_names={axis},
    )
    y, aux, zloss = shard(body)(
        params["router"], params["wi_gate"], params["wi_up"], params["wo"],
        x.reshape(N, D),
    )
    if cfg.num_shared_experts:
        from repro.models.moe import _shared_expert

        y = _shared_expert(params, x.reshape(N, D), y)
    return y.reshape(B, S, D), {"moe_aux": aux, "moe_z": zloss}
