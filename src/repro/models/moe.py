"""Mixture-of-Experts layer.

``moe_apply`` runs the grouped one-hot dispatch einsum
(``moe_apply_dense``), which works on any device count: groups shard over
the data axis, experts over the model axis.  ``moe_apply_onehot`` is its
ungrouped test oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.common.types import (
    AXIS_EMBED,
    AXIS_EXPERTS,
    AXIS_MOE_FF,
    ParamSpec,
)
from repro.models.config import ModelConfig

# tokens per dispatch group (``moe_apply_dense``)
MOE_GROUP_SIZE = 512


def moe_spec(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts
    spec = {
        "router": ParamSpec((d, e), (AXIS_EMBED, None), init="small"),
        "wi_gate": ParamSpec((e, d, f), (AXIS_EXPERTS, AXIS_EMBED, AXIS_MOE_FF)),
        "wi_up": ParamSpec((e, d, f), (AXIS_EXPERTS, AXIS_EMBED, AXIS_MOE_FF)),
        "wo": ParamSpec((e, f, d), (AXIS_EXPERTS, AXIS_MOE_FF, AXIS_EMBED)),
    }
    if cfg.num_shared_experts:
        spec["shared_wi_gate"] = ParamSpec((d, f), (AXIS_EMBED, AXIS_MOE_FF))
        spec["shared_wi_up"] = ParamSpec((d, f), (AXIS_EMBED, AXIS_MOE_FF))
        spec["shared_wo"] = ParamSpec((f, d), (AXIS_MOE_FF, AXIS_EMBED))
    return spec


def router_topk(params, cfg: ModelConfig, x):
    """Router logits -> (topk weights, topk idx, aux losses).

    x: (N, D) flattened tokens.
    """
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.experts_per_token
    topk_p, topk_i = jax.lax.top_k(probs, k)  # (N,k)
    topk_w = topk_p / jnp.clip(jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style) + router z-loss
    e = cfg.num_experts
    me = jnp.mean(probs, axis=0)  # (E,)
    counts = jnp.zeros((e,), jnp.float32).at[topk_i.reshape(-1)].add(1.0)
    ce = counts / x.shape[0]  # mean routed load per expert
    aux = e * jnp.sum(me * ce)
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return topk_w, topk_i, aux, zloss


def _expert_ranks(flat_e, num_experts: int):
    """Rank of each routed (token,k) entry within its expert's queue.

    Sort-based (no (N,E) one-hots): O(Nk log Nk) work, O(Nk) memory.
    """
    nk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts))  # (E,)
    rank_sorted = jnp.arange(nk) - starts[sorted_e]
    ranks = jnp.zeros((nk,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    return ranks


def _dispatch_combine(cfg: ModelConfig, topk_w, topk_i, n_tokens: int, capacity: int):
    """Build (N, E, C) dispatch one-hot and combine weights."""
    e = cfg.num_experts
    k = cfg.experts_per_token
    # expert one-hot per (token, k): (N, k, E)
    onehot = jax.nn.one_hot(topk_i, e, dtype=jnp.int32)
    # position of each (token,k) within its expert queue: exclusive cumsum
    flatoh = onehot.reshape(n_tokens * k, e)
    pos = jnp.cumsum(flatoh, axis=0) - flatoh  # (N*k, E)
    posk = (pos.reshape(n_tokens, k, e) * onehot).sum(-1)  # (N,k) slot index
    expert = topk_i  # (N,k)
    keep = posk < capacity
    disp = (
        jax.nn.one_hot(expert, e, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(posk, capacity, dtype=jnp.float32)[..., None, :]
    )  # (N,k,E,C)
    disp = disp * keep[..., None, None]
    combine = disp * topk_w[..., None, None]
    return disp.sum(1), combine.sum(1)  # (N,E,C) each


def _expert_mlp(params, xe):
    """xe: (..., E, C, D) -> (..., E, C, D) through per-expert SwiGLU."""
    g = jnp.einsum("...ecd,edf->...ecf", xe, params["wi_gate"])
    u = jnp.einsum("...ecd,edf->...ecf", xe, params["wi_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("...ecf,efd->...ecd", h, params["wo"])


def _shared_expert(params, xf, y):
    g = jnp.einsum("nd,df->nf", xf, params["shared_wi_gate"])
    u = jnp.einsum("nd,df->nf", xf, params["shared_wi_up"])
    return y + jnp.einsum("nf,fd->nd", jax.nn.silu(g) * u, params["shared_wo"])


def moe_apply(params, cfg: ModelConfig, x, *, capacity_factor: float = 1.25):
    """The model's MoE layer: grouped dispatch in groups of ``MOE_GROUP_SIZE``."""
    return moe_apply_dense(params, cfg, x, capacity_factor=capacity_factor,
                           group_size=MOE_GROUP_SIZE)


def moe_apply_dense(
    params,
    cfg: ModelConfig,
    x,
    *,
    capacity_factor: float = 1.25,
    group_size: int = MOE_GROUP_SIZE,
):
    """Grouped one-hot dispatch MoE — the production (GSPMD) path.

    x: (B,S,D) -> (B,S,D), raw aux-loss dict (weights applied by the step).

    GShard-style, but with two memory fixes for scale:
      * tokens are split into groups of ``group_size`` so the dispatch
        tensor is (G, g, E, C) with C = g·k·cf/E — total bytes scale with
        N·g·k·cf, independent of E;
      * expert ranks come from a per-group stable sort (no (N,E) cumsum
        one-hots), and the (g,k,E)×(g,k,C) einsum contracts over k so the
        (g,k,E,C) outer product never materializes.

    Sharding is left to GSPMD: the groups follow the batch's data axis and
    the expert matmuls the expert weights' layout.
    """
    B, S, D = x.shape
    N = B * S
    e, k = cfg.num_experts, cfg.experts_per_token
    g = math.gcd(N, group_size)
    G = N // g
    xf = x.reshape(N, D)
    topk_w, topk_i, aux, zloss = router_topk(params, cfg, xf)
    cap = max(int(capacity_factor * g * k / e), 1)
    cap = -(-cap // 8) * 8  # multiple of 8 for TPU-friendly layouts
    cap = min(cap, g * k)

    ranks = jax.vmap(lambda fe: _expert_ranks(fe, e))(topk_i.reshape(G, g * k))
    ranks = ranks.reshape(G, g, k)
    keep = ranks < cap
    slot = jnp.where(keep, ranks, cap)  # cap -> all-zero one-hot row (dropped)
    oh_e = jax.nn.one_hot(topk_i.reshape(G, g, k), e, dtype=x.dtype)
    oh_c = jax.nn.one_hot(slot, cap, dtype=x.dtype)
    disp = jnp.einsum("gnke,gnkc->gnec", oh_e, oh_c)  # (G,g,E,C)
    wk = (topk_w.reshape(G, g, k) * keep).astype(x.dtype)
    comb = jnp.einsum("gnke,gnkc->gnec", oh_e, oh_c * wk[..., None])

    xg = xf.reshape(G, g, D)
    xe = jnp.einsum("gnec,gnd->gecd", disp, xg)  # (G,E,C,D), local per group
    ye = _expert_mlp(params, xe)
    y = jnp.einsum("gnec,gecd->gnd", comb, ye).reshape(N, D)
    if cfg.num_shared_experts:
        y = _shared_expert(params, xf, y)
    losses = {"moe_aux": aux, "moe_z": zloss}
    return y.reshape(B, S, D), losses


def moe_apply_onehot(params, cfg: ModelConfig, x, *, capacity_factor: float = 1.25):
    """GShard-style one-hot dispatch — O(N·E·C) memory; test oracle only."""
    B, S, D = x.shape
    N = B * S
    xf = x.reshape(N, D)
    topk_w, topk_i, aux, zloss = router_topk(params, cfg, xf)
    cap = max(int(capacity_factor * N * cfg.experts_per_token / cfg.num_experts), 1)
    cap = -(-cap // 8) * 8
    cap = min(cap, N * cfg.experts_per_token)
    disp, comb = _dispatch_combine(cfg, topk_w, topk_i, N, cap)
    xe = jnp.einsum("nec,nd->ecd", disp.astype(x.dtype), xf)  # (E,C,D)
    ye = _expert_mlp(params, xe)  # (E,C,D)
    y = jnp.einsum("nec,ecd->nd", comb.astype(x.dtype), ye)
    if cfg.num_shared_experts:
        g = jnp.einsum("nd,df->nf", xf, params["shared_wi_gate"])
        u = jnp.einsum("nd,df->nf", xf, params["shared_wi_up"])
        y = y + jnp.einsum("nf,fd->nd", jax.nn.silu(g) * u, params["shared_wo"])
    losses = {"moe_aux": aux, "moe_z": zloss}
    return y.reshape(B, S, D), losses
