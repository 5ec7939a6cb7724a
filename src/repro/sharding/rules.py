"""Logical-axis → mesh-axis rules per architecture family.

Every parameter declares *logical* axes (repro.common.types); this module
maps them onto the production mesh axes:

  single pod : (data=16, model=16)
  multi-pod  : (pod=2, data=16, model=16)

The ``pod`` axis is the continuum-tier axis (DESIGN §3): each pod hosts an
independent learning party; nothing inside a compiled step crosses it
except the batch dimension of data-parallel gradients.

Rules are plain dicts ``logical_axis -> mesh axis (or tuple, or None)``.
GSPMD handles non-divisible dims by padding, which we rely on for the
few-KV-head GQA configs (kv=2,4,8 over model=16).
"""
from __future__ import annotations

from typing import Mapping, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import types as T

# ---------------------------------------------------------------------------
# Per-family logical-axis rules
# ---------------------------------------------------------------------------

# Dense / VLM / audio: megatron-style tensor parallelism on the model axis.
_DENSE = {
    T.AXIS_VOCAB: "model",
    T.AXIS_EMBED: None,
    T.AXIS_FF: "model",
    T.AXIS_HEADS: "model",
    T.AXIS_KV: "model",
    T.AXIS_INNER: "model",
    T.AXIS_MOE_FF: "model",
    T.AXIS_EXPERTS: None,
    T.AXIS_STATE: None,
    T.AXIS_LAYERS: None,
    T.AXIS_CONV: None,
}

# MoE: expert parallelism over the data axis (experts=128 → 8/shard;
# 16 → 1/shard), expert-FF over the model axis.  Attention like dense.
_MOE = dict(_DENSE)
_MOE.update({T.AXIS_EXPERTS: "data", T.AXIS_MOE_FF: "model"})

# SSM / hybrid: inner (expand) dim and xLSTM head projections over model.
_SSM = dict(_DENSE)

FAMILY_RULES: Mapping[str, Mapping[str, Optional[str]]] = {
    "dense": _DENSE,
    "vlm": _DENSE,
    "audio": _DENSE,
    "moe": _MOE,
    "ssm": _SSM,
    "hybrid": _SSM,
}


def rules_for(family: str) -> Mapping[str, Optional[str]]:
    return FAMILY_RULES[family]


# ---------------------------------------------------------------------------
# PartitionSpec builders
# ---------------------------------------------------------------------------


def _mesh_axis_sizes(mesh: Mesh) -> Mapping[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def pspec_for_axes(axes: Tuple[Optional[str], ...], rules) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    entries = [rules.get(a) if a is not None else None for a in axes]
    # PartitionSpec forbids using one mesh axis twice; keep first occurrence.
    seen = set()
    out = []
    for e in entries:
        names = e if isinstance(e, tuple) else ((e,) if e else ())
        kept = tuple(n for n in names if n not in seen)
        seen.update(kept)
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(kept)
    return P(*out)


def param_pspecs(spec_tree, family: str):
    """Spec tree → PartitionSpec tree (one per parameter)."""
    rules = rules_for(family)
    return jax.tree_util.tree_map(
        lambda s: pspec_for_axes(s.axes, rules),
        spec_tree,
        is_leaf=lambda x: isinstance(x, T.ParamSpec),
    )


def evenly(pspec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharding on dims the mesh does not divide evenly (jax requires
    evenly divisible *input* shardings; GSPMD padding only applies to
    intermediates)."""
    sizes = _mesh_axis_sizes(mesh)
    entries = list(pspec) + [None] * (len(shape) - len(pspec))
    out = []
    for e, dim in zip(entries, shape):
        names = e if isinstance(e, tuple) else ((e,) if e else ())
        total = 1
        for n in names:
            total *= sizes.get(n, 1)
        out.append(e if total > 1 and dim % total == 0 else (None if total > 1 else e))
    return P(*out)


def param_pspecs_even(spec_tree, family: str, mesh: Mesh):
    """Like param_pspecs but guaranteed valid as jit input shardings."""
    rules = rules_for(family)
    return jax.tree_util.tree_map(
        lambda s: evenly(pspec_for_axes(s.axes, rules), s.shape, mesh),
        spec_tree,
        is_leaf=lambda x: isinstance(x, T.ParamSpec),
    )


def param_shardings(mesh: Mesh, spec_tree, family: str):
    return jax.tree_util.tree_map(
        lambda ps: NamedSharding(mesh, ps), param_pspecs_even(spec_tree, family, mesh)
    )


def opt_state_pspec(param_pspec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """ZeRO-style optimizer-moment sharding (beyond-paper memory saver).

    Adam moments are f32 (2× param bytes each); sharding them only on the
    model axis OOMs the 33B+ configs.  We additionally shard the first
    mesh-unsharded dim over ``data`` when it divides evenly.
    """
    if "data" not in mesh.axis_names:
        return param_pspec
    sizes = _mesh_axis_sizes(mesh)
    entries = list(param_pspec) + [None] * (len(shape) - len(param_pspec))
    used = {n for e in entries for n in ((e,) if isinstance(e, str) else (e or ()))}
    if "data" in used:
        return param_pspec
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % sizes["data"] == 0 and dim >= sizes["data"]:
            entries[i] = "data"
            return P(*entries)
    return param_pspec


def batch_pspec(mesh: Mesh) -> P:
    """Batch-dim sharding: over (pod, data) when the pod axis exists."""
    if "pod" in mesh.axis_names:
        return P(("pod", "data"))
    return P("data")


def batch_shardings(mesh: Mesh, batch_tree):
    """Shard every batch leaf on dim 0 (the global batch dimension)."""
    bp = batch_pspec(mesh)

    def leaf(x):
        nd = len(x.shape)
        return NamedSharding(mesh, P(*([bp[0]] + [None] * (nd - 1))))

    return jax.tree_util.tree_map(leaf, batch_tree)


# ---------------------------------------------------------------------------
# KV / state cache shardings (serve path)
# ---------------------------------------------------------------------------


def cache_pspecs(cache_tree, cfg, mesh: Mesh):
    """Heuristic per-leaf cache sharding.

    - a dim equal to the (global) batch size shards over data when divisible;
    - a KV/SSM/xLSTM heads-like dim shards over model (GSPMD pads uneven);
    - with batch=1 (long_500k) the cache *time* dim shards over data instead.
    """
    sizes = _mesh_axis_sizes(mesh)
    data_ax = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    data_size = 1
    for a in data_ax:
        data_size *= sizes[a]
    data_name = data_ax[0] if len(data_ax) == 1 else data_ax

    model_size = sizes.get("model", 1)
    head_like = {
        cfg.num_kv_heads,
        cfg.num_heads,
        cfg.ssm_heads if cfg.ssm_state else -1,
    }
    head_like.discard(-1)

    def leaf(x):
        shape = tuple(x.shape)
        entries: list = [None] * len(shape)
        batch_done = False
        head_done = False
        for i, d in enumerate(shape):
            if i == 0 and len(shape) > 1:
                continue  # leading stacked-layers dim stays replicated
            if not batch_done and d != 1 and d % data_size == 0 and i <= 2:
                entries[i] = data_name
                batch_done = True
                continue
            if not head_done and d in head_like and i >= 2 and d % model_size == 0:
                entries[i] = "model"
                head_done = True
        if not batch_done:
            # batch=1 decode: shard the largest dim (cache time) over data.
            big = max(range(len(shape)), key=lambda i: shape[i], default=0)
            if shape and shape[big] % data_size == 0 and entries[big] is None:
                entries[big] = data_name
                batch_done = True
        if not head_done:
            # big recurrent-state dims (e.g. mLSTM C: dh×dh) cut over model.
            cands = [
                i
                for i, d in enumerate(shape)
                if entries[i] is None
                and i >= 2
                and d % model_size == 0
                and d >= 2 * model_size
            ]
            if cands:
                big = max(cands, key=lambda i: shape[i])
                entries[big] = "model"
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map(leaf, cache_tree)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Party-axis (population data-parallel) helpers
# ---------------------------------------------------------------------------

# The population mesh is 1-D: every cohort pytree carries a leading party
# axis that shards data-parallel across it (ISSUE 6 / ROADMAP item 1).
PARTY_AXIS = "party"


def party_mesh_size(mesh: Optional[Mesh]) -> int:
    """Number of shards along the party axis (1 without a mesh)."""
    if mesh is None:
        return 1
    return int(_mesh_axis_sizes(mesh).get(PARTY_AXIS, 1))


def party_sharding(mesh: Mesh, tree):
    """Shard every leaf's leading (party) dim over the party axis."""
    sh = NamedSharding(mesh, P(PARTY_AXIS))
    return jax.tree_util.tree_map(lambda _: sh, tree)


def party_shard_map(fn, mesh: Optional[Mesh], *, in_specs, out_specs):
    """Wrap ``fn`` in ``shard_map`` over the party mesh; identity without one.

    ``in_specs``/``out_specs`` may be PartitionSpec pytree prefixes, as
    usual for ``shard_map``.  ``check_vma=False`` because the population
    cycle is a pure per-shard map with no collectives.  Callers that jit
    the result keep a single code path whether or not a mesh exists.
    """
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# In-graph activation constraints (no-ops without a mesh context)
# ---------------------------------------------------------------------------


def _context_axes():
    am = jax.sharding.get_abstract_mesh()
    return tuple(am.axis_names) if am is not None else ()


def constrain(x, *spec_entries):
    """``with_sharding_constraint`` that degrades gracefully.

    Entries name mesh axes (or tuples / None).  Axes absent from the
    context mesh are dropped; with no mesh context (CPU smoke tests) this
    is the identity.  Model code can therefore carry production sharding
    annotations unconditionally.
    """
    axes = set(_context_axes())
    if not axes:
        return x
    cleaned = []
    for e in spec_entries:
        names = e if isinstance(e, tuple) else ((e,) if e else ())
        kept = tuple(n for n in names if n in axes)
        cleaned.append(kept[0] if len(kept) == 1 else (kept or None))
    cleaned += [None] * (len(x.shape) - len(cleaned))
    return jax.lax.with_sharding_constraint(x, P(*cleaned))
