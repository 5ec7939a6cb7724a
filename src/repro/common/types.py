"""Declarative parameter specification system.

Models declare their parameters as nested dicts of :class:`ParamSpec`
(shape + logical sharding axes + initializer).  The same spec tree drives

  * parameter materialization (``init_params``),
  * logical-axis extraction for sharding (``logical_axes``), and
  * stacked-layer variants for scan-over-layers (``stack_specs``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Logical axis vocabulary (mapped to mesh axes by repro.sharding.rules).
AXIS_VOCAB = "vocab"
AXIS_EMBED = "embed"
AXIS_FF = "ff"
AXIS_HEADS = "heads"
AXIS_KV = "kv_heads"
AXIS_EXPERTS = "experts"
AXIS_MOE_FF = "moe_ff"
AXIS_INNER = "inner"
AXIS_STATE = "state"
AXIS_LAYERS = "layers"
AXIS_CONV = "conv"


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative description of one parameter tensor."""

    shape: tuple
    axes: tuple  # one logical-axis name (or None) per dim; len == len(shape)
    init: str = "lecun"  # lecun | normal | zeros | ones | embed | small
    scale: float = 1.0
    dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(
                f"ParamSpec shape {self.shape} and axes {self.axes} rank mismatch"
            )


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _materialize(spec: ParamSpec, key: jax.Array) -> jax.Array:
    shape = spec.shape
    if spec.init == "zeros":
        return jnp.zeros(shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(shape, spec.dtype)
    if spec.init == "normal":
        return (spec.scale * jax.random.normal(key, shape)).astype(spec.dtype)
    if spec.init == "embed":
        return (spec.scale * jax.random.normal(key, shape)).astype(spec.dtype)
    if spec.init == "small":
        return (0.02 * spec.scale * jax.random.normal(key, shape)).astype(spec.dtype)
    if spec.init == "lecun":
        fan_in = shape[-2] if len(shape) >= 2 else max(shape[-1], 1)
        std = spec.scale / math.sqrt(fan_in)
        return (std * jax.random.normal(key, shape)).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def init_params(spec_tree, key: jax.Array, dtype=None):
    """Materialize a spec tree into a parameter pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(spec_tree, is_leaf=_is_spec)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = []
    for spec, k in zip(leaves, keys):
        arr = _materialize(spec, k)
        if dtype is not None and jnp.issubdtype(arr.dtype, jnp.floating):
            arr = arr.astype(dtype)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def logical_axes(spec_tree):
    """Extract the logical-axes tree (same structure, tuples of names)."""
    return jax.tree_util.tree_map(lambda s: s.axes, spec_tree, is_leaf=_is_spec)


def stack_specs(spec_tree, n: int):
    """Prepend a stacked ``layers`` dim to every spec (for scan-over-layers)."""

    def stack(spec: ParamSpec):
        return ParamSpec(
            shape=(n,) + tuple(spec.shape),
            axes=(AXIS_LAYERS,) + tuple(spec.axes),
            init=spec.init,
            scale=spec.scale,
            dtype=spec.dtype,
        )

    return jax.tree_util.tree_map(stack, spec_tree, is_leaf=_is_spec)


def init_stacked(spec_tree, key: jax.Array, n: int, dtype=None):
    """Initialize ``n`` independent copies of a layer spec, stacked on dim 0."""
    keys = jax.random.split(key, n)

    def one(k):
        return init_params(spec_tree, k, dtype=dtype)

    return jax.vmap(one)(keys)


def spec_num_params(spec_tree) -> int:
    leaves = jax.tree_util.tree_leaves(spec_tree, is_leaf=_is_spec)
    return int(sum(int(np.prod(s.shape)) for s in leaves))
