"""Mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

TARGET = {
    "name": "tpu-v5e",
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bytes_per_s": 819e9,
    "ici_bytes_per_s_per_link": 50e9,
    "hbm_bytes": 16e9,
}


def _auto_mesh(shape, axes):
    # the program's shardings are annotations for the compiler to propagate
    # (GSPMD), so every axis is ``Auto``; ``Explicit`` axes would make each
    # op's output sharding part of its type
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh with the production axis names (CPU smoke/integration)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_party_mesh(num_devices: int | None = None):
    """1-D population mesh: the party axis data-parallel over devices.

    Used by :class:`repro.runtime.population.PartyPopulation` to shard
    cohort state (see ``sharding.rules.PARTY_AXIS``).  Defaults to all
    local devices; on a single-device host this yields a 1-device mesh
    whose sharded cycles are bit-identical to the unsharded path.
    """
    n = num_devices if num_devices is not None else jax.local_device_count()
    return _auto_mesh((n,), ("party",))
