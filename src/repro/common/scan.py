"""Scan indirection that a caller can unroll.

Programs lower ``lax.scan`` (small HLO, fast compile).  Under
``unroll_scans()`` the same code lowers as a Python loop instead, so one
function can be checked in both forms (``tests/test_models.py``).

``maybe_scan`` is a drop-in for ``jax.lax.scan(body, init, xs)`` at every
depth-axis (and sLSTM time-axis) scan site.
"""
from __future__ import annotations

import contextlib
import threading

import jax

_state = threading.local()


def _unrolling() -> bool:
    return getattr(_state, "unroll", False)


@contextlib.contextmanager
def unroll_scans():
    """Within this context, ``maybe_scan`` unrolls into a Python loop."""
    prev = getattr(_state, "unroll", False)
    _state.unroll = True
    try:
        yield
    finally:
        _state.unroll = prev


def maybe_scan(body, init, xs, length=None):
    """``jax.lax.scan`` unless inside ``unroll_scans()`` (then Python loop)."""
    if not _unrolling():
        return jax.lax.scan(body, init, xs, length=length)
    if xs is None:
        n = length
        slices = [None] * n
    else:
        leaves = jax.tree_util.tree_leaves(xs)
        n = leaves[0].shape[0] if leaves else length
        slices = [jax.tree_util.tree_map(lambda a: a[i], xs) for i in range(n)]
    carry = init
    ys = []
    for s in slices:
        carry, y = body(carry, s)
        ys.append(y)
    if ys and any(leaf is not None
                  for leaf in jax.tree_util.tree_leaves(ys[0])):
        stacked = jax.tree_util.tree_map(
            lambda *a: jax.numpy.stack(a, axis=0), *ys
        )
    else:
        stacked = ys[0] if ys else None
    return carry, stacked
