"""Mamba2 / SSD chunked-scan Pallas TPU kernel.

TPU adaptation: the GPU reference uses warp-level parallel
scans; on TPU the intra-chunk work becomes dense (Q×Q)·(Q×P) MXU matmuls
and the inter-chunk recurrence is carried in a VMEM scratch state (P,N)
across the innermost sequential grid axis — no cross-chunk parallel scan
is needed because the grid already serializes chunks per (batch, head).

Grid: (B, H, S/Q) with the chunk axis innermost/sequential.
Blocks (VMEM): x (Q,P), dt (Q,), B/C (Q,N), carry state (P,N) f32 scratch.

  y[i] = C_i · ( Σ_{j<=i} exp(cum_i - cum_j) dt_j B_j x_j^T  +  exp(cum_i) S_prev )
  S_c  = exp(cum_last) S_prev + Σ_j exp(cum_last - cum_j) dt_j B_j x_j^T
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_scr, *, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # (Q,)
    a = a_ref[0].astype(jnp.float32)              # scalar A (negative)
    b = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)           # (Q, N)

    q = x.shape[0]
    cum = jnp.cumsum(dt * a)                      # (Q,) within-chunk decay

    # intra-chunk: (C B^T) ⊙ causal-decay ⊙ dt_j, then MXU matmul with x
    g = jnp.dot(c, b.T, preferred_element_type=jnp.float32)  # (Q,Q)
    seg = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    m = g * tri * dt[None, :]                     # (Q,Q)
    y = jnp.dot(m, x, preferred_element_type=jnp.float32)    # (Q,P)

    # inter-chunk contribution from the carried state
    state = state_scr[...]                        # (P,N)
    y += jnp.exp(cum)[:, None] * jnp.dot(
        c, state.T, preferred_element_type=jnp.float32
    )

    # state update: S = exp(cum_last) S + Σ_j exp(cum_last - cum_j) dt_j x_j B_j^T
    decay_to_end = jnp.exp(cum[-1] - cum) * dt    # (Q,)
    state = jnp.exp(cum[-1]) * state + jnp.dot(
        (x * decay_to_end[:, None]).T, b, preferred_element_type=jnp.float32
    )
    state_scr[...] = state

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        state_out_ref[0, 0] = state.astype(state_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B_, C_, *, chunk: int = 128, interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H) post-softplus; A: (H,) negative;
    B_, C_: (B,S,N).  Returns y (B,S,H,P) and final state (B,H,P,N)."""
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    # (B,H,nc,Q,·) layouts so the chunk axis is a clean grid dimension
    xh = jnp.transpose(x, (0, 2, 1, 3)).reshape(Bsz, H, nc, chunk, P)
    dth = jnp.transpose(dt, (0, 2, 1)).reshape(Bsz, H, nc, chunk)
    bh = B_.reshape(Bsz, nc, chunk, N)
    ch = C_.reshape(Bsz, nc, chunk, N)

    grid = (Bsz, H, nc)
    kernel = functools.partial(_ssd_kernel, nc=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xh, dth, A, bh, ch)
    y = jnp.transpose(y.reshape(Bsz, H, S, P), (0, 2, 1, 3))
    return y, state
