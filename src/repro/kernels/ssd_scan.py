"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the paper's GPU SSD kernel (arXiv:2405.21060): each chunk
is a dense (Q x Q) masked quadratic form that runs on the MXU, and the
inter-chunk state recurrence is carried in VMEM scratch across the
*sequential* chunk grid dimension (no warp-level primitives needed — the
TPU grid's sequential-innermost semantics replace the GPU's block-level
state exchange).

Layouts (prepared by ops.ssd_scan):
    x   (B, H, C, Q, P)   head inputs, chunked
    dA  (B, H, C, Q)      dt * A  (negative)
    dt  (B, H, C, Q)
    Bm  (B, C, Q, N)      input  projection (shared across heads)
    Cm  (B, C, Q, N)      output projection (shared across heads)
    out (B, H, C, Q, P)
State scratch: (N, P) float32 per (batch, head), reset at chunk 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compiler import compiler_params


def _ssd_kernel(x_ref, dA_ref, dt_ref, b_ref, c_ref, o_ref, state_ref, *, Q: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)    # (Q, P)
    dA = dA_ref[0, 0, 0].astype(jnp.float32)  # (Q, 1)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (Q, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)      # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)      # (Q, N)

    # Mosaic has no cumsum: the within-chunk prefix sums are matmuls with a
    # triangular 0/1 mask, at full f32 precision so they stay exact sums.
    # The per-step vectors ride as (Q, 1) columns; their (1, Q) row forms
    # come from the same contraction with the lhs transposed.
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = (row >= col).astype(jnp.float32)
    exact = jax.lax.Precision.HIGHEST

    def rows(v, m):  # (Q, 1) column, (Q, Q) mask -> (1, Q) row of v^T m
        return jax.lax.dot_general(v, m, (((0,), (0,)), ((), ())),
                                   precision=exact, preferred_element_type=jnp.float32)

    cum = jnp.dot(lower, dA, precision=exact, preferred_element_type=jnp.float32)  # (Q,1)
    cum_t = rows(dA, lower.T)                            # (1, Q)
    dt_t = rows(dt, (row == col).astype(jnp.float32))    # (1, Q)

    # --- intra-chunk: (L o C B^T) (dt*x) on the MXU ---
    L = jnp.where(row >= col, jnp.exp(cum - cum_t), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    M = CB * L * dt_t
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q,P)

    # --- inter-chunk: y += (C * exp(cum)) @ state_in ---
    state_in = state_ref[...]
    y = y + jax.lax.dot_general(Cm * jnp.exp(cum), state_in,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # --- state update: state = exp(cum_Q) * state_in + B^T (dt * decay * x) ---
    last = jnp.sum(dA)                        # cum[Q-1], as a scalar
    wx = x * (dt * jnp.exp(last - cum))       # (Q,P)
    new_state = jnp.exp(last) * state_in + jax.lax.dot_general(
        Bm, wx, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (N,P)
    state_ref[...] = new_state
    o_ref[0, 0, 0] = y.astype(o_ref.dtype)


def ssd_scan_chunked(
    x: jax.Array,   # (B, H, C, Q, P)
    dA: jax.Array,  # (B, H, C, Q)
    dt: jax.Array,  # (B, H, C, Q)
    Bm: jax.Array,  # (B, C, Q, N)
    Cm: jax.Array,  # (B, C, Q, N)
    *,
    interpret: bool = False,
) -> jax.Array:
    B, H, C, Q, P = x.shape
    N = Bm.shape[-1]
    grid = (B, H, C)  # C innermost => sequential state carry per (B,H)
    return pl.pallas_call(
        functools.partial(_ssd_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, dA[..., None], dt[..., None], Bm, Cm)
