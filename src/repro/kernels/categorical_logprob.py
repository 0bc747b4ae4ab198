"""Fused Categorical(logits).log_prob(token) — Pallas TPU kernel.

This is the paper-specific hot spot (DESIGN.md §7): every LM observe site
evaluates log_softmax(logits)[token] over vocabularies up to 256,000. The
naive path materializes the full (B, S, V) log-prob tensor in HBM; this
kernel streams vocab blocks through VMEM with an online logsumexp (the
flash-softmax trick applied to the PPL's density evaluation) and gathers the
target logit on the fly — HBM traffic drops from 2x(B,S,V) to 1x(B,S,V)
reads + (B,S) writes, and nothing (B,S,V)-sized is ever written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compiler import compiler_params

NEG_INF = -1e30


def _logprob_kernel(logits_ref, tokens_ref, o_ref, m_ref, s_ref, t_ref, *,
                    bt: int, bv: int, n_v: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    x = logits_ref[...].astype(jnp.float32)  # (bt, bv)
    tok = tokens_ref[...][:, 0]              # (bt,)

    # online logsumexp
    m_prev, s_prev = m_ref[...], s_ref[...]
    m_cur = jnp.max(x, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    s_ref[...] = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(x - m_new[:, None]), axis=-1
    )
    m_ref[...] = m_new

    # gather the target logit if it falls in this vocab block
    col = iv * bv + jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    hit = col == tok[:, None]
    t_ref[...] = t_ref[...] + jnp.sum(jnp.where(hit, x, 0.0), axis=-1)

    @pl.when(iv == n_v - 1)
    def _finalize():
        o_ref[...] = (t_ref[...] - (m_ref[...] + jnp.log(s_ref[...])))[:, None]


def categorical_logprob_flat(
    logits: jax.Array,  # (T, V)
    tokens: jax.Array,  # (T,) int32
    *,
    block_t: int = 256,
    block_v: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    T, V = logits.shape
    bt = min(block_t, T)
    bv = min(block_v, V)
    # pad: T to a block multiple (dummy rows), V with NEG_INF columns
    Tp, Vp = -(-T // bt) * bt, -(-V // bv) * bv
    if Tp != T or Vp != V:
        logits = jnp.pad(logits, ((0, Tp - T), (0, Vp - V)), constant_values=NEG_INF)
        tokens = jnp.pad(tokens, (0, Tp - T))
    n_v = Vp // bv
    grid = (Tp // bt, n_v)

    out = pl.pallas_call(
        functools.partial(_logprob_kernel, bt=bt, bv=bv, n_v=n_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bv), lambda it, iv: (it, iv)),
            pl.BlockSpec((bt, 1), lambda it, iv: (it, 0)),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda it, iv: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bt,), jnp.float32),  # running max
            pltpu.VMEM((bt,), jnp.float32),  # running sum
            pltpu.VMEM((bt,), jnp.float32),  # target logit
        ],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(logits, tokens[:, None].astype(jnp.int32))
    return out[:T, 0]
