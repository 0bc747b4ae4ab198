"""Causal GQA flash attention — Pallas TPU kernel.

TPU adaptation (DESIGN.md §7): online-softmax with q/kv BlockSpec tiling
sized for VMEM (q-block x kv-block tiles feed the 128x128 MXU); the kv loop
is the innermost *sequential* grid dimension, with running (m, l, acc)
carried in VMEM scratch — the standard TPU flash schedule (cf.
jax.experimental.pallas.ops.tpu.flash_attention), rebuilt here explicitly.

GQA layout: the wrapper reshapes q to (B*K, g, Sq, d) so each kv head's
block is loaded once and shared by its g query heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compiler import compiler_params

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_kv: int, causal: bool, sm_scale: float):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)     # (bk, d)
    v = v_ref[0].astype(jnp.float32)     # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale  # (bq,bk)
    if causal:
        iq = pl.program_id(2)
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...][:, None]).astype(o_ref.dtype)


def flash_attention_gqa(
    q: jax.Array,  # (BK, g, Sq, d)
    k: jax.Array,  # (BK, Skv, d)
    v: jax.Array,  # (BK, Skv, d)
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    BK, g, Sq, d = q.shape
    Skv = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    n_kv = Skv // bk
    grid = (BK, g, Sq // bq, n_kv)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=n_kv, causal=causal,
        sm_scale=1.0 / (d ** 0.5),
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda b, h, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda b, h, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running denom
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=compiler_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q, k, v)
