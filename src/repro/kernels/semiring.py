"""Fused log-space semiring matmul — Pallas TPU kernel.

The enumeration hot path (tensor variable elimination in
`infer/traceenum_elbo.py`) is a chain of *semiring* contractions: with
``⊕ = logsumexp`` (sum-product) or ``⊕ = max`` (max-product / Viterbi) and
``⊗ = +``, eliminating a discrete latent shared by two log-factors is exactly

    out[i, j] = ⊕_k  a[i, k] + b[k, j]

i.e. a matmul over the (⊕, +) semiring. The naive jnp path materializes the
(M, K, N) broadcast sum in HBM before reducing; this kernel streams (bm, bk)
x (bk, bn) tiles through VMEM with an online-logsumexp accumulator, and the
sum-product inner block is rewritten as a *real* MXU matmul via the shifted
exponential identity

    logsumexp_k(a[i,k] + b[k,j]) = m[i,j] + log( exp(a - am) @ exp(b - bm) )
    with am = max_k a[i,:],  bm = max_k b[:,j],  m = am + bm

(the flash-attention trick applied to the probabilistic-programming layer's
contraction), so nothing (M, K, N)-sized ever exists and the MACs run on the
MXU instead of the VPU. The max-product variant keeps the broadcast form per
tile (max-plus has no MXU identity) but still never leaves VMEM.

Precision note (standard log-matmul-exp tradeoff): the shift bound
``am[i] + bm[j]`` can exceed the true entry-wise max when the row max and
column max come from different k, so terms more than ~88 nats (the f32 exp
underflow point) below the bound flush to exactly 0. For ⊕-marginalization
this is benign — a contribution e^-88 below the dominant term is far past
f32 resolution anyway — but an entry whose *entire* sum lies that far below
the bound returns -inf rather than its (astronomically negative) true value.
The max-product semiring takes no shortcut and is exact.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compiler import compiler_params

NEG_INF = -1e30  # finite -inf stand-in: exp(NEG_INF - anything_real) == 0 in f32

SEMIRINGS = ("logsumexp", "max")


def _semiring_matmul_kernel(a_ref, b_ref, o_ref, m_ref, s_ref, *, nk: int, semiring: str):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        # logsumexp keeps a finite floor (m feeds exp-rescale arithmetic);
        # max-plus must start at the true ⊕-identity or fully -inf entries
        # (structurally impossible transitions) would clamp to NEG_INF and
        # break exactness vs the reference backend
        init = NEG_INF if semiring == "logsumexp" else -jnp.inf
        m_ref[...] = jnp.full_like(m_ref, init)
        s_ref[...] = jnp.zeros_like(s_ref)

    a = a_ref[...].astype(jnp.float32)  # (bm, bk)
    b = b_ref[...].astype(jnp.float32)  # (bk, bn)

    if semiring == "logsumexp":
        am = jnp.max(a, axis=1, keepdims=True)  # (bm, 1)
        bm = jnp.max(b, axis=0, keepdims=True)  # (1, bn)
        # guard fully-masked (-inf) rows/cols: exp(-inf - -inf) would be nan
        am_s = jnp.where(jnp.isfinite(am), am, 0.0)
        bm_s = jnp.where(jnp.isfinite(bm), bm, 0.0)
        # full f32 passes: a default-precision TPU matmul rounds the
        # probabilities to bf16, which exact inference cannot afford
        p = jnp.dot(
            jnp.exp(a - am_s), jnp.exp(b - bm_s),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
        )
        m_cur = am_s + bm_s  # (bm, bn) tile max bound
        m_prev, s_prev = m_ref[...], s_ref[...]
        m_new = jnp.maximum(m_prev, m_cur)
        s_ref[...] = s_prev * jnp.exp(m_prev - m_new) + p * jnp.exp(m_cur - m_new)
        m_ref[...] = m_new
    else:  # max-plus: out = max_k a[i,k] + b[k,j]
        x = a[:, :, None] + b[None, :, :]  # (bm, bk, bn) — VMEM-resident only
        m_ref[...] = jnp.maximum(m_ref[...], jnp.max(x, axis=1))

    @pl.when(ik == nk - 1)
    def _finalize():
        if semiring == "logsumexp":
            o_ref[...] = m_ref[...] + jnp.log(s_ref[...])
        else:
            o_ref[...] = m_ref[...]


def _tile(dim: int, block: int, align: int) -> int:
    """The tile for one dim: the whole dim when it fits in `block`, else
    `block` rounded up to a multiple of `align` (the dim is padded to it)."""
    if dim <= block:
        return dim
    return -(-block // align) * align


def semiring_matmul_tiled(
    a: jax.Array,  # (M, K) log-factor
    b: jax.Array,  # (K, N) log-factor
    *,
    semiring: str = "logsumexp",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """out[i, j] = ⊕_k a[i, k] + b[k, j] over the (⊕, +) log-space semiring.

    2-D only; `kernels/ops.semiring_matmul` adds batch dims and backend
    dispatch. K-padding uses NEG_INF (the ⊕ identity), so ragged shapes are
    exact, not approximately masked.
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}; expected one of {SEMIRINGS}")
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"contracting dims disagree: a is {a.shape}, b is {b.shape}")
    # Mosaic tiles a block's last two dims by (8, 128): each must be a
    # multiple of that or the whole array dim. K is a lane dim of `a` and N
    # of `b`/out, so their tiles round up to 128; M only needs 8.
    bm, bn, bk = _tile(M, block_m, 8), _tile(N, block_n, 128), _tile(K, block_k, 128)
    Mp, Np, Kp = -(-M // bm) * bm, -(-N // bn) * bn, -(-K // bk) * bk
    # K-padding must be the exact ⊕-identity: -inf for max-plus (NEG_INF would
    # leak a finite floor into fully -inf entries); the finite stand-in is fine
    # for logsumexp, whose shifted exp underflows it to exactly 0 either way
    pad = NEG_INF if semiring == "logsumexp" else -jnp.inf
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)), constant_values=pad)
    if (Kp, Np) != (K, N):
        b = jnp.pad(b, ((0, Kp - K), (0, Np - N)), constant_values=pad)
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    out = pl.pallas_call(
        functools.partial(_semiring_matmul_kernel, nk=nk, semiring=semiring),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((bk, bn), lambda im, jn, ik: (ik, jn)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),  # running ⊕-max
            pltpu.VMEM((bm, bn), jnp.float32),  # running shifted sum (logsumexp only)
        ],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(a, b)
    return out[:M, :N]
