"""Fused information-form Gaussian combine — Pallas TPU kernel.

The Gaussian-semiring hot path (exact marginalization of linear-Gaussian
latents in `infer/contract/gaussian.py`) is a chain of *edge-factor*
combines: each factor F(a, b) over a left/right variable pair is held in
information form

    log F(a, b) = -1/2 [a;b]^T [[J11, J12],[J12^T, J22]] [a;b] + [h1;h2]^T [a;b] + c

and eliminating the shared middle variable of F(a, b) · G(b, c) is a Schur
complement of the middle block (see `kernels/ref.gaussian_combine_ref` for
the algebra). The combine is associative, so a T-step Kalman chain reduces
in O(log T) rounds of *pairwise* combines — this kernel runs one round: a
large flattened batch of independent (F, G) pairs, one grid step per batch
block, with the middle-block solve done in VMEM via an unrolled Gauss-Jordan
elimination (the state width d is small and static, so every index is
static and the whole inversion is straight-line VPU code — no pivot search,
no gather).

Layout note: the batch is the *last* (lane) axis — refs are (d, d, bb),
(d, bb), (1, bb) — so every elementwise op runs across full 128-lane
vectors regardless of how small d is; d-indexed loops unroll at trace time.

Conditioning contract (the Gaussian analogue of the ~88-nat underflow note
in `kernels/semiring.py`): the middle matrix M = F.J22 + G.J11 is inverted
without pivoting, which is exact-in-spirit only because M is positive
definite by construction — each factor's right diagonal block contains a
genuine conditional precision (Σ⁻¹ of some conditional density), so pivots
are strictly positive. Accuracy degrades linearly with the condition number
κ(M): in f32, expect ~κ(M)·1e-7 relative error in the eliminated marginals,
i.e. results are meaningless once κ(M) approaches 1e7 — e.g. correlations
|ρ| ≳ 1 - 1e-7 or observation noise ~1e-4 times the prior scale. Factors
that well-posed models produce stay far inside the contract (the
conformance suite pins |ρ| = 0.999, κ ≈ 2e3, at rtol 1e-5); rescale your
latents toward unit scale before marginalizing if you are near the edge.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .compiler import compiler_params

LOG_2PI = 1.8378770664093453


def _inv_logdet(M, d: int):
    """Unrolled Gauss-Jordan inverse + log-determinant of a (d, d, bb) stack.

    No pivoting: M must be positive definite (see the conditioning contract
    in the module docstring), so every pivot is strictly positive and
    log(pivot) accumulates log|M| for free. All indices are static — the
    loop unrolls into straight-line elementwise code over the lane axis.
    """
    rows = [M[i] for i in range(d)]                     # each (d, bb)
    bb = M.shape[-1]
    eye_rows = [
        jnp.concatenate(
            [jnp.full((1, bb), 1.0 if j == i else 0.0, jnp.float32) for j in range(d)]
        )
        for i in range(d)
    ]
    inv = eye_rows
    logdet = jnp.zeros((bb,), jnp.float32)
    for k in range(d):
        piv = rows[k][k]                                # (bb,)
        logdet = logdet + jnp.log(piv)
        pivinv = (1.0 / piv)[None, :]
        rows[k] = rows[k] * pivinv
        inv[k] = inv[k] * pivinv
        for i in range(d):
            if i == k:
                continue
            f = rows[i][k][None, :]
            rows[i] = rows[i] - f * rows[k]
            inv[i] = inv[i] - f * inv[k]
    return jnp.concatenate([r[None] for r in inv]), logdet  # (d, d, bb), (bb,)


def _mm(a, b):
    """Lane-batched matmul: a (d1, k, bb) @ b (k, d2, bb) -> (d1, d2, bb).

    Broadcast-multiply-reduce on the VPU — the contracted dim k is tiny and
    static, and the MXU has nothing to offer a (lane-batched, k≤8) product.
    """
    return jnp.sum(a[:, :, None, :] * b[None, :, :, :], axis=1)


def _mv(a, v):
    """Lane-batched matvec: a (d1, k, bb) @ v (k, bb) -> (d1, bb)."""
    return jnp.sum(a * v[None, :, :], axis=1)


def _t(a):
    """Transpose the matrix dims of a (d1, d2, bb) stack."""
    return jnp.swapaxes(a, 0, 1)


def _gaussian_combine_kernel(
    fj11, fj12, fj22, fh1, fh2, fc,
    gj11, gj12, gj22, gh1, gh2, gc,
    oj11, oj12, oj22, oh1, oh2, oc,
    *, d: int,
):
    FJ11, FJ12, FJ22 = fj11[...], fj12[...], fj22[...]
    FH1, FH2 = fh1[...], fh2[...]
    GJ11, GJ12, GJ22 = gj11[...], gj12[...], gj22[...]
    GH1, GH2 = gh1[...], gh2[...]

    M = FJ22 + GJ11                                     # (d, d, bb)
    hb = FH2 + GH1                                      # (d, bb)
    Minv, logdet = _inv_logdet(M, d)

    MiFt = _mm(Minv, _t(FJ12))                          # M⁻¹ F.J12^T
    MiG = _mm(Minv, GJ12)                               # M⁻¹ G.J12
    Mih = _mv(Minv, hb)                                 # M⁻¹ hb

    J11 = FJ11 - _mm(FJ12, MiFt)
    J12 = -_mm(FJ12, MiG)
    J22 = GJ22 - _mm(_t(GJ12), MiG)
    # resymmetrize so float error never compounds across combine rounds
    oj11[...] = 0.5 * (J11 + _t(J11))
    oj12[...] = J12
    oj22[...] = 0.5 * (J22 + _t(J22))
    oh1[...] = FH1 - _mv(FJ12, Mih)
    oh2[...] = GH2 - _mv(_t(GJ12), Mih)
    oc[...] = fc[...] + gc[...] + (
        0.5 * jnp.sum(hb * Mih, axis=0) - 0.5 * logdet + 0.5 * d * LOG_2PI
    )[None, :]


def gaussian_combine_pairs(f, g, *, block_b: int = 256, interpret: bool = False):
    """One round of pairwise information-form combines over a flat batch.

    f, g: edge 6-tuples ``(J11, J12, J22, h1, h2, c)`` with ONE leading batch
    dim N and a uniform square state width d — matrices (N, d, d), info
    vectors (N, d), scalar (N,). Returns the combined 6-tuple, each pair's
    shared middle variable integrated out. `kernels/ops.gaussian_combine`
    adds general batch dims, ragged widths and backend dispatch.

    N is padded to a multiple of ``block_b``; pad entries get M = I (so the
    in-kernel inversion stays finite) and are sliced away on return.
    """
    fJ11 = jnp.asarray(f[0], jnp.float32)
    N, d = fJ11.shape[0], fJ11.shape[-1]
    bb = min(block_b, max(N, 1))
    Np = -(-max(N, 1) // bb) * bb

    half_eye = 0.5 * jnp.eye(d, dtype=jnp.float32)

    def prep(x, kind, diag_pad):
        x = jnp.asarray(x, jnp.float32)
        if Np != N:
            pad_shape = (Np - N,) + x.shape[1:]
            pad = jnp.broadcast_to(half_eye, pad_shape) if diag_pad else jnp.zeros(pad_shape)
            x = jnp.concatenate([x, pad], axis=0)
        if kind == "mat":                               # (Np, d, d) -> (d, d, Np)
            return jnp.transpose(x, (1, 2, 0))
        if kind == "vec":                               # (Np, d) -> (d, Np)
            return jnp.transpose(x, (1, 0))
        return x[None, :]                               # (Np,) -> (1, Np)

    # M = F.J22 + G.J11 on pad entries must be invertible: pad each with I/2
    kinds = ("mat", "mat", "mat", "vec", "vec", "sc")
    inputs = [prep(x, k, False) for x, k in zip(f[:2], kinds[:2])]
    inputs.append(prep(f[2], "mat", True))
    inputs += [prep(x, k, False) for x, k in zip(f[3:], kinds[3:])]
    inputs.append(prep(g[0], "mat", True))
    inputs += [prep(x, k, False) for x, k in zip(g[1:], kinds[1:])]

    mat = jax.ShapeDtypeStruct((d, d, Np), jnp.float32)
    vec = jax.ShapeDtypeStruct((d, Np), jnp.float32)
    sc = jax.ShapeDtypeStruct((1, Np), jnp.float32)
    mat_spec = pl.BlockSpec((d, d, bb), lambda i: (0, 0, i))
    vec_spec = pl.BlockSpec((d, bb), lambda i: (0, i))
    sc_spec = pl.BlockSpec((1, bb), lambda i: (0, i))
    specs = [mat_spec, mat_spec, mat_spec, vec_spec, vec_spec, sc_spec]

    out = pl.pallas_call(
        functools.partial(_gaussian_combine_kernel, d=d),
        grid=(Np // bb,),
        in_specs=specs + specs,
        out_specs=tuple(specs),
        out_shape=(mat, mat, mat, vec, vec, sc),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(*inputs)

    def unprep(x, kind):
        if kind == "mat":
            return jnp.transpose(x, (2, 0, 1))[:N]
        if kind == "vec":
            return jnp.transpose(x, (1, 0))[:N]
        return x[0, :N]

    return tuple(unprep(x, k) for x, k in zip(out, kinds))
