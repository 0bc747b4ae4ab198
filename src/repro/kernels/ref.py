"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True) -> jax.Array:
    """q: (B,H,Sq,d), k/v: (B,K,Skv,d) with H % K == 0. f32 softmax."""
    B, H, Sq, d = q.shape
    K, Skv = k.shape[1], k.shape[2]
    g = H // K
    qg = q.reshape(B, K, g, Sq, d)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = s / (d ** 0.5)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Skv), bool), Skv - Sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, d).astype(q.dtype)


def categorical_logprob_ref(logits, tokens) -> jax.Array:
    """logits: (..., V) f32/bf16; tokens: (...) int32. Returns (...) f32:
    log_softmax(logits)[token] — the LM observe-site hot spot."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tok = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return tok - lse


def ssd_scan_ref(x, dt, A, B, C, *, chunk: int) -> jax.Array:
    """Mamba-2 SSD (see models/ssm.ssd_reference; re-exported here so kernel
    tests depend only on kernels.ref)."""
    from ..models.ssm import ssd_reference

    return ssd_reference(x, dt, A, B, C, chunk)


def semiring_matmul_ref(a, b, *, semiring: str = "logsumexp") -> jax.Array:
    """Log-space semiring matmul: out[..., i, j] = ⊕_k a[..., i, k] + b[..., k, j]
    with ⊕ = logsumexp (sum-product) or max (max-product). Batch dims broadcast.

    The sum-product form uses the shifted-exponential identity
    ``logsumexp_k(a+b) = am + bm + log(exp(a-am) @ exp(b-bm))`` so the inner
    loop is a real matmul instead of a materialized (..., M, K, N) broadcast —
    algebraically identical, and the shift keeps it overflow-safe (this is the
    same rewrite the Pallas kernel uses per tile). Max-plus has no matmul
    identity and keeps the broadcast form.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if semiring == "max":
        return jnp.max(a[..., :, :, None] + b[..., None, :, :], axis=-2)
    if semiring != "logsumexp":
        raise ValueError(f"unknown semiring {semiring!r}")
    am = jnp.max(a, axis=-1, keepdims=True)  # (..., M, 1)
    bm = jnp.max(b, axis=-2, keepdims=True)  # (..., 1, N)
    am_s = jnp.where(jnp.isfinite(am), am, 0.0)  # fully -inf rows stay -inf, not nan
    bm_s = jnp.where(jnp.isfinite(bm), bm, 0.0)
    p = jnp.einsum(
        "...mk,...kn->...mn", jnp.exp(a - am_s), jnp.exp(b - bm_s),
        precision=jax.lax.Precision.HIGHEST,
    )
    return jnp.log(p) + am_s + bm_s


def leapfrog_ref(z, r, inv_mass, step_size, num_steps, potential_fn, *, max_steps):
    """Batched leapfrog oracle for `ops.leapfrog`, in the textbook
    two-half-kicks-per-step form (deliberately *not* the fused kernel's
    shared-gradient rewrite, so parity tests compare independent algebra).

    z, r, inv_mass: (C, D); step_size: (C,) (sign = integration direction);
    num_steps: (C,) int (0 = chain frozen, position/momentum pass through).
    Runs `min(max(num_steps), max_steps)` masked iterations; returns
    (z', r', potential(z')).
    """
    vg = jax.vmap(jax.value_and_grad(potential_fn))
    eps = step_size[:, None].astype(jnp.float32)
    n = num_steps[:, None].astype(jnp.int32)
    nmax = jnp.minimum(jnp.max(n), max_steps)

    def cond(carry):
        return carry[0] < nmax

    def body(carry):
        i, z, r = carry
        active = i < n  # (C, 1)
        _, g = vg(z)
        r2 = r - 0.5 * eps * g
        z2 = z + eps * inv_mass * r2
        _, g2 = vg(z2)
        r2 = r2 - 0.5 * eps * g2
        z = jnp.where(active, z2, z)
        r = jnp.where(active, r2, r)
        return (i + 1, z, r)

    _, z, r = jax.lax.while_loop(cond, body, (jnp.zeros((), jnp.int32), z, r))
    pe, _ = vg(z)
    return z, r, pe


_LOG_2PI = 1.8378770664093453


def _bt(x) -> jax.Array:
    """Batched matrix transpose (swap the trailing two axes)."""
    return jnp.swapaxes(x, -1, -2)


def gaussian_combine_ref(f, g):
    """Associative Kalman combine of two information-form Gaussian edge factors.

    An *edge factor* F(a, b) over a left variable a (width d1) and a right
    variable b (width d2) is the 6-tuple ``(J11, J12, J22, h1, h2, c)``
    encoding

        log F(a, b) = -1/2 [a;b]^T [[J11, J12],[J12^T, J22]] [a;b]
                      + [h1;h2]^T [a;b] + c

    with ``J11: (..., d1, d1)``, ``J12: (..., d1, d2)``, ``J22: (..., d2, d2)``,
    ``h1: (..., d1)``, ``h2: (..., d2)``, ``c: (...)``. Batch dims broadcast.

    The combine integrates out the shared middle variable of F(a, b) · G(b, c):

        (F ⊗ G)(a, c) = ∫ F(a, b) G(b, c) db

    which is exact for Gaussians (Schur complement of the middle block):
    with ``M = F.J22 + G.J11`` and ``hb = F.h2 + G.h1``,

        J11' = F.J11 - F.J12 M⁻¹ F.J12^T
        J12' = -F.J12 M⁻¹ G.J12
        J22' = G.J22 - G.J12^T M⁻¹ G.J12
        h1'  = F.h1 - F.J12 M⁻¹ hb
        h2'  = G.h2 - G.J12^T M⁻¹ hb
        c'   = F.c + G.c + 1/2 hb^T M⁻¹ hb - 1/2 log|M| + (d_b/2) log 2π

    This operator is associative (it is marginalization of a chain graph, and
    integration order over interior variables is exchangeable), which is what
    legalizes the O(log T) tree in `ops.gaussian_scan`. M must be positive
    definite — guaranteed when each factor's diagonal blocks came from genuine
    conditional densities (see kernels/gaussian.py for the conditioning
    contract).
    """
    fJ11, fJ12, fJ22, fh1, fh2, fc = (jnp.asarray(x, jnp.float32) for x in f)
    gJ11, gJ12, gJ22, gh1, gh2, gc = (jnp.asarray(x, jnp.float32) for x in g)
    M = fJ22 + gJ11
    hb = fh2 + gh1
    db = M.shape[-1]
    # broadcast batch dims once so jnp.linalg.solve sees matching operands
    batch = jnp.broadcast_shapes(
        fJ11.shape[:-2], fJ12.shape[:-2], gJ12.shape[:-2], gJ22.shape[:-2],
        M.shape[:-2], hb.shape[:-1], jnp.shape(fc), jnp.shape(gc),
    )
    M = jnp.broadcast_to(M, batch + M.shape[-2:])
    fJ12b = jnp.broadcast_to(fJ12, batch + fJ12.shape[-2:])
    gJ12b = jnp.broadcast_to(gJ12, batch + gJ12.shape[-2:])
    hbb = jnp.broadcast_to(hb, batch + hb.shape[-1:])
    MiFt = jnp.linalg.solve(M, _bt(fJ12b))          # (..., db, d1)
    MiG = jnp.linalg.solve(M, gJ12b)                # (..., db, d2)
    Mih = jnp.linalg.solve(M, hbb[..., None])[..., 0]
    J11 = fJ11 - fJ12 @ MiFt
    J12 = -(fJ12 @ MiG)
    J22 = gJ22 - _bt(gJ12b) @ MiG
    h1 = fh1 - (fJ12b @ Mih[..., None])[..., 0]
    h2 = gh2 - (_bt(gJ12b) @ Mih[..., None])[..., 0]
    _, logdet = jnp.linalg.slogdet(M)
    c = (
        fc + gc + 0.5 * jnp.sum(hbb * Mih, -1)
        - 0.5 * logdet + 0.5 * db * _LOG_2PI
    )
    # Schur complements are symmetric in exact arithmetic; resymmetrize so
    # float error never compounds across a long chain of combines
    J11 = 0.5 * (J11 + _bt(J11))
    J22 = 0.5 * (J22 + _bt(J22))
    return (
        jnp.broadcast_to(J11, batch + J11.shape[-2:]),
        jnp.broadcast_to(J12, batch + J12.shape[-2:]),
        jnp.broadcast_to(J22, batch + J22.shape[-2:]),
        jnp.broadcast_to(h1, batch + h1.shape[-1:]),
        jnp.broadcast_to(h2, batch + h2.shape[-1:]),
        jnp.broadcast_to(c, batch),
    )


def gaussian_scan_ref(factors):
    """Sequential left-fold oracle for `ops.gaussian_scan`: the ordered
    combine F_0 ⊗ F_1 ⊗ ... ⊗ F_{T-1} of a stack of information-form edge
    factors, one `gaussian_combine_ref` at a time (O(T) depth — the allclose
    target for the O(log T) associative-tree path).

    ``factors`` is the edge 6-tuple with a T axis left of each leaf's event
    axes: matrices (..., T, d, d), info vectors (..., T, d), scalar (..., T).
    Returns the single edge factor linking the first left variable to the
    last right variable, every interior variable integrated out.
    """
    J11, J12, J22, h1, h2, c = factors
    T = J11.shape[-3]

    def at(t):
        return (J11[..., t, :, :], J12[..., t, :, :], J22[..., t, :, :],
                h1[..., t, :], h2[..., t, :], c[..., t])

    out = at(0)
    for t in range(1, T):
        out = gaussian_combine_ref(out, at(t))
    return out


def resample_inputs_ref(log_weights) -> jax.Array:
    """Normalized-weight cumsum for systematic resampling, shared by the
    oracle and the kernel dispatch so both backends see bit-identical inputs.

    Degenerate populations (every log-weight ``-inf``, so the normalizer is
    ``-inf`` and self-normalization is 0/0) fall back to uniform weights:
    when every particle is impossible, resampling keeps them all rather than
    propagating NaN through the sweep."""
    lw = jnp.asarray(log_weights, jnp.float32)
    n = lw.shape[-1]
    norm = jax.scipy.special.logsumexp(lw, axis=-1, keepdims=True)
    finite = jnp.isfinite(norm)
    w = jnp.where(
        finite,
        jnp.exp(lw - jnp.where(finite, norm, 0.0)),
        jnp.float32(1.0 / n),
    )
    return jnp.cumsum(w, axis=-1)


def resample_grid_ref(u0, n: int) -> jax.Array:
    """The sorted systematic grid u_i = (u0 + i)/n, u0 ~ U[0, 1)."""
    u0 = jnp.asarray(u0, jnp.float32)
    return (u0 + jnp.arange(n, dtype=jnp.float32)) / n


def systematic_resample_ref(log_weights, u0) -> jax.Array:
    """Systematic-resampling oracle for `ops.resample`: ancestor indices for
    n particles from unnormalized `log_weights` (n,) and one shared uniform
    draw ``u0`` in [0, 1).

    With c the normalized-weight cumsum and u_i = (u0 + i)/n the sorted
    systematic grid, ancestor i is ``#{j : c_j <= u_i}`` — i.e.
    ``searchsorted(c, u, side="right")`` — clipped to n-1 against float
    rounding in the final cumsum entry. Zero-weight particles produce flat
    cumsum runs and are never selected; all-equal weights reproduce the
    identity permutation exactly (u0 < 1 keeps every u_i strictly inside its
    own cumsum cell)."""
    c = resample_inputs_ref(log_weights)
    u = resample_grid_ref(u0, c.shape[-1])
    idx = jnp.searchsorted(c, u, side="right")
    return jnp.minimum(idx, c.shape[-1] - 1).astype(jnp.int32)


def hmm_scan_ref(factors, *, semiring: str = "logsumexp") -> jax.Array:
    """Sequential left-fold oracle for `ops.hmm_scan`: the ordered semiring
    product F_0 ⊗ F_1 ⊗ ... ⊗ F_{T-1} of a (..., T, K, K) stack of log-factors,
    one pairwise `semiring_matmul_ref` at a time (O(T) depth — the allclose
    target for the O(log T) associative-tree path)."""
    out = factors[..., 0, :, :]
    for t in range(1, factors.shape[-3]):
        out = semiring_matmul_ref(out, factors[..., t, :, :], semiring=semiring)
    return out
