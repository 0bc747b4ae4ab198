"""Backend-dispatched public wrappers around the Pallas kernels.

Every op resolves a *kernel backend* and routes to one of three
implementations, so the hot log-prob paths work on every platform CI runs on:

  ``tpu``        compiled Mosaic kernels (requires a TPU jax backend)
  ``interpret``  Pallas interpret mode — the kernel body executed as XLA ops,
                 correct on any platform (what kernel tests exercise on CPU)
  ``reference``  the pure-jnp oracles in `kernels/ref.py` (fastest off-TPU)

Resolution precedence: explicit ``backend=`` argument > the
``REPRO_KERNEL_BACKEND`` env var (``tpu`` / ``interpret`` / ``reference`` /
``auto``) > the legacy ``REPRO_PALLAS_INTERPRET`` flag > platform default
(``tpu`` on TPU, ``reference`` everywhere else). The resolved backend is a
static argument of the underlying jit, so switching backends compiles a
separate executable instead of clobbering one cache entry.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from .. import settings
from . import ref
from .categorical_logprob import categorical_logprob_flat
from .flash_attention import flash_attention_gqa
from .gaussian import gaussian_combine_pairs
from .leapfrog import leapfrog_fused
from .resample import resample_counts_tiled
from .semiring import SEMIRINGS, semiring_matmul_tiled
from .ssd_scan import ssd_scan_chunked

BACKENDS = ("tpu", "interpret", "reference")


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve an explicit/env/platform kernel-backend choice to one of
    `BACKENDS`. See module docstring for precedence."""
    if backend is None:
        backend = settings.get_str("REPRO_KERNEL_BACKEND")
    if backend == "ref":  # convenience alias
        backend = "reference"
    if backend in BACKENDS:
        return backend
    if backend != "auto":
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of {BACKENDS + ('auto',)}"
        )
    legacy = settings.get_raw("REPRO_PALLAS_INTERPRET")
    if legacy is not None:
        resolved = "tpu" if legacy in ("0", "false", "False") else "interpret"
        # anything that isn't 0/false used to silently mean interpret — keep
        # that behavior for compatibility, but say so out loud. FutureWarning
        # (not DeprecationWarning) because the audience is users running
        # scripts with the flag exported, and Python hides DeprecationWarning
        # raised from library code by default.
        warnings.warn(
            f"REPRO_PALLAS_INTERPRET is deprecated (value {legacy!r} resolves to "
            f"{resolved!r}; any value other than '0'/'false' means 'interpret'). "
            "Set REPRO_KERNEL_BACKEND=tpu|interpret|reference|auto instead — see "
            "docs/backends.md for the migration.",
            FutureWarning,
            stacklevel=2,
        )
        return resolved
    return "tpu" if jax.default_backend() == "tpu" else "reference"


# declared per-op support — a new op (or an op dropping a backend) must edit
# this table, and the README matrix mirrors it
_SUPPORT = {
    "flash_attention": ("tpu", "interpret", "reference"),
    "categorical_logprob": ("tpu", "interpret", "reference"),
    "ssd_scan": ("tpu", "interpret", "reference"),
    "semiring_matmul": ("tpu", "interpret", "reference"),
    "hmm_scan": ("tpu", "interpret", "reference"),
    "leapfrog": ("tpu", "interpret", "reference"),
    "gaussian_combine": ("tpu", "interpret", "reference"),
    "gaussian_scan": ("tpu", "interpret", "reference"),
    "resample": ("tpu", "interpret", "reference"),
}


def backend_support_matrix() -> dict:
    """Which backends each op supports (README's support matrix, as data)."""
    return {op: {b: b in sup for b in BACKENDS} for op, sup in _SUPPORT.items()}


def _on_mesh(fn, args, split, mesh, axis=None):
    """Call the Pallas kernel call `fn(*args)` with its batch on `mesh`.

    XLA cannot partition a Mosaic kernel, so under a mesh the call runs per
    device inside `shard_map`: the args flagged in `split` have their leading
    dim split over `axis` (default: the mesh's data axes) when it divides the
    axis size, every other arg is replicated, and the outputs come back split
    the same way. Without a mesh this is just ``fn(*args)``."""
    if mesh is None:
        return fn(*args)
    from jax.sharding import PartitionSpec as P

    from ..distributed.sharding import batch_axes, data_axis_size

    axis = batch_axes(mesh) if axis is None else axis
    n = next(x.shape[0] for x, s in zip(args, split) if s)
    spec = P(axis) if n % data_axis_size(mesh, axis) == 0 else P()
    in_specs = tuple(spec if s else P() for s in split)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False
    )(*args)


# -- flash attention ---------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "backend"))
def _flash_attention(q, k, v, *, causal, block_q, block_k, backend):
    if backend == "reference":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash_attention_kernel(q, k, v, causal, block_q, block_k, backend)


# The LM train step differentiates through attention, and a pallas_call has
# no AD rule of its own: fused forward, reference backward (the same
# function, so the same gradient) — as for the semiring and Gaussian ops.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_kernel(q, k, v, causal, block_q, block_k, backend):
    B, H, Sq, d = q.shape
    K, Skv = k.shape[1], k.shape[2]
    g = H // K
    qr = q.reshape(B, K, g, Sq, d).reshape(B * K, g, Sq, d)
    kr = k.reshape(B * K, Skv, d)
    vr = v.reshape(B * K, Skv, d)
    out = flash_attention_gqa(
        qr, kr, vr, causal=causal, block_q=block_q, block_k=block_k,
        interpret=(backend == "interpret"),
    )
    return out.reshape(B, K, g, Sq, d).reshape(B, H, Sq, d)


def _flash_attention_kernel_fwd(q, k, v, causal, block_q, block_k, backend):
    return _flash_attention_kernel(q, k, v, causal, block_q, block_k, backend), (q, k, v)


def _flash_attention_kernel_bwd(causal, block_q, block_k, backend, res, g):
    _, vjp = jax.vjp(functools.partial(ref.flash_attention_ref, causal=causal), *res)
    return vjp(g)


_flash_attention_kernel.defvjp(_flash_attention_kernel_fwd, _flash_attention_kernel_bwd)


def flash_attention(
    q, k, v, *, causal: bool = True, block_q: int = 256, block_k: int = 512,
    backend: Optional[str] = None,
):
    """q: (B, H, Sq, d); k/v: (B, K, Skv, d), H % K == 0. Returns (B,H,Sq,d)."""
    return _flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        backend=resolve_backend(backend),
    )


# -- categorical log-prob ----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_t", "block_v", "backend"))
def _categorical_logprob(logits, tokens, *, block_t, block_v, backend):
    if backend == "reference":
        return ref.categorical_logprob_ref(logits, tokens)
    V = logits.shape[-1]
    batch_shape = logits.shape[:-1]
    out = categorical_logprob_flat(
        logits.reshape(-1, V), tokens.reshape(-1).astype(jnp.int32),
        block_t=block_t, block_v=block_v, interpret=(backend == "interpret"),
    )
    return out.reshape(batch_shape)


def categorical_logprob(
    logits, tokens, *, block_t: int = 256, block_v: int = 2048,
    backend: Optional[str] = None,
):
    """logits: (..., V); tokens: (...). Returns per-token log p, f32."""
    return _categorical_logprob(
        logits, tokens, block_t=block_t, block_v=block_v,
        backend=resolve_backend(backend),
    )


# -- Mamba-2 SSD scan --------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def _ssd_scan(x, dt, A, B, C, *, chunk, backend):
    if backend == "reference":
        return ref.ssd_scan_ref(x, dt, A, B, C, chunk=chunk)
    b, s, h, p = x.shape
    n = B.shape[-1]
    Q = chunk
    C_ = s // Q
    xr = x.transpose(0, 2, 1, 3).reshape(b, h, C_, Q, p)
    dtr = dt.transpose(0, 2, 1).reshape(b, h, C_, Q).astype(jnp.float32)
    dAr = dtr * A[None, :, None, None]
    Br = B.reshape(b, C_, Q, n)
    Cr = C.reshape(b, C_, Q, n)
    y = ssd_scan_chunked(xr, dAr, dtr, Br, Cr, interpret=(backend == "interpret"))
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, backend: Optional[str] = None):
    """Mamba-2 SSD. x: (b,s,h,p), dt: (b,s,h), A: (h,), B/C: (b,s,n).
    Returns y: (b,s,h,p) float32. s must be a multiple of `chunk`
    (models/ssm.ssd_block pads)."""
    return _ssd_scan(x, dt, A, B, C, chunk=chunk, backend=resolve_backend(backend))


# -- log-space semiring matmul (enumeration hot path) ------------------------


def _semiring_matmul_impl(a, b, *, semiring, block, backend):
    """Batched semiring matmul on a resolved backend (no jit wrapper: called
    both standalone and from inside `_hmm_scan`'s combine)."""
    if backend == "reference":
        return ref.semiring_matmul_ref(a, b, semiring=semiring)
    if 0 in a.shape or 0 in b.shape:
        # degenerate slices (e.g. lax.associative_scan on a length-1 chain)
        # never reach the kernel; the pure-jnp path handles empties exactly
        return ref.semiring_matmul_ref(a, b, semiring=semiring)
    return _semiring_matmul_kernel(a, b, semiring, block, backend)


# The Pallas kernel has no AD rule, but the enumeration engine differentiates
# straight through its contractions (TraceEnum_ELBO SVI steps, the dice-factor
# gradient in discrete_marginals), so the kernel carries a custom VJP: fused
# forward, pure-jnp reference backward. ref.semiring_matmul_ref is the same
# function the kernel computes, so its VJP is the kernel's VJP.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _semiring_matmul_kernel(a, b, semiring, block, backend):
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    a = jnp.broadcast_to(a, batch + a.shape[-2:])
    b = jnp.broadcast_to(b, batch + b.shape[-2:])
    fn = functools.partial(
        semiring_matmul_tiled,
        semiring=semiring,
        block_m=block,
        block_n=block,
        block_k=block,
        interpret=(backend == "interpret"),
    )
    if not batch:
        return fn(a, b)
    out = jax.vmap(fn)(
        a.reshape((-1,) + a.shape[-2:]), b.reshape((-1,) + b.shape[-2:])
    )
    return out.reshape(batch + out.shape[-2:])


def _semiring_matmul_kernel_fwd(a, b, semiring, block, backend):
    return _semiring_matmul_kernel(a, b, semiring, block, backend), (a, b)


def _semiring_matmul_kernel_bwd(semiring, block, backend, res, g):
    a, b = res
    _, vjp = jax.vjp(
        functools.partial(ref.semiring_matmul_ref, semiring=semiring), a, b
    )
    return vjp(g)


_semiring_matmul_kernel.defvjp(_semiring_matmul_kernel_fwd, _semiring_matmul_kernel_bwd)


@functools.partial(jax.jit, static_argnames=("semiring", "block", "backend"))
def _semiring_matmul(a, b, *, semiring, block, backend):
    return _semiring_matmul_impl(a, b, semiring=semiring, block=block, backend=backend)


def semiring_matmul(
    a,
    b,
    *,
    semiring: str = "logsumexp",
    block: int = 128,
    backend: Optional[str] = None,
):
    """Log-space semiring matmul: ``out[..., i, j] = ⊕_k a[..., i, k] + b[..., k, j]``
    with ``⊕ = logsumexp`` (sum-product) or ``max`` (max-product), ``⊗ = +``.
    a: (..., M, K); b: (..., K, N); batch dims broadcast. Returns (..., M, N) f32."""
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}; expected one of {SEMIRINGS}")
    return _semiring_matmul(
        a, b, semiring=semiring, block=block, backend=resolve_backend(backend)
    )


# -- systematic resampling (SMC hot path) -------------------------------------


# Resampling is piecewise-constant in the weights: perturbing a log-weight
# moves an ancestor index only at the measure-zero cell boundaries, so the
# true derivative is zero almost everywhere. The custom VJP makes that
# explicit (zero cotangents to the cumsum and the grid) instead of leaving
# the int32 output's differentiability to ambient float0 plumbing — the
# standard stop-gradient-through-ancestry estimator variational SMC uses;
# `infer.smc.NestedVariational` differentiates through the selected
# particles' continuous values, never through the selection itself.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _resample_counts_kernel(c, u, block, backend):
    counts = resample_counts_tiled(
        c, u, block_u=block, block_c=block, interpret=(backend == "interpret")
    )
    # the clip lives inside the VJP boundary so no int arithmetic is ever
    # differentiated downstream of the kernel
    return jnp.minimum(counts, c.shape[-1] - 1)


def _resample_counts_kernel_fwd(c, u, block, backend):
    return _resample_counts_kernel(c, u, block, backend), (c, u)


def _resample_counts_kernel_bwd(block, backend, res, g):
    c, u = res
    return jnp.zeros_like(c), jnp.zeros_like(u)


_resample_counts_kernel.defvjp(_resample_counts_kernel_fwd, _resample_counts_kernel_bwd)


@functools.partial(jax.jit, static_argnames=("block", "backend", "mesh", "axis"))
def _resample(log_weights, u0, *, block, backend, mesh, axis):
    if backend == "reference":
        return ref.systematic_resample_ref(log_weights, u0)
    n = log_weights.shape[-1]
    # cumsum/grid construction is shared with the oracle, so reference and
    # kernel backends count the exact same comparisons bit-for-bit
    c = ref.resample_inputs_ref(log_weights)
    u = ref.resample_grid_ref(u0, n)
    # each device counts its slice of the grid against the whole cumsum
    return _on_mesh(
        lambda c, u: _resample_counts_kernel(c, u, block, backend),
        (c, u), (False, True), mesh, axis,
    )


def resample(
    log_weights, u0, *, block: int = 256, backend: Optional[str] = None,
    mesh=None, axis=None,
):
    """Systematic resampling: ancestor indices for an SMC particle population.

    log_weights: (N,) unnormalized particle log-weights (``-inf`` = dead
    particle, never selected; an all ``-inf`` population degenerates to
    uniform). u0: scalar uniform draw in [0, 1), shared by the whole sorted
    grid u_i = (u0 + i)/N — one random number per resample event is what
    makes systematic resampling lower-variance than multinomial. Returns (N,)
    int32 ancestor indices, sorted (a free by-product of the sorted-grid
    formulation). Gradients: zero (see `_resample_counts_kernel`).
    ``mesh``/``axis``: the mesh the particle axis is sharded on (see
    `_on_mesh`); ``axis`` defaults to the mesh's data axes."""
    log_weights = jnp.asarray(log_weights)
    if log_weights.ndim != 1:
        raise ValueError(
            f"log_weights must be 1-D (the particle axis), got shape "
            f"{log_weights.shape}; vmap over batch dims instead"
        )
    if log_weights.shape[0] < 1:
        raise ValueError("need at least one particle to resample")
    return _resample(
        log_weights, u0, block=block, backend=resolve_backend(backend),
        mesh=mesh, axis=axis,
    )


# -- fused HMC leapfrog (MCMC hot path) ---------------------------------------


def leapfrog(
    z,
    r,
    inv_mass,
    step_size,
    num_steps,
    potential_fn,
    *,
    max_steps: int,
    block_chains: int = 8,
    backend: Optional[str] = None,
    mesh=None,
):
    """Run a batch of leapfrog trajectories in one fused program.

    z, r, inv_mass: (C, D) — positions, momenta, diagonal inverse mass per
    chain; step_size: (C,) f32 (the *sign* is the integration direction, so
    NUTS runs backward trajectories with a negative step size); num_steps:
    (C,) int (0 freezes a chain: its z/r pass through untouched and it only
    pays the final potential evaluation). potential_fn maps a (D,) vector to
    a scalar potential. Returns ``(z', r', potential(z'))``.

    Unlike the other ops this one takes a *function* argument, so there is no
    jit wrapper here — callers (the MCMC drivers) are jitted already, and the
    resolved backend must be static at their trace time. On the Pallas
    backends the potential is traced once via ``jax.value_and_grad`` →
    ``make_jaxpr`` and replayed inside the kernel; its captured constants
    (model data, transform parameters) become ordinary kernel inputs — see
    `kernels/leapfrog.py` for the closure-conversion details.

    No AD rule on purpose: MCMC never differentiates its own transition, and
    ``jax.grad`` through this op should fail loudly, not silently pick an
    unfused path.

    ``mesh``: the mesh the chain axis is sharded on; the kernel then runs per
    device on its chains (see `_on_mesh`).
    """
    backend = resolve_backend(backend)
    if backend == "reference":
        return ref.leapfrog_ref(
            z, r, inv_mass, step_size, num_steps, potential_fn,
            max_steps=max_steps,
        )
    # traced on an unsharded (D,) shape: the jaxpr is replayed per device
    closed = jax.make_jaxpr(jax.value_and_grad(potential_fn))(
        jax.ShapeDtypeStruct(z.shape[1:], z.dtype)
    )
    consts = [jnp.asarray(c) for c in closed.consts]

    def fused(z, r, inv_mass, step_size, num_steps, *consts):
        return leapfrog_fused(
            z, r, inv_mass, step_size, num_steps, consts,
            jaxpr=closed.jaxpr, max_steps=max_steps, block_chains=block_chains,
            interpret=(backend == "interpret"),
        )

    args = (z, r, inv_mass, step_size, num_steps, *consts)
    return _on_mesh(fused, args, (True,) * 5 + (False,) * len(consts), mesh)


# -- information-form Gaussian combine / Kalman scan (Gaussian semiring) ------

# T-axis position per edge-factor leaf (J11, J12, J22, h1, h2, c): matrices
# carry the chain axis at -3, info vectors at -2, the log-normalizer at -1
_GAUSS_T_AXES = (-3, -3, -3, -2, -2, -1)


def _gauss_slice_t(factors, start, stop, step=1):
    out = []
    for x, ax in zip(factors, _GAUSS_T_AXES):
        idx = [slice(None)] * x.ndim
        idx[ax] = slice(start, stop, step)
        out.append(x[tuple(idx)])
    return tuple(out)


def _gaussian_widths(f):
    """(d_left, d_right) of an edge 6-tuple, from the J12 cross block."""
    return f[1].shape[-2], f[1].shape[-1]


# Like semiring_matmul, the Gaussian combine is differentiated straight
# through (TraceEnum_ELBO objectives, the perturbation trick behind
# gaussian_marginals), so the fused kernel carries a custom VJP with the
# pure-jnp reference as its backward — same function, so same gradient.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gaussian_combine_kernel(f, g, block, backend):
    leaves = f + g
    batch = jnp.broadcast_shapes(
        *(x.shape[:ax + 1 or None] for x, ax in zip(leaves, _GAUSS_T_AXES * 2))
    )

    def flat(x, ax):
        ev = x.shape[ax + 1:] if ax != -1 else ()
        x = jnp.broadcast_to(x, batch + ev)
        return x.reshape((-1,) + ev)

    ff = tuple(flat(x, ax) for x, ax in zip(f, _GAUSS_T_AXES))
    gf = tuple(flat(x, ax) for x, ax in zip(g, _GAUSS_T_AXES))
    out = gaussian_combine_pairs(
        ff, gf, block_b=block, interpret=(backend == "interpret")
    )
    return tuple(
        x.reshape(batch + x.shape[1:]) for x in out
    )


def _gaussian_combine_kernel_fwd(f, g, block, backend):
    return _gaussian_combine_kernel(f, g, block, backend), (f, g)


def _gaussian_combine_kernel_bwd(block, backend, res, ct):
    f, g = res
    _, vjp = jax.vjp(ref.gaussian_combine_ref, f, g)
    return vjp(ct)


_gaussian_combine_kernel.defvjp(_gaussian_combine_kernel_fwd, _gaussian_combine_kernel_bwd)


def _gaussian_combine_impl(f, g, *, block, backend):
    d1, db = _gaussian_widths(f)
    db2, d2 = _gaussian_widths(g)
    if backend == "reference" or not (d1 == db == db2 == d2):
        # ragged widths never reach the kernel (its Gauss-Jordan unroll and
        # lane layout assume one uniform square d); the jnp path is exact
        return ref.gaussian_combine_ref(f, g)
    if any(0 in x.shape for x in f + g):
        return ref.gaussian_combine_ref(f, g)
    return _gaussian_combine_kernel(f, g, block, backend)


@functools.partial(jax.jit, static_argnames=("block", "backend"))
def _gaussian_combine(f, g, *, block, backend):
    return _gaussian_combine_impl(f, g, block=block, backend=backend)


def gaussian_combine(f, g, *, block: int = 256, backend: Optional[str] = None):
    """Integrate out the shared middle variable of two Gaussian edge factors.

    f, g: information-form edge 6-tuples ``(J11, J12, J22, h1, h2, c)`` —
    ``log F(a, b) = -1/2 [a;b]^T J [a;b] + h^T [a;b] + c`` with J11 (..., d1, d1),
    J12 (..., d1, db), J22 (..., db, db), h1 (..., d1), h2 (..., db), c (...).
    g's left width must equal f's right width (db); batch dims broadcast.
    Returns the (..., d1)-by-(..., d2) edge factor of ``∫ F(a, b) G(b, c) db``
    — the associative Kalman-filter combine (see `ref.gaussian_combine_ref`
    for the Schur-complement algebra, `kernels/gaussian.py` for the
    conditioning contract).
    """
    d1, db = _gaussian_widths(f)
    db2, _ = _gaussian_widths(g)
    if db != db2:
        raise ValueError(
            f"middle widths disagree: f's right variable has width {db}, "
            f"g's left variable has width {db2}"
        )
    return _gaussian_combine(
        tuple(f), tuple(g), block=block, backend=resolve_backend(backend)
    )


@functools.partial(jax.jit, static_argnames=("block", "backend"))
def _gaussian_scan(factors, *, block, backend):
    if backend == "reference":
        return ref.gaussian_scan_ref(factors)
    x = factors
    T = x[0].shape[-3]
    # O(log T) associative tree, same shape as _hmm_scan's — except the
    # Gaussian combine has NO identity element (it would need an infinite-
    # precision delta factor), so an odd round carries its unpaired last
    # element forward instead of identity-padding; adjacency is preserved,
    # and associativity makes the regrouping exact
    while T > 1:
        m = (T // 2) * 2
        a = _gauss_slice_t(x, 0, m, 2)
        b = _gauss_slice_t(x, 1, m, 2)
        comb = _gaussian_combine_impl(a, b, block=block, backend=backend)
        if T % 2:
            last = _gauss_slice_t(x, m, T)
            comb = tuple(
                jnp.concatenate([c_, l_], axis=ax)
                for c_, l_, ax in zip(comb, last, _GAUSS_T_AXES)
            )
        x = comb
        T = x[0].shape[-3]
    return tuple(
        jnp.squeeze(x_, axis=ax) for x_, ax in zip(x, _GAUSS_T_AXES)
    )


def gaussian_scan(factors, *, block: int = 256, backend: Optional[str] = None):
    """Eliminate a linear-Gaussian Markov chain in O(log T) depth.

    ``factors`` is an information-form edge 6-tuple stacked along a chain
    axis: matrices (..., T, d, d), info vectors (..., T, d), log-normalizer
    (..., T), where slice t is the edge factor linking chain state t-1 to
    state t. Returns the single (..., d)-by-(..., d) edge factor of the full
    ordered combine F_0 ⊗ F_1 ⊗ ... ⊗ F_{T-1} — every interior state
    integrated out exactly (this *is* the parallel Kalman filter, in
    information form). Associativity of the combine legalizes the log-depth
    tree; the sequential O(T) oracle is `ref.gaussian_scan_ref`.
    """
    factors = tuple(factors)
    if len(factors) != 6:
        raise ValueError(f"expected an edge 6-tuple, got {len(factors)} leaves")
    d1, d2 = _gaussian_widths(factors)
    if d1 != d2:
        raise ValueError(
            f"chain edge factors must have a uniform square width, got ({d1}, {d2})"
        )
    return _gaussian_scan(factors, block=block, backend=resolve_backend(backend))


def _semiring_eye(k: int) -> jax.Array:
    """The semiring identity matrix: 0 on the diagonal, -inf off it —
    M ⊗ I == M exactly for both semirings (the -inf must be genuine: a finite
    stand-in would put a floor under fully -inf entries in max-product)."""
    return jnp.where(jnp.eye(k, dtype=bool), 0.0, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("semiring", "cumulative", "block", "backend"))
def _hmm_scan(factors, *, semiring, cumulative, block, backend):
    combine = functools.partial(
        _semiring_matmul_impl, semiring=semiring, block=block, backend=backend
    )
    if cumulative:
        return jax.lax.associative_scan(combine, factors, axis=-3)
    # total-product reduction: the same O(log T)-depth associative combine that
    # lax.associative_scan uses, minus the prefix completion it would also
    # compute (~2x less work when only the total is needed). Odd rounds pad
    # with the semiring identity, which is exact, not approximate.
    x = factors
    while x.shape[-3] > 1:
        n = x.shape[-3]
        if n % 2:
            eye = jnp.broadcast_to(
                _semiring_eye(x.shape[-1]), x.shape[:-3] + (1,) + x.shape[-2:]
            )
            x = jnp.concatenate([x, eye], axis=-3)
        x = combine(x[..., 0::2, :, :], x[..., 1::2, :, :])
    return x[..., 0, :, :]


def hmm_scan(
    factors,
    *,
    semiring: str = "logsumexp",
    cumulative: bool = False,
    block: int = 128,
    backend: Optional[str] = None,
):
    """Eliminate a Markov chain of K x K log-factors in O(log T) depth.

    factors: (..., T, K, K), where ``factors[..., t, i, j]`` is the log-factor
    linking state i of step t-1 to state j of step t. Returns the ordered
    semiring product ``F_0 ⊗ F_1 ⊗ ... ⊗ F_{T-1}`` — shape (..., K, K) — or,
    with ``cumulative=True``, all T prefix products via `lax.associative_scan`
    (shape (..., T, K, K); the last slice is the total). ``semiring="max"``
    gives the Viterbi (max-product) variant used by
    ``infer_discrete(temperature=0)``. Matmul associativity is what makes the
    log-depth tree legal; the sequential O(T) oracle is `ref.hmm_scan_ref`.
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}; expected one of {SEMIRINGS}")
    if factors.shape[-1] != factors.shape[-2]:
        raise ValueError(f"chain factors must be square, got {factors.shape}")
    return _hmm_scan(
        factors,
        semiring=semiring,
        cumulative=cumulative,
        block=block,
        backend=resolve_backend(backend),
    )
