"""Fused HMC leapfrog integrator — Pallas TPU kernel, batched over chains.

The MCMC hot loop is the leapfrog integrator: for every chain, every
transition runs `n` steps of

    r -= eps/2 * grad U(z);   z += eps * M^-1 r;   r -= eps/2 * grad U(z)

The generic path (PR 2) vmapped a per-chain `lax.scan` whose body called
`jax.grad` twice per step and — because `lax.cond` under `vmap` lowers to
`select` — burned `max_num_steps` gradient evaluations per transition no
matter how short the trajectory actually was. This kernel replaces that with
one fused program per *block of chains*:

* the whole trajectory runs inside the kernel: positions, momenta and
  gradients stay in VMEM across steps — zero HBM round-trips between
  leapfrog sub-steps (the flash-attention locality argument applied to the
  sampler);
* the classic "store the gradient" rewrite shares one gradient evaluation
  between the trailing half-kick of step `i` and the leading half-kick of
  step `i+1`, so a trajectory of `n` steps costs `n + 1` gradient
  evaluations instead of `2 n`;
* steps run under a `lax.while_loop` bounded by the *largest live*
  `num_steps` in the block, with per-chain active masks — chains with short
  (or zero: NUTS's frozen chains) trajectories stop paying as soon as every
  chain in their block is done.

The potential is model-specific, so it cannot be baked into the kernel
source: callers trace `jax.value_and_grad(potential_fn)` to a jaxpr *once*
(see `ops.trace_potential`), and the jaxpr's captured constants — model
data, transform parameters — enter the kernel as ordinary Pallas inputs
(Pallas rejects captured constants by design). The kernel body replays the
jaxpr with `jax.core.eval_jaxpr` on VMEM-resident values, `vmap`-ed over the
chain rows of the block.

No `custom_vjp`: MCMC never differentiates through its own transition (the
Metropolis accept is not differentiable anyway), so unlike `semiring.py`
this kernel carries no AD rule — `jax.grad` through `ops.leapfrog` raises,
which is the correct loud failure.

The pure-jnp oracle is `ref.leapfrog_ref`, deliberately written in the
textbook two-half-kicks-per-step form rather than sharing this module's
shared-gradient rewrite — the two are algebraically identical, so the
fused-vs-reference parity test (conformance suite) checks real math, not
just that one function was called twice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .compiler import compiler_params


def shared_grad_leapfrog(z, r, inv_mass, eps, num_steps, max_steps, vg_fn):
    """The masked shared-gradient leapfrog the kernel body runs.

    z, r, inv_mass: (c, D); eps, num_steps: (c, 1); vg_fn: (c, D) ->
    ((c,) potential, (c, D) gradient). Runs `min(max(num_steps), max_steps)`
    iterations of the one-gradient-per-step form with per-chain active
    masks; returns (z', r', potential(z')).
    """
    live = num_steps > 0  # (c, 1)
    nmax = jnp.minimum(jnp.max(num_steps), max_steps)
    _, g0 = vg_fn(z)
    # leading half-kick (only chains that take at least one step)
    r = jnp.where(live, r - 0.5 * eps * g0, r)

    def cond(carry):
        return carry[0] < nmax

    def body(carry):
        i, z, r, g = carry
        active = i < num_steps  # (c, 1)
        z2 = z + eps * inv_mass * r
        _, g2 = vg_fn(z2)
        r2 = r - eps * g2  # full kick; the overshoot is repaid below
        z = jnp.where(active, z2, z)
        r = jnp.where(active, r2, r)
        g = jnp.where(active, g2, g)
        return (i + 1, z, r, g)

    init = (jnp.zeros((), jnp.int32), z, r, g0)
    _, z, r, g = jax.lax.while_loop(cond, body, init)
    # repay half of the final full kick -> trailing half-kick
    r = jnp.where(live, r + 0.5 * eps * g, r)
    pe, _ = vg_fn(z)
    return z, r, pe


def _leapfrog_kernel(
    z_ref, r_ref, minv_ref, eps_ref, n_ref, *rest, jaxpr, const_shapes, max_steps
):
    nconsts = len(const_shapes)
    const_refs = rest[:nconsts]
    zo_ref, ro_ref, pe_ref = rest[nconsts:]
    consts = [
        c[...].reshape(shape) for c, shape in zip(const_refs, const_shapes)
    ]

    def vg_fn(z_block):
        def one(zvec):
            pe, g = jax.core.eval_jaxpr(jaxpr, consts, zvec)
            return pe, g

        return jax.vmap(one)(z_block)

    z, r, pe = shared_grad_leapfrog(
        z_ref[...], r_ref[...], minv_ref[...], eps_ref[...], n_ref[...],
        max_steps, vg_fn,
    )
    zo_ref[...] = z
    ro_ref[...] = r
    pe_ref[...] = pe[:, None]


def leapfrog_fused(
    z: jax.Array,          # (C, D) positions, f32
    r: jax.Array,          # (C, D) momenta, f32
    inv_mass: jax.Array,   # (C, D) diagonal inverse mass
    step_size: jax.Array,  # (C,) per-chain step size (sign = direction)
    num_steps: jax.Array,  # (C,) int32 per-chain step counts (0 = frozen)
    consts,                # jaxpr constants (model data etc.), kernel inputs
    *,
    jaxpr,                 # jaxpr of value_and_grad(potential_fn) on (D,)
    max_steps: int,
    block_chains: int = 8,
    interpret: bool = False,
):
    """Fused leapfrog over a (C, D) block of chains; returns (z', r', pe').

    `kernels/ops.leapfrog` is the public entry point — it resolves the
    backend, traces the potential, and pads C to the block size. Chains are
    edge-padded (repeating the last live row) so padded rows evaluate the
    potential at an in-support point instead of an arbitrary zero vector.
    """
    C, D = z.shape
    bc = min(block_chains, C)
    Cp = -(-C // bc) * bc
    if Cp != C:
        pad = ((0, Cp - C), (0, 0))
        z = jnp.pad(z, pad, mode="edge")
        r = jnp.pad(r, pad, mode="edge")
        inv_mass = jnp.pad(inv_mass, pad, mode="edge")
        step_size = jnp.pad(step_size, ((0, Cp - C),), mode="edge")
        # padded chains take zero steps: they only pay the final pe eval
        num_steps = jnp.pad(num_steps, ((0, Cp - C),))
    consts = [jnp.asarray(c) for c in consts]
    const_shapes = tuple(jnp.shape(c) for c in consts)
    # scalars ride as (1, 1) blocks; everything else keeps its shape
    const_in = [c.reshape((1, 1)) if c.ndim == 0 else c for c in consts]
    grid = (Cp // bc,)

    def _cspec(c):
        return pl.BlockSpec(c.shape, lambda i, nd=c.ndim: (0,) * nd)

    out = pl.pallas_call(
        functools.partial(
            _leapfrog_kernel,
            jaxpr=jaxpr,
            const_shapes=const_shapes,
            max_steps=max_steps,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, D), lambda i: (i, 0)),  # z
            pl.BlockSpec((bc, D), lambda i: (i, 0)),  # r
            pl.BlockSpec((bc, D), lambda i: (i, 0)),  # inv_mass
            pl.BlockSpec((bc, 1), lambda i: (i, 0)),  # eps
            pl.BlockSpec((bc, 1), lambda i: (i, 0)),  # num_steps
        ]
        + [_cspec(c) for c in const_in],
        out_specs=[
            pl.BlockSpec((bc, D), lambda i: (i, 0)),
            pl.BlockSpec((bc, D), lambda i: (i, 0)),
            pl.BlockSpec((bc, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Cp, D), jnp.float32),
            jax.ShapeDtypeStruct((Cp, D), jnp.float32),
            jax.ShapeDtypeStruct((Cp, 1), jnp.float32),
        ],
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )(
        z,
        r,
        inv_mass,
        step_size[:, None].astype(jnp.float32),
        num_steps[:, None].astype(jnp.int32),
        *const_in,
    )
    z_new, r_new, pe = out
    return z_new[:C], r_new[:C], pe[:C, 0]
