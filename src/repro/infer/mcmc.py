"""Hamiltonian Monte Carlo + No-U-Turn Sampler with a multi-chain driver
(paper §2: Pyro "implement[s] several generic probabilistic inference
algorithms, including ... the No U-turn Sampler, a variant of Hamiltonian
Monte Carlo"; §1 positions inference as "scalable": built on GPU-accelerated
tensor math, which here means the whole run compiles to a constant number of
XLA calls).

Kernels are fully jittable: leapfrog, Welford diagonal mass adaptation, and
dual-averaging step size run inside `lax` control flow. NUTS uses iterative
progressive doubling with multinomial sampling along the trajectory and a
subtree U-turn check at each doubling (Hoffman & Gelman 2014; iterative form
after Phan et al. 2019). Step-size and mass-matrix adaptation freeze once
`state.i` passes the warmup length, so collection draws come from a fixed
transition kernel.

The `MCMC` driver runs `num_chains` chains initialized from split PRNG keys.
Warmup (with windowed mass-matrix re-estimation) and collection each run
inside a single `lax.scan`, so one `MCMC.run` issues a constant number of
compiled calls regardless of `num_warmup`/`num_samples`
(`benchmarks/mcmc_chains.py` asserts this). Passing `mesh=` (a Mesh, or
``"auto"`` for the default 1-D device mesh) additionally constrains the
chain axis onto the mesh's data axes via `distributed.sharding.shard_chains`,
which is a no-op transformation of the math — on a 1-device mesh the output
is bit-for-bit identical to the local-vmap default (`mesh=None`). The legacy
`chain_method="vectorized"/"sharded"` spelling survives as a FutureWarning
alias.

Two interiors implement that contract. The default **fused** driver ravels
all chains into one (num_chains, D) matrix and steps them together through
the backend-dispatched `ops.leapfrog` kernel — a shared-gradient integrator
costing n + 1 potential gradients per trajectory (not the textbook 2n) and
only the steps actually taken (not the `max_num_steps` cap). Adaptation is
pooled across chains: one dual-averaged step size from the mean accept
probability, one diagonal mass matrix from a cross-chain Welford
accumulator, and (`HMC(adapt_trajectory_length=True)`) one ChEES-adapted
trajectory length (see `infer/chees.py`). NUTS builds its trees batched:
iterative doubling with per-chain active masks, no per-chain control flow.
The **legacy** per-chain vmap sampler — `REPRO_MCMC_FUSED=0` or
`MCMC(..., fused=False)` — is retained as the benchmark baseline;
`benchmarks/mcmc_bench.py` holds fused to >= 2x its draws/sec at 1024
chains, and `tests/test_mcmc_conformance.py` pins the fused distribution
against closed-form targets under both kernel backends.

Example — two HMC chains on a conjugate model, grouped samples::

    >>> import jax, jax.numpy as jnp
    >>> from repro import distributions as dist
    >>> from repro.core import primitives as P
    >>> from repro.infer import HMC, MCMC
    >>> def model(data):
    ...     loc = P.sample("loc", dist.Normal(0.0, 10.0))
    ...     with P.plate("N", data.shape[0]):
    ...         P.sample("obs", dist.Normal(loc, 1.0), obs=data)
    >>> data = jnp.asarray([1.0, 2.0, 3.0])
    >>> mcmc = MCMC(HMC(model, max_num_steps=16), num_warmup=100,
    ...             num_samples=100, num_chains=2)
    >>> samples = mcmc.run(jax.random.PRNGKey(0), data)
    >>> samples["loc"].shape            # chains flattened by default
    (200,)
    >>> mcmc.get_samples(group_by_chain=True)["loc"].shape
    (2, 100)
    >>> bool(mcmc.get_extra_fields()["diverging"].sum() >= 0)
    True
"""
from __future__ import annotations

import math
import warnings
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from .. import settings, telemetry
from ..kernels import ops
from .chees import ChEESState, chees_init, chees_update, halton_jitter
from .util import init_to_uniform, initialize_model, potential_energy, transform_fn

# ---------------------------------------------------------------------------
# pytree-of-arrays helpers
# ---------------------------------------------------------------------------


def _tree_dot(a, b):
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    return sum(jnp.sum(x * y) for x, y in zip(leaves_a, leaves_b))


def _tree_axpy(alpha, x, y):
    return jax.tree_util.tree_map(lambda xi, yi: alpha * xi + yi, x, y)


def _tree_scale(alpha, x):
    return jax.tree_util.tree_map(lambda xi: alpha * xi, x)


def _tree_where(pred, a, b):
    return jax.tree_util.tree_map(lambda x, y: jnp.where(pred, x, y), a, b)


# ---------------------------------------------------------------------------
# Dual averaging + Welford variance (mass matrix) adaptation
# ---------------------------------------------------------------------------


class DAState(NamedTuple):
    log_step: jax.Array
    log_step_avg: jax.Array
    h_avg: jax.Array
    mu: jax.Array
    t: jax.Array


def da_init(step_size: float) -> DAState:
    return DAState(
        jnp.log(step_size),
        jnp.log(step_size),
        jnp.zeros(()),
        jnp.log(10.0 * step_size),
        jnp.zeros(()),
    )


def da_update(state: DAState, accept_prob: jax.Array, target: float = 0.8) -> DAState:
    t = state.t + 1
    kappa, gamma, t0 = 0.75, 0.05, 10.0
    h = (1 - 1 / (t + t0)) * state.h_avg + (target - accept_prob) / (t + t0)
    log_step = state.mu - jnp.sqrt(t) / gamma * h
    eta = t ** (-kappa)
    log_avg = eta * log_step + (1 - eta) * state.log_step_avg
    return DAState(log_step, log_avg, h, state.mu, t)


class WelfordState(NamedTuple):
    mean: Any
    m2: Any
    n: jax.Array


def welford_init(proto) -> WelfordState:
    zeros = jax.tree_util.tree_map(jnp.zeros_like, proto)
    return WelfordState(zeros, zeros, jnp.zeros(()))


def welford_update(state: WelfordState, sample) -> WelfordState:
    n = state.n + 1
    delta = jax.tree_util.tree_map(lambda s, m: s - m, sample, state.mean)
    mean = jax.tree_util.tree_map(lambda m, d: m + d / n, state.mean, delta)
    delta2 = jax.tree_util.tree_map(lambda s, m: s - m, sample, mean)
    m2 = jax.tree_util.tree_map(lambda a, d, d2: a + d * d2, state.m2, delta, delta2)
    return WelfordState(mean, m2, n)


def welford_variance(state: WelfordState, regularize: bool = True):
    def var(m2):
        v = m2 / jnp.maximum(state.n - 1, 1)
        if regularize:  # Stan's shrinkage toward unit
            v = (state.n / (state.n + 5.0)) * v + 1e-3 * (5.0 / (state.n + 5.0))
        return v

    return jax.tree_util.tree_map(var, state.m2)


def welford_update_batch(mean, m2, n, x):
    """Fold a whole (C, D) batch into a pooled (D,)-per-dim Welford
    accumulator in one shot (Chan et al.'s parallel combine) — the fused
    driver feeds all chains' draws to ONE cross-chain mass-matrix estimate
    per transition instead of C independent ones."""
    c = x.shape[0]
    bmean = jnp.mean(x, axis=0)
    bm2 = jnp.sum(jnp.square(x - bmean), axis=0)
    delta = bmean - mean
    tot = n + c
    mean_new = mean + delta * (c / tot)
    m2_new = m2 + bm2 + jnp.square(delta) * (n * c / tot)
    return mean_new, m2_new, tot


def pooled_variance(m2, n, regularize: bool = True):
    """Variance of a pooled accumulator, with Stan's shrinkage toward unit
    (same regularizer as `welford_variance`, n counted across chains)."""
    v = m2 / jnp.maximum(n - 1.0, 1.0)
    if regularize:
        v = (n / (n + 5.0)) * v + 1e-3 * (5.0 / (n + 5.0))
    return v


# ---------------------------------------------------------------------------
# Leapfrog
# ---------------------------------------------------------------------------


def leapfrog(potential_fn, z, r, inv_mass, step_size, n_steps):
    grad_fn = jax.grad(potential_fn)

    def body(carry, _):
        z, r = carry
        r = _tree_axpy(-0.5 * step_size, grad_fn(z), r)
        z = jax.tree_util.tree_map(lambda zi, ri, mi: zi + step_size * mi * ri, z, r, inv_mass)
        r = _tree_axpy(-0.5 * step_size, grad_fn(z), r)
        return (z, r), None

    (z, r), _ = jax.lax.scan(body, (z, r), None, length=n_steps)
    return z, r


def _kinetic(r, inv_mass):
    return 0.5 * sum(
        jnp.sum(m * jnp.square(ri))
        for ri, m in zip(jax.tree_util.tree_leaves(r), jax.tree_util.tree_leaves(inv_mass))
    )


def _sample_momentum(key, proto, inv_mass):
    leaves, treedef = jax.tree_util.tree_flatten(proto)
    keys = jax.random.split(key, len(leaves))
    inv_leaves = treedef.flatten_up_to(inv_mass)
    rs = [
        jax.random.normal(k, x.shape, jnp.float32) / jnp.sqrt(jnp.clip(m, 1e-10))
        for k, x, m in zip(keys, leaves, inv_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, rs)


# ---------------------------------------------------------------------------
# HMC
# ---------------------------------------------------------------------------


class HMCState(NamedTuple):
    z: Any
    potential: jax.Array
    rng_key: jax.Array
    step_size: jax.Array
    inv_mass: Any
    da: DAState
    welford: Any
    i: jax.Array
    accept_prob: jax.Array
    num_steps: jax.Array  # leapfrog steps taken (diagnostics)
    diverging: jax.Array  # this transition hit an energy error > threshold


class FusedCounters(NamedTuple):
    """The fused drivers' work, summed over every transition of a run
    (warmup and draws). Device arrays; the host sums them when it reads."""

    leapfrog_steps: jax.Array  # (C,) int32 leapfrog steps the chain took
    grad_evals: jax.Array      # (C,) int32 value-and-gradient evaluations
    #                            `ops.leapfrog` reports making on the chain's row
    leapfrog_calls: jax.Array  # () int32 `ops.leapfrog` calls


def _count_work(counters: FusedCounters, steps=0, reports=()) -> FusedCounters:
    """Add leapfrog steps and the `ops.leapfrog_reports` of leapfrog calls."""
    return FusedCounters(
        counters.leapfrog_steps + steps,
        counters.grad_evals + sum(reports),
        counters.leapfrog_calls + len(reports),
    )


class FlatHMCState(NamedTuple):
    """State of the fused batched driver: ALL chains in one struct, positions
    raveled to a (C, D) matrix so the hot loop is dense batched linear
    algebra (and `ops.leapfrog` kernel calls) instead of a vmap of pytree
    traversals. Adaptation state is cross-chain: one step size, one diagonal
    mass matrix, one pooled Welford accumulator, one ChEES trajectory length
    — shared by every chain, which is what lets thousands of short chains
    warm up from each other's statistics."""

    z: jax.Array            # (C, D) unconstrained positions
    potential: jax.Array    # (C,)
    rng_key: jax.Array      # single PRNG key; per-step keys fold in `i`
    step_size: jax.Array    # () shared across chains
    inv_mass: jax.Array     # (D,) shared diagonal inverse mass
    da: DAState             # shared dual-averaging state (scalars)
    wf_mean: jax.Array      # (D,) pooled Welford mean
    wf_m2: jax.Array        # (D,) pooled Welford sum of squared deviations
    wf_n: jax.Array         # () pooled sample count (counts chain-draws)
    chees: ChEESState       # shared trajectory-length adaptation (scalars)
    i: jax.Array            # () transition counter
    accept_prob: jax.Array  # (C,) last accept probabilities
    num_steps: jax.Array    # (C,) int32 leapfrog steps (diagnostics)
    diverging: jax.Array    # (C,) bool divergence flags
    counters: FusedCounters


class HMC:
    # names the fused path's device scopes: ``repro.<scope_name>.<phase>``
    scope_name = "hmc"

    def __init__(
        self,
        model: Optional[Callable] = None,
        potential_fn: Optional[Callable] = None,
        step_size: float = 0.1,
        trajectory_length: float = 2 * math.pi,
        adapt_step_size: bool = True,
        adapt_mass_matrix: bool = True,
        target_accept_prob: float = 0.8,
        max_tree_depth: int = 10,
        max_num_steps: int = 1024,
        adapt_trajectory_length: bool = False,
    ):
        if (model is None) == (potential_fn is None):
            raise ValueError("pass exactly one of model / potential_fn")
        self.model = model
        self._potential_fn = potential_fn
        self.step_size = step_size
        self.trajectory_length = trajectory_length
        self.adapt_step_size = adapt_step_size
        self.adapt_mass_matrix = adapt_mass_matrix
        self.target_accept = target_accept_prob
        self.max_tree_depth = max_tree_depth
        self.max_num_steps = max_num_steps
        # ChEES cross-chain trajectory tuning (fused driver only; needs >= 2
        # chains to carry information — see infer/chees.py). NUTS ignores it:
        # the tree IS its trajectory adaptation.
        self.adapt_trajectory_length = adapt_trajectory_length
        self._transforms = None

    # -- setup ---------------------------------------------------------------
    def setup(self, rng_key, *args, **kwargs):
        """Trace the model once (host-side): returns (potential_fn, dict of
        unconstrained init prototypes). For `potential_fn` kernels the
        prototype is the caller-supplied `init_params`."""
        if self._potential_fn is not None:
            return self._potential_fn, kwargs.pop("init_params")
        pe, transforms, inits = initialize_model(rng_key, self.model, args, kwargs)
        self._transforms = transforms
        return pe, inits

    def init_state(self, rng_key, pe_fn, z0) -> HMCState:
        """Build the kernel state at position `z0`. Pure in (rng_key, z0):
        the multi-chain driver vmaps this over split keys."""
        z0 = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), z0)
        inv_mass = jax.tree_util.tree_map(jnp.ones_like, z0)
        return HMCState(
            z0,
            pe_fn(z0),
            rng_key,
            jnp.asarray(self.step_size, jnp.float32),
            inv_mass,
            da_init(self.step_size),
            welford_init(z0),
            jnp.zeros((), jnp.int32),
            jnp.zeros(()),
            jnp.zeros((), jnp.int32),
            jnp.asarray(False),
        )

    def init(self, rng_key, *args, **kwargs) -> Tuple[HMCState, Callable]:
        key_setup, key_state = jax.random.split(rng_key)
        pe_fn, z0 = self.setup(key_setup, *args, **kwargs)
        if self.model is not None:
            z0 = init_to_uniform(key_setup, z0)
        return self.init_state(key_state, pe_fn, z0), pe_fn

    # -- adaptation bookkeeping shared by HMC and NUTS ------------------------
    def _adapt(self, state: HMCState, accept_prob, z_next, warmup_len):
        """Advance dual-averaging / Welford state while `state.i <
        warmup_len`, freezing both afterwards so collection uses a fixed
        kernel. Returns (da, step_size, welford)."""
        in_warmup = state.i < warmup_len
        if self.adapt_step_size:
            da_new = da_update(state.da, accept_prob, self.target_accept)
            da = _tree_where(in_warmup, da_new, state.da)
            step_size = jnp.where(
                in_warmup, jnp.exp(da.log_step), jnp.exp(da.log_step_avg)
            )
        else:
            da, step_size = state.da, state.step_size
        if self.adapt_mass_matrix:
            wf_new = welford_update(state.welford, z_next)
            welford = _tree_where(in_warmup, wf_new, state.welford)
        else:
            welford = state.welford
        return da, step_size, welford

    # -- one transition (jittable) --------------------------------------------
    def sample_step(self, state: HMCState, pe_fn, warmup_len: int = 0) -> HMCState:
        key, key_mom, key_accept = jax.random.split(state.rng_key, 3)
        r = _sample_momentum(key_mom, state.z, state.inv_mass)
        energy0 = state.potential + _kinetic(r, state.inv_mass)
        n_steps = jnp.clip(
            (self.trajectory_length / state.step_size).astype(jnp.int32), 1, self.max_num_steps
        )
        # fixed upper bound for scan; mask extra steps
        max_steps = self.max_num_steps

        grad_fn = jax.grad(pe_fn)

        def body(carry, i):
            z, r = carry
            do = i < n_steps

            def step(zr):
                z, r = zr
                r = _tree_axpy(-0.5 * state.step_size, grad_fn(z), r)
                z = jax.tree_util.tree_map(
                    lambda zi, ri, mi: zi + state.step_size * mi * ri, z, r, state.inv_mass
                )
                r = _tree_axpy(-0.5 * state.step_size, grad_fn(z), r)
                return z, r

            z, r = jax.lax.cond(do, step, lambda zr: zr, (z, r))
            return (z, r), None

        (z_new, r_new), _ = jax.lax.scan(body, (state.z, r), jnp.arange(max_steps))
        pe_new = pe_fn(z_new)
        energy1 = pe_new + _kinetic(r_new, state.inv_mass)
        delta = energy0 - energy1
        delta = jnp.where(jnp.isnan(delta), -jnp.inf, delta)
        diverging = -delta > 1000.0
        accept_prob = jnp.minimum(1.0, jnp.exp(delta))
        accept = jax.random.uniform(key_accept) < accept_prob
        z = jax.tree_util.tree_map(
            lambda a, b: jnp.where(accept, a, b), z_new, state.z
        )
        potential = jnp.where(accept, pe_new, state.potential)
        da, step_size, welford = self._adapt(state, accept_prob, z, warmup_len)
        return HMCState(
            z, potential, key, step_size, state.inv_mass, da, welford,
            state.i + 1, accept_prob, n_steps, diverging,
        )

    def finalize_warmup(self, state: HMCState) -> HMCState:
        inv_mass = state.inv_mass
        if self.adapt_mass_matrix:
            # only trust the estimate once the current window has >= 2 draws
            # (a freshly reset Welford accumulator would otherwise collapse
            # the mass matrix to the regularizer floor)
            var = welford_variance(state.welford)
            ok = state.welford.n > 1
            inv_mass = _tree_where(ok, var, inv_mass)
        step_size = jnp.exp(state.da.log_step_avg) if self.adapt_step_size else state.step_size
        return state._replace(inv_mass=inv_mass, step_size=step_size)

    # -- fused batched path (all chains at once, ops.leapfrog hot loop) ------
    def fused_init_state(self, rng_key, z_flat, potential) -> FlatHMCState:
        """State for the fused driver: z_flat (C, D), potential (C,)."""
        C, D = z_flat.shape
        return FlatHMCState(
            z_flat,
            potential,
            rng_key,
            jnp.asarray(self.step_size, jnp.float32),
            jnp.ones((D,), jnp.float32),
            da_init(self.step_size),
            jnp.zeros((D,), jnp.float32),
            jnp.zeros((D,), jnp.float32),
            jnp.zeros(()),
            chees_init(self.trajectory_length),
            jnp.zeros((), jnp.int32),
            jnp.zeros((C,)),
            jnp.zeros((C,), jnp.int32),
            jnp.zeros((C,), bool),
            FusedCounters(
                jnp.zeros((C,), jnp.int32),
                jnp.zeros((C,), jnp.int32),
                jnp.zeros((), jnp.int32),
            ),
        )

    def scope(self, phase: str):
        """`jax.named_scope` of a phase of the fused path, for device traces."""
        return jax.named_scope(f"repro.{self.scope_name}.{phase}")

    def _fused_adapt(self, state: FlatHMCState, accept_prob, z_batch, warmup_len):
        """Cross-chain analogue of `_adapt`: dual averaging on the MEAN
        accept probability across chains, pooled Welford over the whole
        (C, D) batch of draws. Frozen once `state.i` passes warmup."""
        in_warmup = state.i < warmup_len
        if self.adapt_step_size:
            da_new = da_update(state.da, jnp.mean(accept_prob), self.target_accept)
            da = _tree_where(in_warmup, da_new, state.da)
            step_size = jnp.where(
                in_warmup, jnp.exp(da.log_step), jnp.exp(da.log_step_avg)
            )
        else:
            da, step_size = state.da, state.step_size
        if self.adapt_mass_matrix:
            wf_new = welford_update_batch(
                state.wf_mean, state.wf_m2, state.wf_n, z_batch
            )
            wf = _tree_where(in_warmup, wf_new, (state.wf_mean, state.wf_m2, state.wf_n))
        else:
            wf = (state.wf_mean, state.wf_m2, state.wf_n)
        return da, step_size, wf

    def fused_sample_step(
        self, state: FlatHMCState, pe_flat, warmup_len: int = 0,
        backend: Optional[str] = None, mesh=None,
    ) -> FlatHMCState:
        """One batched HMC transition for all C chains via `ops.leapfrog`.
        The trajectory length is shared across chains — fixed at
        `trajectory_length`, or Halton-jittered and ChEES-adapted during
        warmup when `adapt_trajectory_length` (see infer/chees.py)."""
        C, D = state.z.shape
        key = jax.random.fold_in(state.rng_key, state.i)
        key_mom, key_accept = jax.random.split(key)
        inv_b = jnp.broadcast_to(state.inv_mass, (C, D))
        r = jax.random.normal(key_mom, (C, D)) / jnp.sqrt(jnp.clip(inv_b, 1e-10))
        energy0 = state.potential + 0.5 * jnp.sum(inv_b * r * r, axis=-1)
        if self.adapt_trajectory_length:
            u = halton_jitter(state.i)
            traj = u * jnp.exp(state.chees.log_tau)
        else:
            u = jnp.ones(())
            traj = jnp.asarray(self.trajectory_length, jnp.float32)
        n = jnp.clip(
            (traj / state.step_size).astype(jnp.int32), 1, self.max_num_steps
        )
        eps_c = jnp.broadcast_to(state.step_size, (C,)).astype(jnp.float32)
        n_c = jnp.broadcast_to(n, (C,)).astype(jnp.int32)
        with ops.leapfrog_reports() as reports:
            z_new, r_new, pe_new = ops.leapfrog(
                state.z, r, inv_b, eps_c, n_c, pe_flat,
                max_steps=self.max_num_steps, backend=backend, mesh=mesh,
            )
        energy1 = pe_new + 0.5 * jnp.sum(inv_b * r_new * r_new, axis=-1)
        delta = energy0 - energy1
        delta = jnp.where(jnp.isnan(delta), -jnp.inf, delta)
        diverging = -delta > 1000.0
        accept_prob = jnp.minimum(1.0, jnp.exp(delta))
        accept = jax.random.uniform(key_accept, (C,)) < accept_prob
        z = jnp.where(accept[:, None], z_new, state.z)
        potential = jnp.where(accept, pe_new, state.potential)
        with self.scope("adapt"):
            da, step_size, (wf_mean, wf_m2, wf_n) = self._fused_adapt(
                state, accept_prob, z, warmup_len
            )
        chees = state.chees
        if self.adapt_trajectory_length:
            chees_new = chees_update(
                state.chees, state.z, z_new, r_new, accept_prob, inv_b, u,
            )
            chees = _tree_where(state.i < warmup_len, chees_new, state.chees)
        return FlatHMCState(
            z, potential, state.rng_key, step_size, state.inv_mass, da,
            wf_mean, wf_m2, wf_n, chees, state.i + 1, accept_prob, n_c,
            diverging, _count_work(state.counters, n_c, reports),
        )

    def fused_finalize_warmup(self, state: FlatHMCState) -> FlatHMCState:
        inv_mass = state.inv_mass
        if self.adapt_mass_matrix:
            ok = state.wf_n > 1
            var = pooled_variance(state.wf_m2, state.wf_n)
            inv_mass = jnp.where(ok, var, inv_mass)
        step_size = (
            jnp.exp(state.da.log_step_avg)
            if self.adapt_step_size
            else state.step_size
        )
        return state._replace(inv_mass=inv_mass, step_size=step_size)


# ---------------------------------------------------------------------------
# NUTS: iterative progressive doubling with multinomial trajectory sampling
# ---------------------------------------------------------------------------


class _TreeState(NamedTuple):
    z_left: Any
    r_left: Any
    z_right: Any
    r_right: Any
    z_proposal: Any
    pe_proposal: jax.Array
    log_weight: jax.Array  # log sum of exp(-energy) over trajectory
    turning: jax.Array
    diverging: jax.Array
    sum_accept: jax.Array
    n_leapfrog: jax.Array


def _subtree_leaf(z_ck, r_ck, z, r, t, direction, inv_mass):
    """Take leaf `t` (0-based, in the order built) of a subtree into the
    iterative U-turn check over its balanced sub-trees (the checkpoint
    scheme of NumPyro's `_is_iterative_turning` and Stan's sub-tree checks).

    An even leaf is stored at checkpoint `popcount(t >> 1)`; an odd leaf
    closes the balanced sub-trees that end at it, and is checked against
    the first leaf of each, held at checkpoints `idx_min..idx_max`. Only
    balanced sub-trees are checked, so the verdict does not depend on the
    direction the subtree was built in, as NUTS's reversibility needs.

    z_ck, r_ck: (K, C, D) checkpoints, K at least the subtree's depth (and
    1); z, r: (C, D) the new leaf; t: scalar int32, the same for every
    chain; direction: (C,) +1 or -1; inv_mass: (C, D).
    Returns the checkpoints and a (C,) bool: a sub-tree ending at the leaf
    makes a U-turn."""
    idx_max = jax.lax.population_count(t >> 1)
    idx_min = idx_max - jax.lax.population_count((t ^ (t + 1)) >> 1) + 1
    slot = jnp.where(t % 2 == 0, idx_max, z_ck.shape[0])  # odd leaves: dropped
    z_ck = z_ck.at[slot].set(z, mode="drop")
    r_ck = r_ck.at[slot].set(r, mode="drop")
    # direction-normalized: dz points forward along the trajectory
    dz = direction[:, None] * (z - z_ck)
    turn = ((jnp.sum(dz * inv_mass * r_ck, axis=-1) < 0)
            | (jnp.sum(dz * inv_mass * r, axis=-1) < 0))
    k = jnp.arange(z_ck.shape[0])[:, None]
    return z_ck, r_ck, jnp.any(turn & (k >= idx_min) & (k <= idx_max), axis=0)


class NUTS(HMC):
    """No-U-Turn sampler. At each doubling j we extend the trajectory by 2^j
    leapfrog steps in a random direction, multinomially sampling a proposal
    within the new subtree (progressive sampling), and stop on a U-turn
    between trajectory endpoints or on divergence."""

    scope_name = "nuts"

    def sample_step(self, state: HMCState, pe_fn, warmup_len: int = 0) -> HMCState:
        key, key_mom, key_dirs, key_accept = jax.random.split(state.rng_key, 4)
        r0 = _sample_momentum(key_mom, state.z, state.inv_mass)
        energy0 = state.potential + _kinetic(r0, state.inv_mass)
        grad_fn = jax.grad(pe_fn)
        step_size = state.step_size
        inv_mass = state.inv_mass
        max_delta = 1000.0

        def one_leapfrog(z, r, direction):
            eps = step_size * direction
            r = _tree_axpy(-0.5 * eps, grad_fn(z), r)
            z = jax.tree_util.tree_map(lambda zi, ri, mi: zi + eps * mi * ri, z, r, inv_mass)
            r = _tree_axpy(-0.5 * eps, grad_fn(z), r)
            return z, r

        def is_turning(z_left, r_left, z_right, r_right):
            dz = jax.tree_util.tree_map(lambda a, b: a - b, z_right, z_left)
            v_left = jax.tree_util.tree_map(lambda m, r: m * r, inv_mass, r_left)
            v_right = jax.tree_util.tree_map(lambda m, r: m * r, inv_mass, r_right)
            return (_tree_dot(dz, v_left) < 0) | (_tree_dot(dz, v_right) < 0)

        def extend_subtree(carry_key, tree: _TreeState, depth_j, direction):
            """Take 2^depth_j leapfrog steps from the chosen end, doing
            progressive multinomial proposal updates step-by-step."""
            n_steps = 2 ** depth_j

            def body(carry, i):
                key, z_end, r_end, z_prop, pe_prop, log_w, turning, diverging, sum_acc, z_sub_first, r_sub_first, started = carry
                do = (i < n_steps) & ~turning & ~diverging

                def step(args):
                    (key, z_end, r_end, z_prop, pe_prop, log_w, turning, diverging,
                     sum_acc, z_first, r_first, started) = args
                    z_new, r_new = one_leapfrog(z_end, r_end, direction)
                    pe_new = pe_fn(z_new)
                    energy_new = pe_new + _kinetic(r_new, inv_mass)
                    delta = energy_new - energy0
                    delta = jnp.where(jnp.isnan(delta), jnp.inf, delta)
                    diverging2 = delta > max_delta
                    log_w_new = -delta  # weight relative to initial energy
                    log_w2 = jnp.logaddexp(log_w, log_w_new)
                    key, key_u = jax.random.split(key)
                    take = jax.random.uniform(key_u) < jnp.exp(log_w_new - log_w2)
                    z_prop2 = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(take, a, b), z_new, z_prop
                    )
                    pe_prop2 = jnp.where(take, pe_new, pe_prop)
                    sum_acc2 = sum_acc + jnp.minimum(1.0, jnp.exp(-delta))
                    # record subtree start for the U-turn check
                    z_first2 = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(started, a, b), z_first, z_new
                    )
                    r_first2 = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(started, a, b), r_first, r_new
                    )
                    # direction-normalized U-turn check: dz always points
                    # "forward" along the trajectory regardless of direction
                    dz = jax.tree_util.tree_map(
                        lambda a, b: direction * (a - b), z_new, z_first2
                    )
                    v_first = jax.tree_util.tree_map(lambda m, r: m * r, inv_mass, r_first2)
                    v_new = jax.tree_util.tree_map(lambda m, r: m * r, inv_mass, r_new)
                    turning2 = (
                        (_tree_dot(dz, v_first) < 0) | (_tree_dot(dz, v_new) < 0)
                    ) & started  # need at least 2 pts
                    return (key, z_new, r_new, z_prop2, pe_prop2, log_w2, turning2,
                            diverging2, sum_acc2, z_first2, r_first2, jnp.asarray(True))

                carry2 = jax.lax.cond(do, step, lambda a: a,
                                      (key, z_end, r_end, z_prop, pe_prop, log_w, turning,
                                       diverging, sum_acc, z_sub_first, r_sub_first, started))
                return carry2, None

            z_end = jax.lax.cond(direction > 0, lambda: tree.z_right, lambda: tree.z_left)
            r_end = jax.lax.cond(direction > 0, lambda: tree.r_right, lambda: tree.r_left)
            init = (carry_key, z_end, r_end, tree.z_proposal, tree.pe_proposal,
                    -jnp.inf, jnp.asarray(False), jnp.asarray(False), jnp.zeros(()),
                    z_end, r_end, jnp.asarray(False))
            out, _ = jax.lax.scan(body, init, jnp.arange(2 ** self.max_tree_depth))
            (key, z_end, r_end, z_prop, pe_prop, log_w_sub, turning, diverging,
             sum_acc, _, _, _) = out
            return key, z_end, r_end, z_prop, pe_prop, log_w_sub, turning, diverging, sum_acc

        # -- progressive doubling loop (unrolled over max_tree_depth) -------
        tree = _TreeState(
            state.z, r0, state.z, r0, state.z, state.potential,
            jnp.zeros(()),  # initial point has weight exp(0)
            jnp.asarray(False), jnp.asarray(False), jnp.zeros(()), jnp.zeros((), jnp.int32),
        )
        key_loop = key_dirs
        for j in range(self.max_tree_depth):
            key_loop, key_dir, key_swap = jax.random.split(key_loop, 3)
            direction = jnp.where(jax.random.bernoulli(key_dir), 1.0, -1.0)
            stop = tree.turning | tree.diverging
            (key_loop, z_end, r_end, z_prop_sub, pe_prop_sub, log_w_sub, turning_sub,
             diverging_sub, sum_acc) = extend_subtree(key_loop, tree, j, direction)
            # biased progressive sampling between old tree and new subtree
            total = jnp.logaddexp(tree.log_weight, log_w_sub)
            take_new = (jax.random.uniform(key_swap) < jnp.exp(log_w_sub - total)) & ~turning_sub & ~diverging_sub
            z_proposal = jax.tree_util.tree_map(
                lambda a, b: jnp.where(take_new & ~stop, a, b), z_prop_sub, tree.z_proposal
            )
            pe_proposal = jnp.where(take_new & ~stop, pe_prop_sub, tree.pe_proposal)
            z_left = jax.tree_util.tree_map(
                lambda new, old: jnp.where((direction < 0) & ~stop, new, old), z_end, tree.z_left
            )
            r_left = jax.tree_util.tree_map(
                lambda new, old: jnp.where((direction < 0) & ~stop, new, old), r_end, tree.r_left
            )
            z_right = jax.tree_util.tree_map(
                lambda new, old: jnp.where((direction > 0) & ~stop, new, old), z_end, tree.z_right
            )
            r_right = jax.tree_util.tree_map(
                lambda new, old: jnp.where((direction > 0) & ~stop, new, old), r_end, tree.r_right
            )
            turning_full = is_turning(z_left, r_left, z_right, r_right)
            tree = _TreeState(
                z_left, r_left, z_right, r_right, z_proposal, pe_proposal,
                jnp.where(stop, tree.log_weight, total),
                tree.turning | turning_sub | turning_full,
                tree.diverging | diverging_sub,
                tree.sum_accept + jnp.where(stop, 0.0, sum_acc),
                tree.n_leapfrog + jnp.where(stop, 0, 2 ** j),
            )

        accept_prob = tree.sum_accept / jnp.maximum(tree.n_leapfrog, 1)
        da, step_size, welford = self._adapt(
            state, accept_prob, tree.z_proposal, warmup_len
        )
        return HMCState(
            tree.z_proposal, tree.pe_proposal, key, step_size, state.inv_mass, da,
            welford, state.i + 1, accept_prob, tree.n_leapfrog, tree.diverging,
        )

    # -- fused batched path: tree building vectorized across the chain axis --
    def fused_sample_step(
        self, state: FlatHMCState, pe_flat, warmup_len: int = 0,
        backend: Optional[str] = None, mesh=None,
    ) -> FlatHMCState:
        """One batched NUTS transition: the iterative doubling loop runs ONCE
        for the whole (C, D) batch with per-chain direction draws and active
        masks, so every leapfrog step in the trajectory is a single
        `ops.leapfrog` call over all chains (`num_steps` 1 where the chain is
        still growing its tree, 0 where it has stopped) — the chain batch
        never leaves the mesh. The doubling-j subtree is a while loop of at
        most 2^j steps that ends as soon as none of its chains is still
        growing, so a transition makes as many calls as the longest tree in
        the batch has steps (`FusedCounters.leapfrog_calls`), and a level no
        chain enters makes none. Random numbers are keyed by level and step,
        so a chain's draws do not depend on how long the others' trees run.
        Inside a subtree a U-turn is checked over its balanced sub-trees
        (`_subtree_leaf`), as reversibility needs."""
        C, D = state.z.shape
        key = jax.random.fold_in(state.rng_key, state.i)
        key_mom, key_loop = jax.random.split(key)
        inv_b = jnp.broadcast_to(state.inv_mass, (C, D))
        r0 = jax.random.normal(key_mom, (C, D)) / jnp.sqrt(jnp.clip(inv_b, 1e-10))
        energy0 = state.potential + 0.5 * jnp.sum(inv_b * r0 * r0, axis=-1)
        eps = jnp.broadcast_to(state.step_size, (C,)).astype(jnp.float32)
        max_delta = 1000.0

        def row_dot(a, b):
            return jnp.sum(a * b, axis=-1)

        # trajectory state, one row per chain
        z_left = z_right = z_prop = state.z
        r_left = r_right = r0
        pe_prop = state.potential
        log_w = jnp.zeros((C,))           # initial point has weight exp(0)
        turning = jnp.zeros((C,), bool)
        diverging = jnp.zeros((C,), bool)
        sum_acc = jnp.zeros((C,))
        n_leap = jnp.zeros((C,), jnp.int32)
        work = state.counters

        for j in range(self.max_tree_depth):
            key_j = jax.random.fold_in(key_loop, j)
            key_dir, key_swap, key_in = jax.random.split(key_j, 3)
            dirs = jnp.where(jax.random.bernoulli(key_dir, 0.5, (C,)), 1.0, -1.0)
            stop = turning | diverging  # chains whose tree is finished
            fwd = (dirs > 0)[:, None]
            z_end = jnp.where(fwd, z_right, z_left)
            r_end = jnp.where(fwd, r_right, r_left)

            def more(carry, n=2 ** j, stop=stop):
                t, s_turn, s_div = carry[0], carry[6], carry[7]
                return (t < n) & jnp.any(~stop & ~s_turn & ~s_div)

            def body(carry, dirs=dirs, stop=stop, key_in=key_in):
                (t, z_e, r_e, z_p, pe_p, lw, s_turn, s_div, s_acc,
                 z_ck, r_ck, taken, work) = carry
                active = ~stop & ~s_turn & ~s_div
                # a device trace names an op after the innermost scope over
                # it: the kernel keeps the name it had in a scan's body
                # (`closed_call`), not the while loop's `body`
                with ops.leapfrog_reports() as reports, jax.named_scope("closed_call"):
                    z_n, r_n, pe_n = ops.leapfrog(
                        z_e, r_e, inv_b, eps * dirs, active.astype(jnp.int32),
                        pe_flat, max_steps=1, backend=backend, mesh=mesh,
                    )
                e_n = pe_n + 0.5 * jnp.sum(inv_b * r_n * r_n, axis=-1)
                delta = e_n - energy0
                delta = jnp.where(jnp.isnan(delta), jnp.inf, delta)
                div_n = delta > max_delta
                lw_n = -delta
                lw2 = jnp.logaddexp(lw, lw_n)
                take = (
                    jax.random.uniform(jax.random.fold_in(key_in, t), (C,))
                    < jnp.exp(lw_n - lw2)
                )
                upd = active
                sel = upd & take
                z_p = jnp.where(sel[:, None], z_n, z_p)
                pe_p = jnp.where(sel, pe_n, pe_p)
                s_acc = s_acc + jnp.where(upd, jnp.minimum(1.0, jnp.exp(-delta)), 0.0)
                z_ck, r_ck, turn_n = _subtree_leaf(z_ck, r_ck, z_n, r_n, t, dirs, inv_b)
                s_turn = s_turn | (upd & turn_n)
                s_div = s_div | (upd & div_n)
                lw = jnp.where(upd, lw2, lw)
                z_e = jnp.where(upd[:, None], z_n, z_e)
                r_e = jnp.where(upd[:, None], r_n, r_e)
                taken = taken + upd.astype(jnp.int32)
                work = _count_work(work, reports=reports)
                return (t + 1, z_e, r_e, z_p, pe_p, lw, s_turn, s_div, s_acc,
                        z_ck, r_ck, taken, work)

            # the balanced sub-trees' first leaves (`_subtree_leaf`)
            ckpts = jnp.zeros((max(j, 1), C, D), state.z.dtype)
            init = (
                jnp.zeros((), jnp.int32),
                z_end, r_end, z_prop, pe_prop, jnp.full((C,), -jnp.inf),
                jnp.zeros((C,), bool), jnp.zeros((C,), bool), jnp.zeros((C,)),
                ckpts, ckpts, jnp.zeros((C,), jnp.int32), work,
            )
            with self.scope("tree"):
                (_, z_end, r_end, z_ps, pe_ps, lw_sub, turn_sub, div_sub, acc_sub,
                 _, _, taken, work) = jax.lax.while_loop(more, body, init)

            # biased progressive sampling between the old tree and the subtree
            total = jnp.logaddexp(log_w, lw_sub)
            take_new = (
                (jax.random.uniform(key_swap, (C,)) < jnp.exp(lw_sub - total))
                & ~turn_sub & ~div_sub & ~stop
            )
            z_prop = jnp.where(take_new[:, None], z_ps, z_prop)
            pe_prop = jnp.where(take_new, pe_ps, pe_prop)
            move = ~stop
            grow_l = ((dirs < 0) & move)[:, None]
            grow_r = ((dirs > 0) & move)[:, None]
            z_left = jnp.where(grow_l, z_end, z_left)
            r_left = jnp.where(grow_l, r_end, r_left)
            z_right = jnp.where(grow_r, z_end, z_right)
            r_right = jnp.where(grow_r, r_end, r_right)
            dzf = z_right - z_left
            turn_full = (
                (row_dot(dzf, inv_b * r_left) < 0)
                | (row_dot(dzf, inv_b * r_right) < 0)
            )
            log_w = jnp.where(move, total, log_w)
            turning = turning | turn_sub | (move & turn_full)
            diverging = diverging | div_sub
            sum_acc = sum_acc + acc_sub  # already masked per chain
            n_leap = n_leap + taken

        accept_prob = sum_acc / jnp.maximum(n_leap, 1)
        with self.scope("adapt"):
            da, step_size, (wf_mean, wf_m2, wf_n) = self._fused_adapt(
                state, accept_prob, z_prop, warmup_len
            )
        return FlatHMCState(
            z_prop, pe_prop, state.rng_key, step_size, state.inv_mass, da,
            wf_mean, wf_m2, wf_n, state.chees, state.i + 1, accept_prob,
            n_leap, diverging, _count_work(work, steps=n_leap),
        )


# ---------------------------------------------------------------------------
# MCMC driver: multi-chain, scan-based, optionally mesh-sharded
# ---------------------------------------------------------------------------


class MCMC:
    """Multi-chain MCMC engine.

    `run` initializes `num_chains` kernel states from split PRNG keys, runs
    warmup (with windowed mass-matrix re-estimation) and sample collection
    inside `lax.scan` — the entire run is ONE jit-compiled call, so the
    number of XLA dispatches is constant in `num_warmup` and `num_samples`.

    fused:
      * ``True`` (the default; env override ``REPRO_MCMC_FUSED=0``) — all
        chains step together as one (num_chains, D) batch through the
        backend-dispatched `ops.leapfrog` kernel, with adaptation pooled
        across chains (shared step size / mass matrix / optional ChEES
        trajectory length). The raw-speed path, >= 2x legacy draws/sec at
        1024 chains (`benchmarks/mcmc_bench.py`).
      * ``False`` — the legacy interior: the per-chain program is vmapped
        over the chain axis, each chain adapts independently. Kept as the
        benchmark baseline.

    mesh (the canonical sharding knob, shared with the ELBOs and SMC):
      * ``None`` — chains ride a plain local `vmap` (default);
      * ``"auto"`` — identical computation, but the chain axis is
        constrained onto the data axes of a default 1-D mesh over all
        local devices via the PR-1 sharding rules, distributing chains
        across devices. On a 1-device mesh this is bit-for-bit identical
        to ``mesh=None``;
      * a `jax.sharding.Mesh` — same, on the given mesh.

    chain_method (deprecated):
      the pre-unification spelling. ``chain_method="vectorized"`` means
      ``mesh=None``; ``chain_method="sharded"`` means ``mesh="auto"``
      (or the explicitly passed mesh). Passing it emits a FutureWarning;
      `self.chain_method` remains readable either way.

    Samples come back as ``{site: (num_chains, num_samples, ...)}`` via
    ``get_samples(group_by_chain=True)`` (flattened to
    ``(num_chains * num_samples, ...)`` by default); per-draw diagnostics
    (accept prob, divergences, step counts, energies) via
    ``get_extra_fields``.
    """

    def __init__(
        self,
        kernel: HMC,
        num_warmup: int,
        num_samples: int,
        num_chains: int = 1,
        thinning: int = 1,
        chain_method: Optional[str] = None,
        mesh=None,
        fused: Optional[bool] = None,
    ):
        if chain_method is not None:
            warnings.warn(
                "MCMC(chain_method=...) is deprecated; pass mesh= instead "
                "(mesh=None for the local vmap, mesh='auto' or a "
                "jax.sharding.Mesh to shard chains across devices).",
                FutureWarning,
                stacklevel=2,
            )
            if chain_method not in ("vectorized", "sharded"):
                raise ValueError(
                    f"chain_method must be 'vectorized' or 'sharded', got {chain_method!r}"
                )
            if chain_method == "sharded":
                mesh = "auto" if mesh is None else mesh
            else:
                # vectorized historically ignored any mesh argument
                mesh = None
        if isinstance(mesh, str):
            if mesh != "auto":
                raise ValueError(
                    f"mesh must be None, 'auto', or a jax.sharding.Mesh, got {mesh!r}"
                )
            from ..distributed.sharding import default_mesh

            mesh = default_mesh()
        if num_chains < 1:
            raise ValueError("num_chains must be >= 1")
        if fused is None:
            # default ON; REPRO_MCMC_FUSED=0 keeps the per-chain vmap path
            # (the pre-fused baseline benchmarks compare against)
            fused = settings.get_bool("REPRO_MCMC_FUSED")
        self.fused = fused
        self.kernel = kernel
        self.num_warmup = num_warmup
        self.num_samples = num_samples
        self.num_chains = num_chains
        self.thinning = thinning
        self.mesh = mesh
        self.chain_method = "sharded" if mesh is not None else "vectorized"
        self._samples = None  # {site: (C, S, ...)} constrained space
        self._extra_fields = None  # {field: (C, S)}
        self._last_state = None
        # incremented each time the fused driver is *traced*; the benchmark
        # asserts this stays at 1 per run regardless of num_samples, and that
        # a second run with the same arg shapes reuses the executable
        self.num_traces = 0
        self._exec = None  # cached jitted driver
        self._exec_key = None

    # -- the legacy per-chain driver -----------------------------------------
    def _build_driver(self, randomize: bool, treedef, is_dyn, static_leaves):
        """Build the legacy (init -> warmup -> collect) program. Model args
        ride the traced signature (array leaves in `is_dyn` positions) so
        repeat runs with fresh keys/data of the same shapes reuse one
        compiled executable; non-array leaves are baked in statically."""
        kernel = self.kernel
        transforms = kernel._transforms
        W, S, T = self.num_warmup, self.num_samples, self.thinning
        win = max(1, W // 2)
        mesh = self.mesh
        adapt_mm = kernel.adapt_mass_matrix
        if mesh is not None:
            from ..distributed.sharding import shard_chains

        def make_pe(dyn_leaves):
            if kernel.model is None:
                return kernel._potential_fn
            it = iter(dyn_leaves)
            merged = [next(it) if d else s for d, s in zip(is_dyn, static_leaves)]
            margs, mkwargs = jax.tree_util.tree_unflatten(treedef, merged)
            return partial(potential_energy, kernel.model, margs, mkwargs, transforms)

        def one_chain(state, pe_fn):
            def warmup_body(s, i):
                s = kernel.sample_step(s, pe_fn, W)
                if adapt_mm:
                    # windowed re-estimation: swap in the current Welford
                    # variance and restart the accumulator at each interior
                    # window boundary; the final window feeds finalize_warmup
                    do = ((i + 1) % win == 0) & (i + 1 < W)
                    s = jax.lax.cond(
                        do,
                        lambda s: s._replace(
                            inv_mass=welford_variance(s.welford),
                            welford=welford_init(s.z),
                        ),
                        lambda s: s,
                        s,
                    )
                return s, None

            if W > 0:
                state, _ = jax.lax.scan(warmup_body, state, jnp.arange(W))
            state = kernel.finalize_warmup(state)

            def collect_body(s, _):
                if T > 1:
                    # a divergence anywhere in the thinned block must surface,
                    # not just one on the kept draw — OR the flags through
                    def thin_step(carry, _):
                        s, div = carry
                        s = kernel.sample_step(s, pe_fn, W)
                        return (s, div | s.diverging), None

                    (s, diverging), _ = jax.lax.scan(
                        thin_step, (s, jnp.asarray(False)), None, length=T
                    )
                else:
                    s = kernel.sample_step(s, pe_fn, W)
                    diverging = s.diverging
                extras = {
                    "accept_prob": s.accept_prob,
                    "diverging": diverging,
                    "num_steps": s.num_steps,
                    "potential_energy": s.potential,
                    "step_size": s.step_size,
                }
                return s, (s.z, extras)

            state, (z, extras) = jax.lax.scan(collect_body, state, None, length=S)
            return state, z, extras

        def driver(chain_keys, proto, dyn_leaves):
            self.num_traces += 1  # trace-time side effect (retrace detector)
            pe_fn = make_pe(dyn_leaves)

            def init_one(key, z0):
                if randomize:
                    z0 = init_to_uniform(key, z0)
                return kernel.init_state(key, pe_fn, z0)

            states = jax.vmap(init_one)(chain_keys, proto)
            if mesh is not None:
                states = shard_chains(states, mesh)
            states, z, extras = jax.vmap(partial(one_chain, pe_fn=pe_fn))(states)
            if mesh is not None:
                z = shard_chains(z, mesh)
                extras = shard_chains(extras, mesh)
            return states, z, extras

        return driver

    def _build_fused_driver(
        self, randomize: bool, treedef, is_dyn, static_leaves, backend: str
    ):
        """The fused batched program: positions raveled to one (C, D) matrix,
        transitions stepped for ALL chains at once through `ops.leapfrog` on
        the resolved kernel backend, adaptation pooled across chains. Same
        external contract as `_build_driver` (one trace per run, samples as
        {site: (C, S, ...)}), different interior: no per-chain vmap, so
        cross-chain statistics (shared dual averaging, pooled Welford, ChEES)
        are ordinary batch reductions."""
        kernel = self.kernel
        transforms = kernel._transforms
        W, S, T, C = self.num_warmup, self.num_samples, self.thinning, self.num_chains
        win = max(1, W // 2)
        mesh = self.mesh
        adapt_mm = kernel.adapt_mass_matrix
        if mesh is not None:
            from ..distributed.sharding import shard_chains

        def make_pe(dyn_leaves):
            if kernel.model is None:
                return kernel._potential_fn
            it = iter(dyn_leaves)
            merged = [next(it) if d else s for d, s in zip(is_dyn, static_leaves)]
            margs, mkwargs = jax.tree_util.tree_unflatten(treedef, merged)
            return partial(potential_energy, kernel.model, margs, mkwargs, transforms)

        def shard_state(s: FlatHMCState) -> FlatHMCState:
            # only the chain-major leaves ride the mesh's data axes — the
            # shared adaptation scalars/vectors are replicated by definition
            if mesh is None:
                return s
            batch = {
                "z": s.z, "potential": s.potential, "accept_prob": s.accept_prob,
                "num_steps": s.num_steps, "diverging": s.diverging,
            }
            batch = shard_chains(batch, mesh)
            c = s.counters
            steps, evals = shard_chains((c.leapfrog_steps, c.grad_evals), mesh)
            return s._replace(
                **batch, counters=c._replace(leapfrog_steps=steps, grad_evals=evals)
            )

        def driver(chain_keys, proto, dyn_leaves):
            self.num_traces += 1  # trace-time side effect (retrace detector)
            pe_fn = make_pe(dyn_leaves)
            z0 = proto
            if randomize:
                z0 = jax.vmap(init_to_uniform)(chain_keys, z0)
            _, unravel = ravel_pytree(
                jax.tree_util.tree_map(lambda x: x[0], proto)
            )
            flat = jax.vmap(lambda t: ravel_pytree(t)[0])(z0)  # (C, D)

            def pe_flat(zvec):
                return pe_fn(unravel(zvec))

            state = kernel.fused_init_state(
                chain_keys[0], flat, jax.vmap(pe_flat)(flat)
            )
            state = shard_state(state)

            def step(s):
                return kernel.fused_sample_step(s, pe_flat, W, backend=backend, mesh=mesh)

            def warmup_body(s, i):
                s = step(s)
                if adapt_mm:
                    do = ((i + 1) % win == 0) & (i + 1 < W)
                    with kernel.scope("adapt"):
                        s = jax.lax.cond(
                            do,
                            lambda s: s._replace(
                                inv_mass=pooled_variance(s.wf_m2, s.wf_n),
                                wf_mean=jnp.zeros_like(s.wf_mean),
                                wf_m2=jnp.zeros_like(s.wf_m2),
                                wf_n=jnp.zeros_like(s.wf_n),
                            ),
                            lambda s: s,
                            s,
                        )
                return s, None

            if W > 0:
                with kernel.scope("warmup"):
                    state, _ = jax.lax.scan(warmup_body, state, jnp.arange(W))
            state = kernel.fused_finalize_warmup(state)

            def collect_body(s, _):
                if T > 1:
                    def thin_step(carry, _):
                        s, div = carry
                        s = step(s)
                        return (s, div | s.diverging), None

                    (s, diverging), _ = jax.lax.scan(
                        thin_step, (s, jnp.zeros((C,), bool)), None, length=T
                    )
                else:
                    s = step(s)
                    diverging = s.diverging
                extras = {
                    "accept_prob": s.accept_prob,
                    "diverging": diverging,
                    "num_steps": s.num_steps,
                    "potential_energy": s.potential,
                    "step_size": jnp.broadcast_to(s.step_size, (C,)),
                }
                return s, (s.z, extras)

            with kernel.scope("sample"):
                state, (zs, extras) = jax.lax.scan(collect_body, state, None, length=S)
            zs = jnp.swapaxes(zs, 0, 1)  # (S, C, D) -> (C, S, D)
            extras = jax.tree_util.tree_map(
                lambda x: jnp.swapaxes(x, 0, 1), extras
            )
            z = jax.vmap(jax.vmap(unravel))(zs)  # {site: (C, S, ...)}
            if mesh is not None:
                z = shard_chains(z, mesh)
                extras = shard_chains(extras, mesh)
            return state, z, extras

        return driver

    # -- public API ----------------------------------------------------------
    def run(self, rng_key, *args, init_params=None, **kwargs):
        """Run all chains; returns `get_samples()` (flattened across chains).

        `init_params`, when given, is an *unbatched* pytree of unconstrained
        initial values broadcast to every chain (chains still decorrelate
        through their momenta/keys). Required for `potential_fn` kernels.

        Runs inside the telemetry span ``mcmc.run`` (children
        ``mcmc.model_setup`` and ``mcmc.call``); on the fused path the span's
        counters are the run's `FusedCounters`, left on the device.
        """
        with telemetry.span("mcmc.run") as record:
            key_setup, key_init = jax.random.split(rng_key)
            kernel = self.kernel
            if kernel.model is not None:
                with telemetry.span("mcmc.model_setup"):
                    _, proto = kernel.setup(key_setup, *args, **kwargs)
                randomize = init_params is None
                if init_params is not None:
                    proto = init_params
            else:
                if init_params is None:
                    raise ValueError("potential_fn kernels require init_params=")
                proto, randomize = init_params, False

            C = self.num_chains
            proto = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(jnp.asarray(x, jnp.float32), (C,) + jnp.shape(x)),
                proto,
            )
            chain_keys = jax.random.split(key_init, C)

            # static/dynamic partition of model args: arrays are traced (a fresh
            # dataset of the same shape reuses the executable), everything else
            # (plate sizes, flags) stays static so model control flow is unchanged
            leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
            is_dyn = tuple(isinstance(l, (jax.Array, np.ndarray)) for l in leaves)
            dyn_leaves = [l for l, d in zip(leaves, is_dyn) if d]
            static_leaves = tuple(None if d else l for l, d in zip(leaves, is_dyn))
            # the kernel backend is a trace-time constant of the fused driver, so
            # it joins the cache key (flipping REPRO_KERNEL_BACKEND between runs
            # recompiles instead of silently reusing the old backend)
            backend = ops.resolve_backend(None) if self.fused else None
            exec_key = (randomize, treedef, is_dyn, static_leaves, self.fused, backend)
            if self._exec is None or self._exec_key != exec_key:
                if self.fused:
                    driver = self._build_fused_driver(
                        randomize, treedef, is_dyn, static_leaves, backend
                    )
                else:
                    driver = self._build_driver(randomize, treedef, is_dyn, static_leaves)
                self._exec = jax.jit(driver)
                self._exec_key = exec_key
            with telemetry.span("mcmc.call"):
                states, z, extras = self._exec(chain_keys, proto, dyn_leaves)
            if self.fused:
                record["counters"].update(states.counters._asdict())
            self._last_state = states
            self._extra_fields = extras
            if kernel._transforms:
                z = transform_fn(kernel._transforms, z)
            self._samples = z
            return self.get_samples()

    def get_samples(self, group_by_chain: bool = False):
        """Posterior samples in constrained space: ``(chain, draw, ...)`` when
        `group_by_chain`, else flattened to ``(chain * draw, ...)``."""
        if self._samples is None:
            return None
        if group_by_chain:
            return self._samples
        return jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), self._samples
        )

    def get_extra_fields(self, group_by_chain: bool = True):
        """Per-draw diagnostics: accept_prob, diverging, num_steps,
        potential_energy, step_size — each ``(chain, draw)`` when
        `group_by_chain` (default), else flattened."""
        if self._extra_fields is None:
            return None
        if group_by_chain:
            return self._extra_fields
        return jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), self._extra_fields
        )

    def summary(self, prob: float = 0.9, print_table: bool = True):
        """Per-site posterior statistics + convergence diagnostics (split-R̂,
        bulk/tail ESS, divergence count). Prints the table unless
        `print_table=False`; returns the stats as ``{site: {stat: array}}``."""
        from .diagnostics import print_summary, summary as _summary

        if self._samples is None:
            raise RuntimeError("no samples available; call MCMC.run(...) first")
        if print_table:
            print_summary(self._samples, extra_fields=self._extra_fields, prob=prob)
        return _summary(self._samples, prob=prob)
