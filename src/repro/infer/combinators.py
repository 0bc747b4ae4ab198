"""Inference combinators: models and proposals as first-class values.

The combinator calculus of Stites & Zimmermann ("Learning proposals for
probabilistic programs with inference combinators", 2103.00668, PAPERS.md)
treats inference programs as *values* that compose, each carrying a properly
weighted sample. Here a program has one-particle semantics

    program.run(key, params, *args, **kwargs) -> Run(trace, output, log_weight)

where ``log_weight`` is the incremental importance weight of the particle the
run produced, and population semantics (``run_population``) obtained by
vmapping ``run`` over the shared particle-sharding path the ELBOs use
(`elbo.shard_particles` — particles ride a ``mesh=`` exactly like
multi-particle ELBO estimates).

Combinators::

    primitive(f)      lift a repro model: sample latents from the prior,
                      score observations (log_weight = observed log-prob).
    compose(f2, f1)   proposal composition: run f1, feed its output to f2,
                      union the traces, add the weights.
    extend(p, f)      target extension: auxiliary sites appended to target
                      p by kernel f (same mechanics as compose, opposite
                      role — f enlarges the *numerator* of a later propose).
    propose(p, q)     importance step: draw from proposal q, rescore under
                      target p (replaying q's choices), weight
                      p(replayed sites + observations) / q(latent sites).
    resample(prog)    population-level: when the incoming population's ESS
                      drops below threshold*N, draw ancestors with
                      `kernels.ops.resample` (systematic) or categorical
                      (multinomial), reset the weights, bank the marginal-
                      likelihood increment — then run ``prog``.

`infer.smc.SMC` is a scan of ``resample(propose(...))`` steps; one-step
``propose`` with no time loop is importance sampling (`ImportanceSampling`
below, which the legacy `infer.importance.Importance` now aliases); SMC² is
an outer sweep whose step programs `P.factor` an inner sweep's log-evidence
increment (see `smc.smc_sweep`).
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from .. import settings
from ..core.handlers import replay, seed, trace
from ..kernels import ops
from .elbo import shard_particles
from .util import log_mean_exp, substitute_params

RESAMPLE_METHODS = ("systematic", "multinomial")


class Run(NamedTuple):
    """One particle's worth of a program: its trace, its return value (the
    carry fed to downstream programs), and its incremental log-weight."""

    trace: Any
    output: Any
    log_weight: jax.Array


class Population(NamedTuple):
    """A particle population: vmapped carries plus persistent log-weights."""

    carry: Any
    log_weights: jax.Array


class StepAux(NamedTuple):
    """Per-step diagnostics a population step emits (the SMC history row).
    `log_weights` are the post-reweight population weights — what filtering
    expectations at that step weight by; `ess` is their Kish ESS."""

    latents: Dict[str, jax.Array]
    incr_log_weight: jax.Array
    log_weights: jax.Array
    ess: jax.Array
    resampled: jax.Array
    log_z_incr: jax.Array


def effective_sample_size(log_weights: jax.Array) -> jax.Array:
    """Kish ESS of unnormalized log-weights: (Σw)²/Σw² after normalization.
    Equal weights give exactly N (the no-resample fixed point of the
    ``ess < threshold * N`` trigger at threshold=1)."""
    norm = jax.scipy.special.logsumexp(log_weights, axis=-1, keepdims=True)
    finite = jnp.isfinite(norm)
    w = jnp.where(
        finite,
        jnp.exp(log_weights - jnp.where(finite, norm, 0.0)),
        1.0 / log_weights.shape[-1],
    )
    return 1.0 / jnp.sum(jnp.square(w), axis=-1)


class Program:
    """Base combinator. Subclasses implement `run` (one particle); the
    population path below is shared."""

    def run(self, rng_key, params, *args, replay_from=None, **kwargs) -> Run:
        raise NotImplementedError

    def run_population(
        self,
        rng_key,
        params,
        population: Population,
        args: Tuple = (),
        kwargs: Optional[dict] = None,
        *,
        mesh=None,
        particle_axis=None,
    ) -> Tuple[Population, StepAux]:
        """Propagate + reweight every particle: vmap `run` over per-particle
        keys (sharded across `mesh` exactly like ELBO particles), feeding
        each particle its own carry, and add the incremental weights."""
        kwargs = kwargs or {}
        n = population.log_weights.shape[0]
        keys = shard_particles(jax.random.split(rng_key, n), mesh, particle_axis)

        def one(k, carry):
            r = self.run(k, params, carry, *args, **kwargs)
            latents = {nm: r.trace[nm]["value"] for nm in r.trace.stochastic_nodes()}
            return r.output, jnp.asarray(r.log_weight, jnp.float32), latents

        out, incr, latents = jax.vmap(one)(keys, population.carry)
        lw = population.log_weights + incr
        return Population(out, lw), StepAux(
            latents=latents,
            incr_log_weight=incr,
            log_weights=lw,
            ess=effective_sample_size(lw),
            resampled=jnp.asarray(False),
            log_z_incr=jnp.float32(0.0),
        )

    def init_population(
        self,
        rng_key,
        params,
        num_particles: int,
        args: Tuple = (),
        kwargs: Optional[dict] = None,
        *,
        mesh=None,
        particle_axis=None,
    ) -> Tuple[Population, StepAux]:
        """Spawn a fresh population (no incoming carry): the t=0 step."""
        kwargs = kwargs or {}
        keys = shard_particles(
            jax.random.split(rng_key, num_particles), mesh, particle_axis
        )

        def one(k):
            r = self.run(k, params, *args, **kwargs)
            latents = {nm: r.trace[nm]["value"] for nm in r.trace.stochastic_nodes()}
            return r.output, jnp.asarray(r.log_weight, jnp.float32), latents

        out, lw, latents = jax.vmap(one)(keys)
        return Population(out, lw), StepAux(
            latents=latents,
            incr_log_weight=lw,
            log_weights=lw,
            ess=effective_sample_size(lw),
            resampled=jnp.asarray(False),
            log_z_incr=jnp.float32(0.0),
        )


class Primitive(Program):
    """A repro model as a combinator value. Sampling semantics: latents from
    the prior (or replayed from ``replay_from``), observations scored —
    log_weight is the observed-site log-prob (likelihood weighting)."""

    def __init__(self, fn: Callable):
        self.fn = fn

    def run(self, rng_key, params, *args, replay_from=None, **kwargs) -> Run:
        seeded = seed(substitute_params(self.fn, params), rng_key)
        if replay_from is not None:
            seeded = replay(seeded, replay_from)
        handler = trace(seeded)
        # call through the handler (not get_trace) so the model's return
        # value — the carry downstream programs consume — is kept
        out = handler(*args, **kwargs)
        t = handler.trace
        lw = t.log_prob_sum(lambda n, s: s["is_observed"])
        return Run(t, out, lw)


class Compose(Program):
    """``compose(f2, f1)``: run f1, pipe its output into f2 (as f2's first
    positional argument), union the traces, add the weights. Site names must
    be disjoint — a clash raises at trace time."""

    role = "composed"

    def __init__(self, f2: Program, f1: Program):
        self.f2 = f2
        self.f1 = f1

    def run(self, rng_key, params, *args, replay_from=None, **kwargs) -> Run:
        k1, k2 = jax.random.split(rng_key)
        r1 = self.f1.run(k1, params, *args, replay_from=replay_from, **kwargs)
        r2 = self.f2.run(k2, params, r1.output, replay_from=replay_from)
        merged = r1.trace.copy()
        for name, site in r2.trace.nodes.items():
            merged.add_node(name, site)  # raises on duplicates
        return Run(merged, r2.output, r1.log_weight + r2.log_weight)


class Extend(Compose):
    """``extend(p, f)``: target extension. Mechanically `compose(f, p)` —
    the kernel f runs *after* the target p, on p's output — but the role
    differs: f's sites belong to the extended target, so a later `propose`
    scores them in the numerator (auxiliary-variable targets, Stites &
    Zimmermann §3.2)."""

    role = "extended"

    def __init__(self, p: Program, f: Program):
        super().__init__(f, p)


class Propose(Program):
    """``propose(p, q)``: properly weighted importance step. The proposal q
    runs free; the target p replays q's choices and scores them plus its
    observations. Weight: q's own weight, plus target density over replayed
    + observed sites, minus proposal density over its latent sites. Target
    sites the proposal does not cover are prior-sampled and cancel out of
    the weight (their density appears in neither term)."""

    def __init__(self, p: Program, q: Program):
        self.p = p
        self.q = q

    def run(self, rng_key, params, *args, replay_from=None, **kwargs) -> Run:
        key_q, key_p = jax.random.split(rng_key)
        rq = self.q.run(key_q, params, *args, replay_from=replay_from, **kwargs)
        rp = self.p.run(key_p, params, *args, replay_from=rq.trace, **kwargs)
        lp = rp.trace.log_prob_sum(
            lambda n, s: s["is_observed"] or n in rq.trace
        )
        lq = rq.trace.log_prob_sum(lambda n, s: not s["is_observed"])
        return Run(rp.trace, rp.output, rq.log_weight + lp - lq)


class Resample(Program):
    """``resample(prog)``: population-level combinator. Before ``prog``
    propagates, check the incoming population's ESS; below
    ``ess_threshold * N``, draw ancestors (`ops.resample` systematic kernel
    by default, `REPRO_SMC_RESAMPLE` / ``method=`` to override), gather the
    carries, bank ``logsumexp(W) - log N`` into the marginal-likelihood
    accumulator and reset the weights. The decision is data-dependent but
    shape-stable: both branches are computed and selected with `jnp.where`,
    so the step stays scan- and shard-compatible."""

    def __init__(
        self,
        inner: Program,
        ess_threshold: float = 0.5,
        method: Optional[str] = None,
    ):
        if not 0.0 <= ess_threshold <= 1.0:
            raise ValueError(
                f"ess_threshold must be in [0, 1], got {ess_threshold}"
            )
        if method is not None and method not in RESAMPLE_METHODS:
            raise ValueError(
                f"unknown resample method {method!r}; expected one of "
                f"{RESAMPLE_METHODS}"
            )
        self.inner = inner
        self.ess_threshold = ess_threshold
        self.method = method

    def _resolved_method(self) -> str:
        method = self.method or settings.get_str("REPRO_SMC_RESAMPLE")
        if method not in RESAMPLE_METHODS:
            raise ValueError(
                f"REPRO_SMC_RESAMPLE={method!r}; expected one of "
                f"{RESAMPLE_METHODS}"
            )
        return method

    def run(self, rng_key, params, *args, replay_from=None, **kwargs) -> Run:
        raise TypeError(
            "resample is population-level: it permutes particles across the "
            "population and has no single-particle semantics. Run it through "
            "run_population (or the SMC engine)."
        )

    def run_population(
        self,
        rng_key,
        params,
        population: Population,
        args: Tuple = (),
        kwargs: Optional[dict] = None,
        *,
        mesh=None,
        particle_axis=None,
    ) -> Tuple[Population, StepAux]:
        key_r, key_s = jax.random.split(rng_key)
        lw = population.log_weights
        n = lw.shape[0]
        ess_in = effective_sample_size(lw)
        do = ess_in < self.ess_threshold * n
        if self._resolved_method() == "systematic":
            u0 = jax.random.uniform(key_r)
            ancestors = ops.resample(lw, u0, mesh=mesh, axis=particle_axis)
        else:
            ancestors = jnp.sort(jax.random.categorical(key_r, lw, shape=(n,)))
        idx = jnp.where(do, ancestors, jnp.arange(n))
        carry = jax.tree.map(lambda x: x[idx], population.carry)
        log_z_incr = jnp.where(
            do, jax.scipy.special.logsumexp(lw) - jnp.log(jnp.float32(n)), 0.0
        )
        new_lw = jnp.where(do, jnp.zeros_like(lw), lw)
        pop, aux = self.inner.run_population(
            key_s,
            params,
            Population(carry, new_lw),
            args,
            kwargs,
            mesh=mesh,
            particle_axis=particle_axis,
        )
        return pop, aux._replace(resampled=do, log_z_incr=log_z_incr)


def primitive(fn: Union[Callable, Program]) -> Program:
    """Lift a repro model into a combinator program (idempotent)."""
    return fn if isinstance(fn, Program) else Primitive(fn)


def compose(f2: Union[Callable, Program], f1: Union[Callable, Program]) -> Program:
    return Compose(primitive(f2), primitive(f1))


def extend(p: Union[Callable, Program], f: Union[Callable, Program]) -> Program:
    return Extend(primitive(p), primitive(f))


def propose(p: Union[Callable, Program], q: Union[Callable, Program]) -> Program:
    return Propose(primitive(p), primitive(q))


def resample(
    prog: Union[Callable, Program],
    ess_threshold: float = 0.5,
    method: Optional[str] = None,
) -> Program:
    return Resample(primitive(prog), ess_threshold=ess_threshold, method=method)


# ---------------------------------------------------------------------------
# importance sampling: the degenerate one-step propose
# ---------------------------------------------------------------------------


class ImportanceSampling:
    """Self-normalized importance sampling as a combinator composition: one
    `propose(primitive(model), primitive(guide))` step over a particle
    population (guide-less, the bare likelihood-weighting `primitive`).

    This is the canonical engine behind the legacy `infer.Importance` (now a
    FutureWarning alias): same key structure, same weights, bit-for-bit.
    Implements the `InferenceEngine` protocol — `run`, `get_samples`,
    `num_traces` (which counts vmap traces: one per `run`, since the sweep
    is a single eager vmap, not a cached jit)."""

    def __init__(
        self,
        model: Callable,
        guide: Optional[Callable] = None,
        num_particles: int = 100,
        *,
        mesh=None,
        particle_axis=None,
    ):
        if num_particles < 1:
            raise ValueError(f"num_particles must be >= 1, got {num_particles}")
        self.model = model
        self.guide = guide
        self.num_particles = num_particles
        self.mesh = mesh
        self.particle_axis = particle_axis
        self.program = (
            propose(primitive(model), primitive(guide))
            if guide is not None
            else primitive(model)
        )
        self.num_traces = 0
        self.log_weights = None
        self.latents = None

    def run(self, rng_key, *args, params=None, **kwargs):
        params = params or {}
        keys = shard_particles(
            jax.random.split(rng_key, self.num_particles),
            self.mesh,
            self.particle_axis,
        )

        def one(k):
            self.num_traces += 1  # trace-time side effect (vmap traces once)
            r = self.program.run(k, params, *args, **kwargs)
            latents = {n: r.trace[n]["value"] for n in r.trace.stochastic_nodes()}
            return r.log_weight, latents

        self.log_weights, self.latents = jax.vmap(one)(keys)
        return self

    def get_samples(self, group_by_chain: bool = False):
        """Latent draws with the particle axis leading — pair with
        `log_weights` (these are weighted draws, not posterior samples).
        ``group_by_chain=True`` adds a singleton chain axis, matching the
        MCMC convention of (chains, draws, ...)."""
        if self.latents is None:
            raise RuntimeError("no samples yet — call .run(rng_key, ...) first")
        if group_by_chain:
            return jax.tree.map(lambda x: x[None], self.latents)
        return self.latents

    def log_evidence(self):
        return log_mean_exp(self.log_weights)

    def effective_sample_size(self):
        return effective_sample_size(self.log_weights)

    def resample(self, rng_key, num: int):
        """Draw `num` equally weighted latents (multinomial, with
        replacement) from the weighted population."""
        idx = jax.random.categorical(rng_key, self.log_weights, shape=(num,))
        return jax.tree.map(lambda x: x[idx], self.latents)
