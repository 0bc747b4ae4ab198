"""TraceEnum_ELBO + infer_discrete: exact parallel enumeration of discrete
latents (paper §2's flagship example of composable custom inference).

Enumeration reduces to broadcast-then-contract over named dims (funsor,
Obermeyer et al. 2019): the `enum` messenger gives every annotated discrete
site its full support along a fresh negative batch dim left of all plate
dims, and the contraction below sum-eliminates those dims out of the joint
log-density with logsumexp (sum-product) or max (max-product for MAP
decoding), *respecting plate structure*: a plate is a product over
independent slices, so enum dims local to a plate are eliminated before the
plate's log-factors are summed over the plate axis, while enum dims shared
with enclosing ordinals survive the plate sum (the classic "global mixture
component observed across a data plate" pattern).

Everything here is trace-time Python: under `jax.jit` the handler stack and
the contraction schedule run while XLA traces, so a compiled SVI step with
enumeration contains only the einsum-style broadcast/reduce ops —
`TraceEnum_ELBO` plugs into the shared `ELBO` engine from PR 1 and inherits
particle vectorization, `mesh=` sharding, and the compile-once `update_jit`
path unchanged (`num_traces` counts retraces the same way `mcmc.num_traces`
does).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, FrozenSet, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core.handlers import block, enum, replay, seed, substitute, trace
from ..core.primitives import prng_key
from ..distributions.continuous import MultivariateNormal, Normal
from .contract.gaussian import (
    GaussianFactor,
    affine_gaussian_factor,
    color_sites,
    eliminate_gaussian_factors,
    jaxpr_dependencies,
)
from .contract import (
    _dispatch_mode,
    _from_matrix,
    _logsumexp_op,
    _max_op,
    _to_matrix,
    _ve_eliminate,
    clear_plan_cache,
    contract_log_factors,
    plan_cache_stats,
)
from .contract.structure import (
    _add_all,
    _enum_dims,
    _reduce_dims,
    _scaled,
    _uniform_scale,
)
from .elbo import ELBO, _apply_scale_mask
from .util import substitute_params

# The contraction engine (planner, plan cache, executor) lives in
# `repro.infer.contract`; the helpers above are re-exported here because this
# module is the historical home of the contraction API.
__all__ = [
    "TraceEnum_ELBO",
    "contract_log_factors",
    "discrete_marginals",
    "gaussian_marginals",
    "infer_discrete",
    "plan_cache_stats",
    "clear_plan_cache",
    "_add_all",
    "_dispatch_mode",
    "_enum_dims",
    "_from_matrix",
    "_logsumexp_op",
    "_max_op",
    "_reduce_dims",
    "_scaled",
    "_to_matrix",
    "_uniform_scale",
    "_ve_eliminate",
]

# ---------------------------------------------------------------------------
# log-factor collection
# ---------------------------------------------------------------------------


def _max_plate_nesting(*traces) -> int:
    """Deepest plate dim used by any site across the given traces."""
    mpn = 0
    for tr in traces:
        for site in tr.nodes.values():
            for frame in site.get("cond_indep_stack", ()):
                mpn = max(mpn, -frame.dim)
    return mpn


def _collect_factors(model_tr, skip: FrozenSet[str] = frozenset()):
    """Extract (ordinal, log_prob, pending_scale) triples from a model trace,
    plus the frame->nesting-depth map used to order plate elimination and the
    pool of dims the enum messenger allocated. The ordinal of a factor is the
    frozenset of plate frames enclosing its site. Sites named in ``skip`` are
    excluded entirely — used for Gaussian-entangled sites, whose densities
    enter through the eliminated Gaussian factors' log-normalizers instead.

    Scale handling: a site scale (plate subsampling's size/subsample_size, or
    handlers.scale) is an exponent on probabilities — for factors entangled
    with enum dims it must multiply the *marginalized* per-slice log-density,
    i.e. apply AFTER logsumexp, not before (s*logsumexp(lp), never
    logsumexp(s*lp)). Factors free of enum dims get their scale applied here;
    the rest carry it as `pending` until the contraction finishes their local
    eliminations. Masking: a masked-out slice of an enumerated site fills with
    -log(K) (so its logsumexp contributes exactly 0), while every other
    factor fills with 0 as usual."""
    factors: List[Tuple[FrozenSet, jax.Array, Any]] = []
    depth: Dict = {}
    enum_dim_pool = set()
    for name, site in model_tr.nodes.items():
        if site["type"] != "sample" or name in skip:
            continue
        enum_dim = site["infer"].get("_enumerate_dim")
        if enum_dim is not None:
            enum_dim_pool.add(enum_dim)
        lp = site["fn"].log_prob(site["value"])
        mask = site["mask"]
        if enum_dim is not None:
            # distribution-level masks (.mask()) zero-fill inside log_prob,
            # which is wrong across an enum dim — fold them into the site
            # mask so the -log K neutral fill below covers both paths
            fn, dist_mask = site["fn"], None
            while fn is not None:
                m = getattr(fn, "_mask", None)
                if m is not None:
                    dist_mask = m if dist_mask is None else dist_mask & m
                fn = getattr(fn, "base_dist", None)
            if dist_mask is not None:
                mask = dist_mask if mask is None else mask & dist_mask
        if mask is not None:
            neutral = (
                -jnp.log(site["infer"]["_enumerate_cardinality"])
                if enum_dim is not None
                else 0.0
            )
            lp = jnp.where(mask, lp, neutral)
        frames = site["cond_indep_stack"]
        # cond_indep_stack is ordered outermost -> innermost
        for i, f in enumerate(frames):
            depth[f] = max(depth.get(f, 0), i)
        factors.append((frozenset(frames), lp, site["scale"]))
    pool = frozenset(enum_dim_pool)
    # scales on enum-free factors commute with everything downstream
    factors = [
        (o, lp, s) if _enum_dims(lp, pool) else (o, _scaled(lp, s), None)
        for o, lp, s in factors
    ]
    return factors, depth, pool


# ---------------------------------------------------------------------------
# Gaussian-site lowering (exact marginalization of linear-Gaussian latents)
# ---------------------------------------------------------------------------


def _gaussian_sites(model_tr) -> List[str]:
    """Non-observed sites annotated ``infer={"marginalize": "gaussian"}``,
    in trace order (which becomes the elimination order)."""
    return [
        name
        for name, site in model_tr.nodes.items()
        if site["type"] == "sample"
        and not site["is_observed"]
        and site["infer"].get("marginalize") == "gaussian"
    ]


def _check_gaussian_site(name, site, *, marginalized: bool):
    role = "marginalized" if marginalized else "Gaussian-entangled"
    if not isinstance(site["fn"], (Normal, MultivariateNormal)):
        raise NotImplementedError(
            f"{role} site '{name}' has distribution "
            f"{type(site['fn']).__name__}; Gaussian marginalization supports "
            "Normal and MultivariateNormal sites only"
        )
    if site["cond_indep_stack"]:
        raise NotImplementedError(
            f"{role} site '{name}' is inside a plate; plate-local Gaussian "
            "marginalization is not implemented — write time/feature "
            "structure as separate sites (or an MVN event dim) instead"
        )
    if site["scale"] is not None or site["mask"] is not None:
        raise NotImplementedError(
            f"{role} site '{name}' carries a scale or mask; neither commutes "
            "with exact Gaussian elimination"
        )


def _check_gaussian_lead(name, lead, pool):
    for i, s in enumerate(lead):
        d = i - len(lead)
        if s > 1 and d not in pool:
            raise NotImplementedError(
                f"Gaussian-entangled site '{name}' has a non-enumeration "
                f"batch axis of size {s} at dim {d}; only enum dims may "
                "batch Gaussian factors (vectorized/plated Gaussian sites "
                "are unsupported — use separate sites or an MVN event dim)"
            )


class _GaussianLowering(NamedTuple):
    factors: List[GaussianFactor]       # one per entangled site
    order: List[str]                    # marginalized sites, trace order
    entangled: FrozenSet[str]           # sites the factors' densities own
    widths: Dict[str, int]
    event_shapes: Dict[str, Tuple[int, ...]]


def _lower_gaussian_trace(make_trace, model_tr, pool, *, fixed: FrozenSet[str]):
    """Lower every ``marginalize="gaussian"`` site (and each site whose
    location depends on one) to an information-form `GaussianFactor`.

    Dependence structure is discovered with `jax.linearize` of a model
    retrace that substitutes candidate values, plus a conservative jaxpr
    dataflow walk (`contract.gaussian.jaxpr_dependencies`) — both work under
    `jax.jit`. The affine coefficients A in loc_s = Σ_p A_sp x_p + b_s come
    from JVP basis pushes, batched with a greedy conflict coloring
    (`color_sites`) so a T-step chain costs 2 vectorized pushes, not T.
    Anything non-linear-Gaussian in the entangled set raises
    `NotImplementedError`: a dependent non-Gaussian site, a covariance
    depending on a marginalized value, or (checked numerically when tracing
    eagerly; skipped under jit) a non-affine location.

    ``fixed`` names latents whose values are legitimately pinned (guide
    draws); an entangled free latent that is neither fixed, observed, nor
    itself marginalized is an error rather than a silent conditioning."""
    marg = _gaussian_sites(model_tr)
    if not marg:
        return None
    marg_set = set(marg)
    for name in marg:
        _check_gaussian_site(name, model_tr.nodes[name], marginalized=True)

    widths: Dict[str, int] = {}
    event_shapes: Dict[str, Tuple[int, ...]] = {}

    def register(name):
        fn = model_tr.nodes[name]["fn"]
        ev = tuple(fn.event_shape) if isinstance(fn, MultivariateNormal) else ()
        event_shapes[name] = ev
        widths[name] = int(ev[0]) if ev else 1

    for name in marg:
        register(name)
    protos = {n: jnp.zeros(event_shapes[n], jnp.float32) for n in marg}

    sample_names = [n for n, s in model_tr.nodes.items() if s["type"] == "sample"]

    def retrace(values):
        tr = make_trace(values)
        outs = {}
        for n in sample_names:
            site = tr.nodes[n]
            fn = site["fn"]
            if isinstance(fn, Normal):
                outs[("loc", n)] = jnp.asarray(fn.loc, jnp.float32)
                outs[("scale", n)] = jnp.asarray(fn.scale, jnp.float32)
            elif isinstance(fn, MultivariateNormal):
                outs[("loc", n)] = jnp.asarray(fn.loc, jnp.float32)
                outs[("scale", n)] = jnp.asarray(fn.scale_tril, jnp.float32)
            else:
                outs[("lp", n)] = fn.log_prob(site["value"])
        return outs

    primal, jvp = jax.linearize(retrace, protos)
    in_names = sorted(protos)           # dict flatten order == sorted keys
    out_keys = sorted(primal)
    dep_idx = jaxpr_dependencies(retrace, protos)
    deps = {
        k: frozenset(in_names[i] for i in dep_idx[j]) & marg_set
        for j, k in enumerate(out_keys)
    }

    for (kind, n), ds in sorted(deps.items()):
        if not ds or kind == "loc":
            continue
        if kind == "scale":
            raise NotImplementedError(
                f"the scale/covariance of site '{n}' depends on marginalized "
                f"sites {sorted(ds)}; only locations may depend on "
                "Gaussian-marginalized latents (linear-Gaussian structure)"
            )
        raise NotImplementedError(
            f"site '{n}' depends on marginalized sites {sorted(ds)} but is "
            "not Normal/MultivariateNormal; every site downstream of a "
            "marginalized latent must be linear-Gaussian"
        )

    entangled = [
        n for n in sample_names
        if n in marg_set or deps.get(("loc", n), frozenset())
    ]
    for n in entangled:
        site = model_tr.nodes[n]
        if n not in marg_set:
            _check_gaussian_site(n, site, marginalized=False)
            if not site["is_observed"] and n not in fixed:
                raise NotImplementedError(
                    f"site '{n}' depends on marginalized sites but is a free "
                    "latent; annotate it for marginalization too, or sample "
                    "it in the guide"
                )
            register(n)

    # numeric affine-ness check: only possible on concrete values (eager
    # tracing); under jit the primal is a tracer and the check is skipped —
    # the structural guards above still hold, linearity is trusted.
    if not any(isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(primal)):
        delta = {n: jnp.full(event_shapes[n], 0.7357, jnp.float32) for n in marg}
        lhs = retrace(delta)
        tang = jvp(delta)
        for n in entangled:
            want = primal[("loc", n)] + tang[("loc", n)]
            if not np.allclose(lhs[("loc", n)], want, rtol=1e-3, atol=1e-4):
                raise NotImplementedError(
                    f"the location of site '{n}' is not affine in the "
                    "marginalized sites; exact Gaussian elimination requires "
                    "linear-Gaussian dependence"
                )

    # Jacobian blocks via color-batched JVP basis pushes
    dependents_map = {
        ("loc", n): deps.get(("loc", n), frozenset()) for n in entangled
    }
    jac: Dict[Tuple[str, str], jax.Array] = {}
    for group in color_sites(marg, dependents_map):
        group = [
            p for p in group
            if any(p in deps.get(("loc", n), ()) for n in entangled)
        ]
        if not group:
            continue
        wmax = max(widths[p] for p in group)

        def basis(p, i):
            z = jnp.zeros(event_shapes[p], jnp.float32)
            if p not in group or i >= widths[p]:
                return z
            return z.at[i].set(1.0) if event_shapes[p] else z + 1.0

        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[{p: basis(p, i) for p in protos} for i in range(wmax)],
        )
        pushed = jax.vmap(jvp)(stacked)
        for n in entangled:
            for p in group:
                if p not in deps.get(("loc", n), ()):
                    continue
                col = jnp.moveaxis(pushed[("loc", n)][: widths[p]], 0, -1)
                if not event_shapes[n]:
                    col = col[..., None, :]         # scalar site: (*lead, 1, w_p)
                jac[(n, p)] = col

    # one information-form factor per entangled site
    factors: List[GaussianFactor] = []
    for n in entangled:
        site = model_tr.nodes[n]
        is_mvn = bool(event_shapes[n])
        loc = jnp.asarray(primal[("loc", n)], jnp.float32)
        scale = jnp.asarray(primal[("scale", n)], jnp.float32)
        if is_mvn:
            lead = jnp.broadcast_shapes(loc.shape[:-1], scale.shape[:-2])
            locb = jnp.broadcast_to(loc, lead + loc.shape[-1:])
            L = jnp.broadcast_to(scale, lead + scale.shape[-2:])
        else:
            lead = jnp.broadcast_shapes(loc.shape, scale.shape)
            locb = jnp.broadcast_to(loc, lead)[..., None]
            L = jnp.broadcast_to(scale, lead)[..., None, None]
        _check_gaussian_lead(n, lead, pool)
        parents = sorted(deps.get(("loc", n), frozenset()), key=marg.index)
        if n in marg_set:
            vars_ = (n,) + tuple(p for p in parents if p != n)
            m0, own = -locb, n
        else:
            vars_ = tuple(parents)
            value = jnp.asarray(site["value"], jnp.float32)
            m0 = (value if is_mvn else value[..., None]) - locb
            own = None
        factors.append(
            affine_gaussian_factor(
                vars_,
                tuple(widths[v] for v in vars_),
                {p: jac[(n, p)] for p in vars_ if p != n},
                m0,
                L,
                own,
            )
        )
    return _GaussianLowering(factors, marg, frozenset(entangled), widths, event_shapes)


# ---------------------------------------------------------------------------
# TraceEnum_ELBO
# ---------------------------------------------------------------------------


class TraceEnum_ELBO(ELBO):
    """ELBO with exact parallel marginalization of enumerated discrete model
    sites. Annotate sites with ``infer={"enumerate": "parallel"}`` (or wrap
    the model in ``config(enumerate=True)``); the guide must not sample them.

    Plugs into the shared `ELBO` engine: `num_particles`, `mesh=` particle
    sharding, and SVI's compile-once `update_jit` all work unchanged.
    `max_plate_nesting` is detected from a prototype trace when not given;
    pass it explicitly when the model's shapes depend on rarely-exercised
    branches. `num_traces` counts XLA retraces (jit-stability assertion hook,
    same idiom as `mcmc.num_traces`).
    """

    def __init__(
        self,
        num_particles: int = 1,
        max_plate_nesting: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        particle_axis: Union[str, Tuple[str, ...], None] = None,
    ):
        super().__init__(num_particles, mesh=mesh, particle_axis=particle_axis)
        self.max_plate_nesting = max_plate_nesting
        self.num_traces = 0

    def _single_particle(self, rng_key, params, model, guide, args, kwargs):
        self.num_traces += 1  # trace-time side effect (retrace detector)
        key_guide, key_model = jax.random.split(rng_key)
        seeded_guide = seed(substitute_params(guide, params), key_guide)
        guide_tr = trace(seeded_guide).get_trace(*args, **kwargs)
        for name, site in guide_tr.nodes.items():
            if (
                site["type"] == "sample"
                and not site["is_observed"]
                and site["infer"].get("enumerate")
            ):
                raise NotImplementedError(
                    f"guide site '{name}' requests enumeration; guide-side "
                    "enumeration is not implemented — annotate the model site "
                    "and remove it from the guide so TraceEnum_ELBO can "
                    "marginalize it exactly"
                )
        seeded_model = seed(substitute_params(model, params), key_model)
        if self.max_plate_nesting is None:
            # one extra prototype trace (trace-time only), then cached
            proto_tr = trace(replay(seeded_model, guide_tr)).get_trace(*args, **kwargs)
            self.max_plate_nesting = _max_plate_nesting(guide_tr, proto_tr)
        mpn = self.max_plate_nesting
        with enum(first_available_dim=-1 - mpn):
            model_tr = trace(replay(seeded_model, guide_tr)).get_trace(*args, **kwargs)

        guide_latents = frozenset(
            name
            for name, site in guide_tr.nodes.items()
            if site["type"] == "sample" and not site["is_observed"]
        )
        for name in _gaussian_sites(model_tr):
            if name in guide_latents:
                raise NotImplementedError(
                    f"guide samples site '{name}' which the model marks for "
                    "Gaussian marginalization; remove it from the guide so "
                    "TraceEnum_ELBO can integrate it out exactly"
                )
        pool = frozenset(
            s["infer"]["_enumerate_dim"]
            for s in model_tr.nodes.values()
            if s["type"] == "sample" and s["infer"].get("_enumerate_dim") is not None
        )

        def make_trace(values):
            with enum(first_available_dim=-1 - mpn):
                return trace(
                    substitute(replay(seeded_model, guide_tr), data=values)
                ).get_trace(*args, **kwargs)

        gauss = _lower_gaussian_trace(make_trace, model_tr, pool, fixed=guide_latents)
        skip = gauss.entangled if gauss else frozenset()
        factors, depth, pool = _collect_factors(model_tr, skip=skip)
        if gauss:
            # the eliminated factors' log-normalizers are ordinary enum-lead
            # log-factors at the root ordinal (plates on entangled sites are
            # rejected in the lowering), completing the mixed contraction
            for t in eliminate_gaussian_factors(gauss.factors, gauss.order):
                factors.append((frozenset(), t, None))
        elbo = jnp.sum(contract_log_factors(factors, depth, pool))
        score_logq = 0.0  # REINFORCE factor for non-reparam guide sites
        for site in guide_tr.nodes.values():
            if site["type"] != "sample" or site["is_observed"]:
                continue
            lq = _apply_scale_mask(site["fn"].log_prob(site["value"]), site)
            elbo = elbo - jnp.sum(lq)
            if not site["fn"].has_rsample:
                score_logq = score_logq + jnp.sum(lq)
        surrogate = elbo + jax.lax.stop_gradient(elbo) * (
            score_logq - jax.lax.stop_gradient(score_logq)
        )
        return elbo, surrogate


# ---------------------------------------------------------------------------
# infer_discrete: posterior decoding of enumerated sites
# ---------------------------------------------------------------------------


def _index_factor(t: jax.Array, dim: int, idx: jax.Array) -> jax.Array:
    """Condition a right-aligned log-factor on idx along enum dim `dim`
    (idx is right-aligned with a size-1 slot at `dim`)."""
    axis = jnp.ndim(t) + dim
    if axis < 0 or jnp.shape(t)[axis] == 1:
        return t  # factor does not carry this dim
    if jnp.ndim(idx) > jnp.ndim(t):
        t = jnp.reshape(t, (1,) * (jnp.ndim(idx) - jnp.ndim(t)) + jnp.shape(t))
        axis = jnp.ndim(t) + dim
    elif jnp.ndim(idx) < jnp.ndim(t):
        idx = jnp.reshape(idx, (1,) * (jnp.ndim(t) - jnp.ndim(idx)) + jnp.shape(idx))
    return jnp.take_along_axis(t, idx.astype(jnp.int32), axis=axis)


def _enum_trace(model, rng_key, args, kwargs, first_available_dim):
    """Run the hidden enumeration pass: seed, auto-detect max_plate_nesting
    (unless first_available_dim pins it), and trace under `enum`. Shared by
    discrete_marginals and _decode_discrete."""
    with block():  # hide the enumeration pass from enclosing handlers
        seeded = seed(model, jnp.asarray(rng_key))
        if first_available_dim is None:
            proto_tr = trace(seeded).get_trace(*args, **kwargs)
            mpn = _max_plate_nesting(proto_tr)
        else:
            mpn = -first_available_dim - 1
        with enum(first_available_dim=-1 - mpn):
            tr = trace(seeded).get_trace(*args, **kwargs)
    return tr


def discrete_marginals(
    model: Callable,
    rng_key,
    *args,
    first_available_dim: Optional[int] = None,
    **kwargs,
) -> Dict[str, jax.Array]:
    """Exact posterior marginals of every enumerated site, as normalized
    log-probabilities with the site's support on the LAST axis (preceded by
    the site's plate dims). Condition/substitute the model beforehand.

    Uses the dice-factor identity: d logZ / d (site's log-factor) is the
    posterior marginal of that factor's indices, which stays exact even when
    a global enumerated variable couples plate slices (a per-site contraction
    would drop the other slices' evidence about the global)."""
    if rng_key is None:
        rng_key = prng_key()  # ambient seed handler, if any
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    tr = _enum_trace(model, rng_key, args, kwargs, first_available_dim)
    factors, depth, pool = _collect_factors(tr)

    enum_sites = {
        name: site
        for name, site in tr.nodes.items()
        if site["type"] == "sample" and "_enumerate_dim" in site["infer"]
    }
    sample_names = [
        name for name, site in tr.nodes.items() if site["type"] == "sample"
    ]

    def log_z(perturbs: Dict[str, jax.Array]) -> jax.Array:
        perturbed = [
            (o, t + perturbs[name], s) if name in perturbs else (o, t, s)
            for name, (o, t, s) in zip(sample_names, factors)
        ]
        return jnp.sum(contract_log_factors(perturbed, depth, pool))

    zero = {
        name: jnp.zeros_like(factors[sample_names.index(name)][1])
        for name in enum_sites
    }
    joint_probs = jax.grad(log_z)(zero)

    marginals: Dict[str, jax.Array] = {}
    for name, site in enum_sites.items():
        d = site["infer"]["_enumerate_dim"]
        probs = joint_probs[name]
        # sum joint posterior over everything but this site's own enum dim
        # and its plate dims (per-slice marginals)
        keep = {d} | {f.dim for f in site["cond_indep_stack"]}
        drop = tuple(a for a in range(-jnp.ndim(probs), 0) if a not in keep)
        probs = jnp.sum(probs, axis=drop, keepdims=True) if drop else probs
        logits = jnp.moveaxis(jnp.log(probs), jnp.ndim(probs) + d, -1)
        target_rank = max([-f.dim for f in site["cond_indep_stack"]], default=0)
        marginals[name] = _squeeze_to_rank(
            jax.nn.log_softmax(logits, -1), target_rank + 1
        )
    return marginals


def _squeeze_to_rank(x: jax.Array, rank: int) -> jax.Array:
    """Drop leading size-1 axes until `x` has `rank` dims."""
    while jnp.ndim(x) > rank and jnp.shape(x)[0] == 1:
        x = x[0]
    return x


def gaussian_marginals(
    model: Callable,
    rng_key,
    *args,
    sites: Optional[List[str]] = None,
    first_available_dim: Optional[int] = None,
    **kwargs,
) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """Exact posterior (mean, covariance) of every Gaussian-marginalized
    site — the smoother marginals of a Kalman model, conjugate posteriors of
    a Bayesian linear regression, or the moment-matched mixture marginals of
    a switching LDS (discrete enum and Gaussian elimination run in one mixed
    contraction). Condition/substitute observations into the model first,
    the way `discrete_marginals` expects.

    Returns ``{site: (mean, cov)}``: scalar mean and variance for `Normal`
    sites, ``(D,)`` mean and ``(D, D)`` covariance for `MultivariateNormal`
    sites. ``sites`` restricts the query (covariances scale cubically with
    total queried width).

    Uses the cumulant trick — the Gaussian analogue of `discrete_marginals`'
    dice-factor identity: appending a zero-precision perturbation factor
    with info_vec ε to a site makes ∇_ε log Z the posterior mean and the
    ε-Hessian the posterior covariance, both exact (and mixture-exact under
    enumeration, since log Z sums over the discrete support)."""
    if rng_key is None:
        rng_key = prng_key()
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    tr = _enum_trace(model, rng_key, args, kwargs, first_available_dim)
    mpn = (
        -first_available_dim - 1
        if first_available_dim is not None
        else _max_plate_nesting(tr)
    )
    seeded = seed(model, jnp.asarray(rng_key))

    def make_trace(values):
        with block():
            with enum(first_available_dim=-1 - mpn):
                return trace(substitute(seeded, data=values)).get_trace(*args, **kwargs)

    pool = frozenset(
        s["infer"]["_enumerate_dim"]
        for s in tr.nodes.values()
        if s["type"] == "sample" and s["infer"].get("_enumerate_dim") is not None
    )
    gauss = _lower_gaussian_trace(make_trace, tr, pool, fixed=frozenset())
    if gauss is None:
        raise ValueError(
            "no sites are annotated for Gaussian marginalization; wrap the "
            'model in config(marginalize="gaussian") (formerly '
            "config_gaussian) or annotate sites with "
            'infer={"marginalize": "gaussian"}'
        )
    factors, depth, _ = _collect_factors(tr, skip=gauss.entangled)
    query = list(gauss.order) if sites is None else list(sites)
    for n in query:
        if n not in gauss.order:
            raise ValueError(
                f"site '{n}' is not Gaussian-marginalized "
                f"(marginalized sites: {gauss.order})"
            )

    def log_z(eps: Dict[str, jax.Array]) -> jax.Array:
        gfs = list(gauss.factors)
        for n, e in eps.items():
            w = gauss.widths[n]
            gfs.append(
                GaussianFactor(
                    (n,), (w,),
                    jnp.zeros((w, w), jnp.float32), e, jnp.zeros((), jnp.float32),
                )
            )
        extra = [
            (frozenset(), t, None)
            for t in eliminate_gaussian_factors(gfs, gauss.order)
        ]
        return jnp.sum(contract_log_factors(factors + extra, depth, pool))

    zero = {n: jnp.zeros((gauss.widths[n],), jnp.float32) for n in query}
    means = jax.grad(log_z)(zero)
    # reverse over reverse: the Gaussian kernels carry custom VJPs, which
    # forward mode (jacfwd) cannot differentiate
    covs = jax.jacrev(jax.grad(log_z))(zero)
    out: Dict[str, Tuple[jax.Array, jax.Array]] = {}
    for n in query:
        m, C = means[n], covs[n][n]
        out[n] = (m, C) if gauss.event_shapes[n] else (m[0], C[0, 0])
    return out


def _decode_discrete(model, rng_key, args, kwargs, first_available_dim, temperature):
    """Decode enumerated sites: temperature=1 -> exact joint posterior sample
    (sequential conditioning = chain rule); 0 -> exact joint MAP (max-product
    elimination + sequential argmax)."""
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    key_trace, key_sample = jax.random.split(jnp.asarray(rng_key))
    sum_op = _max_op if temperature == 0 else _logsumexp_op
    tr = _enum_trace(model, key_trace, args, kwargs, first_available_dim)
    factors, depth, pool = _collect_factors(tr)

    enum_sites = [
        (name, site)
        for name, site in tr.nodes.items()
        if site["type"] == "sample" and "_enumerate_dim" in site["infer"]
    ]
    # allocation order == execution order == decreasing dim
    enum_sites.sort(key=lambda ns: -ns[1]["infer"]["_enumerate_dim"])

    values: Dict[str, jax.Array] = {}
    for i, (name, site) in enumerate(enum_sites):
        d = site["infer"]["_enumerate_dim"]
        ordinal = frozenset(site["cond_indep_stack"])
        marg = contract_log_factors(
            factors, depth, pool, keep_dims=frozenset([d]), keep_frames=ordinal,
            sum_op=sum_op,
        )
        logits = jnp.moveaxis(marg, jnp.ndim(marg) + d, -1)  # (*plates, K)
        # the decoded value's batch rank comes from the site's plate context
        # (the enum-trace fn.batch_shape is polluted by parent enum dims)
        target_rank = max([-f.dim for f in site["cond_indep_stack"]], default=0)
        if temperature == 0:
            idx = jnp.argmax(logits, -1)
        else:
            idx = jax.random.categorical(jax.random.fold_in(key_sample, i), logits)
        # condition the remaining factors on the decoded value (chain rule)
        idx_r = jnp.expand_dims(idx, d)
        factors = [(o, _index_factor(t, d, idx_r), s) for o, t, s in factors]
        # map index -> support value, shaped like an ordinary draw at the site
        support = site["fn"].enumerate_support(expand=False)
        event_shape = site["fn"].event_shape
        support_flat = jnp.reshape(support, (jnp.shape(support)[0],) + event_shape)
        val = jnp.take(support_flat, idx, axis=0)
        values[name] = _squeeze_to_rank(val, target_rank + len(event_shape))

    # pin every free (non-enumerated) latent to its decode-pass draw: the
    # discrete sites were decoded AGAINST those values, so re-sampling them in
    # the replay pass would return an inconsistent (continuous, discrete) pair
    for name, site in tr.nodes.items():
        if (
            site["type"] == "sample"
            and not site["is_observed"]
            and name not in values
        ):
            values[name] = site["value"]
    return values


class _InferDiscrete:
    """Callable wrapper produced by `infer_discrete`."""

    def __init__(self, fn, first_available_dim, temperature, rng_key):
        self.fn = fn
        self.first_available_dim = first_available_dim
        self.temperature = temperature
        self.rng_key = rng_key
        functools.update_wrapper(self, fn, updated=[])

    def __call__(self, *args, **kwargs):
        # no explicit key -> draw one from the ambient seed handler, so each
        # seeded call of the wrapper yields a fresh posterior draw instead of
        # silently repeating one fixed decode
        rng_key = self.rng_key
        if rng_key is None:
            rng_key = prng_key()
        values = _decode_discrete(
            self.fn,
            rng_key,
            args,
            kwargs,
            self.first_available_dim,
            self.temperature,
        )
        return substitute(self.fn, data=values)(*args, **kwargs)


def infer_discrete(
    fn: Optional[Callable] = None,
    *,
    first_available_dim: Optional[int] = None,
    temperature: int = 1,
    rng_key=None,
) -> Callable:
    """Posterior decoding of enumerated discrete sites (Pyro's
    `infer_discrete`): returns a model whose annotated discrete sites take
    exact joint posterior samples (``temperature=1``, sequential conditioning
    via the chain rule) or the exact joint MAP assignment (``temperature=0``,
    max-product elimination), given the observations/conditioning baked into
    the model. Any free continuous latents are drawn once (keyed by
    ``rng_key``) and pinned across the decode and replay passes, so the
    returned execution is one coherent joint draw — but their posterior is
    NOT inferred here. Continuous posteriors go in first — substitute
    SVI/MCMC draws into the model, then decode:

        guide_draws = {...}                      # from SVI or MCMC
        decoded = infer_discrete(
            handlers.substitute(config(model, enumerate=True), data=guide_draws),
            temperature=0, rng_key=key)
        tr = handlers.trace(decoded).get_trace(data)
        assignments = tr["z"]["value"]
    """
    if fn is None:
        return functools.partial(
            infer_discrete,
            first_available_dim=first_available_dim,
            temperature=temperature,
            rng_key=rng_key,
        )
    if temperature not in (0, 1):
        raise ValueError(f"temperature must be 0 (MAP) or 1 (sample), got {temperature}")
    return _InferDiscrete(fn, first_available_dim, temperature, rng_key)
