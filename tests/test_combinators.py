"""Inference-combinator semantics (ISSUE 10): programs as values.

`primitive`/`compose`/`extend`/`propose`/`resample` are the algebra the SMC
engine is assembled from; these tests pin their weight/trace semantics on
models with closed-form answers — the propose weight against an analytic
marginal likelihood, compose/extend trace merging (duplicate sites must
raise), the resample combinator's population-only contract, and the
`ImportanceSampling` engine (the degenerate one-step propose) against both
the analytic evidence and its own documented accessors.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import distributions as dist
from repro.core import primitives as P
from repro.distributed import make_mesh
from repro.infer import ImportanceSampling, compose, extend, primitive, propose
from repro.infer import resample as resample_combinator
from repro.infer.combinators import (
    Population,
    Primitive,
    effective_sample_size,
)

KEY = jax.random.PRNGKey(0)


# conjugate pair: z ~ N(0,1), y | z ~ N(z,1)  =>  p(y) = N(y; 0, sqrt(2))
Y_OBS = jnp.float32(0.5)
LOG_Z_EXACT = float(dist.Normal(0.0, jnp.sqrt(2.0)).log_prob(Y_OBS))


def model():
    z = P.sample("z", dist.Normal(0.0, 1.0))
    P.sample("y", dist.Normal(z, 1.0), obs=Y_OBS)
    return z


def guide():
    # the exact posterior N(y/2, 1/sqrt(2)): zero-variance importance weights
    return P.sample("z", dist.Normal(Y_OBS / 2.0, 1.0 / jnp.sqrt(2.0)))


# ---------------------------------------------------------------------------
# primitive
# ---------------------------------------------------------------------------


def test_primitive_run_returns_trace_output_weight():
    r = primitive(model).run(KEY, {})
    assert "z" in r.trace and "y" in r.trace
    # weight = observed log prob only
    expected = float(dist.Normal(r.trace["z"]["value"], 1.0).log_prob(Y_OBS))
    assert np.isclose(float(r.log_weight), expected, rtol=1e-6)
    assert float(r.output) == float(r.trace["z"]["value"])


def test_primitive_is_idempotent():
    p = primitive(model)
    assert primitive(p) is p
    assert isinstance(p, Primitive)


# ---------------------------------------------------------------------------
# compose / extend
# ---------------------------------------------------------------------------


def test_compose_merges_traces_and_adds_weights():
    def f1():
        x = P.sample("x", dist.Normal(0.0, 1.0))
        P.sample("obs1", dist.Normal(x, 1.0), obs=jnp.float32(0.1))
        return x

    def f2(x):
        y = P.sample("y", dist.Normal(x, 1.0))
        P.sample("obs2", dist.Normal(y, 1.0), obs=jnp.float32(0.2))
        return y

    r = compose(f2, f1).run(KEY, {})
    assert set(r.trace.nodes) >= {"x", "y", "obs1", "obs2"}
    w1 = float(dist.Normal(r.trace["x"]["value"], 1.0).log_prob(0.1))
    w2 = float(dist.Normal(r.trace["y"]["value"], 1.0).log_prob(0.2))
    assert np.isclose(float(r.log_weight), w1 + w2, rtol=1e-5)


def test_compose_duplicate_site_raises():
    def f1():
        return P.sample("z", dist.Normal(0.0, 1.0))

    def f2(z):
        return P.sample("z", dist.Normal(z, 1.0))

    with pytest.raises(RuntimeError, match="duplicate site"):
        compose(f2, f1).run(KEY, {})


def test_extend_is_compose_with_swapped_roles():
    def p_prog():
        return P.sample("a", dist.Normal(0.0, 1.0))

    def f_prog(a):
        return P.sample("b", dist.Normal(a, 1.0))

    r = extend(p_prog, f_prog).run(KEY, {})
    assert "a" in r.trace and "b" in r.trace


# ---------------------------------------------------------------------------
# propose
# ---------------------------------------------------------------------------


def test_propose_weight_is_importance_weight():
    """With the exact-posterior guide the importance weight is constant
    (= log Z) for every particle — the zero-variance property."""
    prog = propose(primitive(model), primitive(guide))
    weights = [
        float(prog.run(jax.random.PRNGKey(i), {}).log_weight) for i in range(20)
    ]
    assert np.allclose(weights, LOG_Z_EXACT, atol=1e-5), (weights[:3], LOG_Z_EXACT)


def test_propose_guide_value_replayed_into_model():
    r = propose(primitive(model), primitive(guide)).run(KEY, {})
    # the model's z is the guide's draw, and both ended up in the trace
    assert float(r.output) == float(r.trace["z"]["value"])


# ---------------------------------------------------------------------------
# resample combinator
# ---------------------------------------------------------------------------


def test_resample_validates_arguments():
    with pytest.raises(ValueError):
        resample_combinator(primitive(model), ess_threshold=1.5)
    with pytest.raises(ValueError):
        resample_combinator(primitive(model), method="stratified")


def test_resample_rejects_single_particle_run():
    prog = resample_combinator(primitive(model))
    with pytest.raises(TypeError):
        prog.run(KEY, {})


def _step_population(ess_threshold, log_weights):
    """Drive one resample(primitive(step)) population step from a synthetic
    incoming population and report whether resampling triggered."""

    def step(carry):
        x = P.sample("x", dist.Normal(carry, 1.0))
        return x

    n = log_weights.shape[0]
    prog = resample_combinator(primitive(step), ess_threshold=ess_threshold)
    pop = Population(jnp.zeros(n), jnp.asarray(log_weights, jnp.float32))
    _, aux = jax.jit(
        lambda k, p: prog.run_population(k, {}, p, ())
    )(KEY, pop)
    return aux


def test_ess_boundary_equal_weights_never_resample():
    """Equal weights sit exactly at ESS == N; the trigger is strict `<`, so
    even ess_threshold=1.0 (resample 'always') must not fire — resampling a
    uniform population is pure ancestry noise."""
    aux = _step_population(1.0, jnp.zeros(64))
    assert not bool(aux.resampled)
    assert float(aux.log_z_incr) == 0.0


def test_skewed_weights_trigger_resample_and_reset():
    lw = jnp.concatenate([jnp.zeros(4), jnp.full(60, -30.0)])
    aux = _step_population(0.5, lw)
    assert bool(aux.resampled)
    # logZ increment flushed at the event: logsumexp(W) - log N
    expected = float(jax.scipy.special.logsumexp(lw) - jnp.log(64.0))
    assert np.isclose(float(aux.log_z_incr), expected, rtol=1e-5)


def test_threshold_zero_never_resamples():
    lw = jnp.concatenate([jnp.zeros(2), jnp.full(62, -30.0)])
    aux = _step_population(0.0, lw)
    assert not bool(aux.resampled)


def test_effective_sample_size_contract():
    assert float(effective_sample_size(jnp.zeros(128))) == 128.0
    one = jnp.full(16, -jnp.inf).at[3].set(0.0)
    assert np.isclose(float(effective_sample_size(one)), 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# ImportanceSampling: the degenerate one-step propose
# ---------------------------------------------------------------------------


def test_importance_sampling_log_evidence():
    eng = ImportanceSampling(model, guide, num_particles=4000)
    eng.run(jax.random.PRNGKey(2))
    assert np.isclose(float(eng.log_evidence()), LOG_Z_EXACT, atol=1e-4)
    # exact-posterior guide => ESS == N
    assert np.isclose(float(eng.effective_sample_size()), 4000.0, rtol=1e-4)


def test_importance_sampling_no_guide_prior_proposal():
    eng = ImportanceSampling(model, num_particles=20000)
    eng.run(jax.random.PRNGKey(3))
    assert np.isclose(float(eng.log_evidence()), LOG_Z_EXACT, atol=0.05)


def test_importance_sampling_accessors():
    eng = ImportanceSampling(model, guide, num_particles=64)
    assert eng.run(jax.random.PRNGKey(4)) is eng  # fluent (the legacy contract)
    assert eng.get_samples()["z"].shape == (64,)
    assert eng.get_samples(group_by_chain=True)["z"].shape == (1, 64)
    assert eng.log_weights.shape == (64,)
    assert eng.num_traces == 1  # vmap traces the particle program once
    draws = eng.resample(jax.random.PRNGKey(5), 32)
    assert draws["z"].shape == (32,)


def test_importance_sampling_sharded_matches_vectorized():
    mesh = make_mesh((1,), ("data",))
    vec = ImportanceSampling(model, guide, num_particles=256)
    sh = ImportanceSampling(model, guide, num_particles=256, mesh=mesh)
    vec.run(jax.random.PRNGKey(6))
    sh.run(jax.random.PRNGKey(6))
    assert jnp.array_equal(vec.log_weights, sh.log_weights)
    assert jnp.array_equal(vec.get_samples()["z"], sh.get_samples()["z"])
