"""SMC engine conformance: exact Kalman gate (ISSUE 10).

The acceptance anchor: filtering means and the log-marginal-likelihood
estimate of the SMC engine on a linear-Gaussian SSM must converge, at
N = 65536 particles within ~3 sigma of their Monte-Carlo error, to the
exact answers — the float64 sequential Kalman filter here, cross-checked
against `gaussian_marginals` (the PR-8 Gaussian semiring) on the same
model. The reference kernel backend carries the 64k row; the interpret
backend (Pallas resampling body, O(N^2) on CPU) runs the same gate at
N = 4096 with proportionally wider tolerance.

Also pinned: sharded == vectorized bit-identity on a 1-device mesh, the
compile-once contract (`num_traces == 1` across re-runs), the multinomial
resampling alternative, the streaming `SMCFilter` against the offline
sweep, `NestedVariational` training, and SMC^2 as a pure composition
(an inner marginal-likelihood population inside the outer carry).
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import distributions as dist
from repro.core import primitives as P
from repro.distributed import make_mesh
from repro.infer import (
    SMC,
    SMCFilter,
    SVI,
    NestedVariational,
    gaussian_marginals,
    sequential_pair,
)
from repro.optim import Adam

A, S_TRANS, S_OBS, P0 = 0.9, 0.3, 0.5, 1.0
GM = {"marginalize": "gaussian"}


def kalman_filter_reference(ys):
    """Float64 sequential Kalman filter: per-step filtering means/variances
    and the exact log marginal likelihood (independent of everything under
    test)."""
    T = len(ys)
    fm, fp = np.zeros(T), np.zeros(T)
    pm, pp = 0.0, P0 * P0
    logz = 0.0
    for t in range(T):
        if t > 0:
            pm, pp = A * fm[t - 1], A * A * fp[t - 1] + S_TRANS**2
        s = pp + S_OBS**2
        logz += -0.5 * ((ys[t] - pm) ** 2 / s + np.log(2 * np.pi * s))
        k = pp / s
        fm[t] = pm + k * (ys[t] - pm)
        fp[t] = (1 - k) * pp
    return fm, fp, logz


def model_init(y):
    x = P.sample("x", dist.Normal(0.0, P0))
    P.sample("y", dist.Normal(x, S_OBS), obs=y)
    return {"x": x}


def model_step(carry, y):
    x = P.sample("x", dist.Normal(A * carry["x"], S_TRANS))
    P.sample("y", dist.Normal(x, S_OBS), obs=y)
    return {"x": x}


def observations(T=12, seed=0):
    gen = np.random.default_rng(seed)
    xs = [gen.normal(0.0, P0)]
    for _ in range(T - 1):
        xs.append(A * xs[-1] + gen.normal(0.0, S_TRANS))
    return jnp.asarray([x + gen.normal(0.0, S_OBS) for x in xs], dtype=jnp.float32)


YS = observations()
FM, FP, LOG_Z = kalman_filter_reference(np.asarray(YS, np.float64))


# ---------------------------------------------------------------------------
# tentpole gate: Kalman conformance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "backend,n",
    [("reference", 65536), ("interpret", 4096)],
    ids=["reference-64k", "interpret-4k"],
)
def test_smc_matches_kalman(backend, n, monkeypatch):
    """Filtering means within ~3 sigma of their Monte-Carlo standard error
    at every step, and log Z within a few sigma of the resampling noise.
    The MC error of a weighted mean is ~sqrt(Var/ESS); resampling couples
    particles over time, so the gate uses a conservative 5x floor on the
    iid estimate rather than pretending independence."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    smc = SMC(model_init, model_step, num_particles=n)
    smc.run(jax.random.PRNGKey(0), YS)

    means = np.asarray(smc.filtering_means()["x"])
    assert smc.result.includes_init and means.shape == (len(YS),)
    for t in range(len(YS)):
        se = 5.0 * math.sqrt(FP[t] / n)
        assert abs(means[t] - FM[t]) < max(3.0 * se, 0.02), (
            t, means[t], FM[t], se
        )
    # logZ: T resampling stages each contribute O(1/sqrt(N)) noise
    tol = max(10.0 * len(YS) / math.sqrt(n), 0.05)
    assert abs(float(smc.log_evidence()) - LOG_Z) < tol, (
        float(smc.log_evidence()), LOG_Z, tol
    )


def test_kalman_reference_agrees_with_gaussian_semiring():
    """The float64 filter above and PR-8's Gaussian semiring compute the
    same posterior: smoother mean == filtering mean at the final step."""

    def marginalized():
        x = P.sample("x0", dist.Normal(0.0, P0), infer=GM)
        P.sample("y0", dist.Normal(x, S_OBS), obs=YS[0])
        for t in range(1, len(YS)):
            x = P.sample(f"x{t}", dist.Normal(A * x, S_TRANS), infer=GM)
            P.sample(f"y{t}", dist.Normal(x, S_OBS), obs=YS[t])

    last = f"x{len(YS) - 1}"
    out = gaussian_marginals(marginalized, jax.random.PRNGKey(0), sites=[last])
    m, v = out[last]
    assert np.isclose(float(m), FM[-1], rtol=1e-4, atol=1e-5)
    assert np.isclose(float(v), FP[-1], rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# engine contracts
# ---------------------------------------------------------------------------


def test_num_traces_stays_one_across_reruns():
    smc = SMC(model_init, model_step, num_particles=512)
    for rep in range(3):
        smc.run(jax.random.PRNGKey(rep), YS + 1e-4 * rep)
    assert smc.num_traces == 1


def test_sharded_matches_vectorized_bit_identical():
    mesh = make_mesh((jax.device_count(),), ("data",))
    vec = SMC(model_init, model_step, num_particles=1024)
    sh = SMC(model_init, model_step, num_particles=1024, mesh=mesh)
    vec.run(jax.random.PRNGKey(1), YS)
    sh.run(jax.random.PRNGKey(1), YS)
    if jax.device_count() == 1:
        assert jnp.array_equal(vec.log_weights, sh.log_weights)
        assert jnp.array_equal(vec.get_samples()["x"], sh.get_samples()["x"])
        assert float(vec.log_evidence()) == float(sh.log_evidence())


def test_get_samples_shapes_and_chain_convention():
    smc = SMC(model_init, model_step, num_particles=256)
    out = smc.run(jax.random.PRNGKey(2), YS)
    assert out["x"].shape == (256,)
    assert smc.get_samples(group_by_chain=True)["x"].shape == (1, 256)
    assert smc.ess_history().shape == (len(YS),)


def test_multinomial_resampling_also_converges():
    smc = SMC(
        model_init, model_step, num_particles=8192, resample_method="multinomial"
    )
    smc.run(jax.random.PRNGKey(3), YS)
    assert abs(float(smc.log_evidence()) - LOG_Z) < 0.3
    assert abs(float(smc.filtering_means()["x"][-1]) - FM[-1]) < 0.05


def test_adaptive_resampling_actually_fires():
    smc = SMC(model_init, model_step, num_particles=1024, ess_threshold=0.5)
    smc.run(jax.random.PRNGKey(4), YS)
    resampled = np.asarray(smc.result.history.resampled)
    assert resampled.any(), "no resample event in a 12-step bootstrap sweep"
    assert not resampled.all(), "resampling every step at threshold 0.5"


def test_never_resample_matches_plain_importance_weights():
    """ess_threshold=0 degenerates SMC to sequential importance sampling:
    log Z must equal the one flush of the final weights."""
    smc = SMC(model_init, model_step, num_particles=512, ess_threshold=0.0)
    smc.run(jax.random.PRNGKey(5), YS)
    lw = smc.log_weights
    flush = float(jax.scipy.special.logsumexp(lw) - jnp.log(512.0))
    assert np.isclose(float(smc.log_evidence()), flush, rtol=1e-6)
    assert not np.asarray(smc.result.history.resampled).any()


# ---------------------------------------------------------------------------
# streaming filter
# ---------------------------------------------------------------------------


def test_smc_filter_streams_with_one_compile():
    f = SMCFilter(model_init, model_step, num_particles=2048)
    state, info = f.init_state(jax.random.PRNGKey(6), YS[0])
    for y in YS[1:]:
        state, info = f.update(state, y)
    assert int(state.t) == len(YS)
    assert f.num_traces == 1 and f.num_init_traces == 1
    # the streamed estimate converges on the same exact targets
    assert abs(float(info["log_evidence"]) - LOG_Z) < 0.5
    assert abs(float(info["means"]["x"]) - FM[-1]) < 0.1


def test_smc_filter_params_hot_swap_no_recompile():
    """`params` rides the traced signature: streaming with swapped param
    values must not retrace (the serve-layer refresh contract)."""

    def q_init(y):
        loc = P.param("q_loc", jnp.float32(0.0))
        return P.sample("x", dist.Normal(loc, P0))

    def q_step(carry, y):
        g = P.param("q_gain", jnp.float32(A))
        return P.sample("x", dist.Normal(g * carry["x"], S_TRANS))

    f = SMCFilter(
        model_init, model_step,
        proposal_init=q_init, proposal_step=q_step, num_particles=256,
    )
    state, _ = f.init_state(
        jax.random.PRNGKey(7), YS[0], params={"q_loc": jnp.float32(0.0),
                                              "q_gain": jnp.float32(A)}
    )
    for i, y in enumerate(YS[1:]):
        state, _ = f.update(
            state, y, params={"q_loc": jnp.float32(0.01 * i),
                              "q_gain": jnp.float32(A + 0.001 * i)}
        )
    assert f.num_traces == 1, f.num_traces


# ---------------------------------------------------------------------------
# nested compositions: variational SMC and SMC^2
# ---------------------------------------------------------------------------


Y1 = jnp.asarray([0.7], dtype=jnp.float32)  # T=1: the sweep degenerates to
# the IWAE bound (no resampling); fixed so the misspecified starting
# proposal below is unambiguously far from the posterior
# posterior for x0 | y0: precision-weighted combination of N(0, P0) and the
# observation; evidence N(y0; 0, sqrt(P0^2 + S_OBS^2))
_POST_VAR = 1.0 / (1.0 / P0**2 + 1.0 / S_OBS**2)
_POST_MEAN = float(_POST_VAR * float(Y1[0]) / S_OBS**2)
_LOG_Z1 = float(
    dist.Normal(0.0, math.sqrt(P0**2 + S_OBS**2)).log_prob(Y1[0])
)


def _q_step_prior(carry, y):
    return {"x": P.sample("x", dist.Normal(A * carry["x"], S_TRANS))}


def test_nested_variational_exact_proposal_is_tight():
    """With the exact posterior as the proposal, every inner particle's
    weight equals log Z exactly — the bound is tight with zero variance,
    for any key. This pins the propose-weight arithmetic end to end."""

    def q_exact(y):
        return {"x": P.sample("x", dist.Normal(_POST_MEAN, math.sqrt(_POST_VAR)))}

    loss = NestedVariational(
        model_init, model_step,
        proposal_init=q_exact, proposal_step=_q_step_prior, num_inner=4,
    )
    vals = [
        float(loss.loss(jax.random.PRNGKey(i), {}, None, None, Y1))
        for i in range(5)
    ]
    assert np.allclose(vals, -_LOG_Z1, atol=1e-5), (vals, -_LOG_Z1)


def test_nested_variational_trains_toward_tight_bound():
    """T=1 keeps the gradient unbiased (no ancestry to stop-gradient
    through): SVI must drive a misspecified proposal location toward the
    posterior mean and the averaged loss down toward -log Z."""

    def q_learn(y):
        loc = P.param("q_loc", jnp.float32(-1.0))
        return {"x": P.sample("x", dist.Normal(loc, math.sqrt(_POST_VAR)))}

    loss = NestedVariational(
        model_init, model_step,
        proposal_init=q_learn, proposal_step=_q_step_prior, num_inner=8,
    )
    svi = SVI(
        sequential_pair(model_init, model_step),
        sequential_pair(q_learn, _q_step_prior),
        Adam(5e-2),
        loss,
    )
    state = svi.init(jax.random.PRNGKey(8), Y1)
    p0 = svi.optim.get_params(state.optim_state)
    for _ in range(300):
        state, val = svi.update_jit(state, Y1)
        assert np.isfinite(float(val))
    pT = svi.optim.get_params(state.optim_state)
    assert svi.num_traces == 1

    def avg_loss(p):
        return float(np.mean([
            float(loss.loss(jax.random.PRNGKey(500 + i), p, None, None, Y1))
            for i in range(16)
        ]))

    assert abs(float(pT["q_loc"]) - _POST_MEAN) < 0.3, float(pT["q_loc"])
    l0, lT = avg_loss(p0), avg_loss(pT)
    assert lT < l0 - 1.0, (l0, lT)  # large, unambiguous improvement
    assert lT < -_LOG_Z1 + 0.2  # near the tight floor
    assert lT > -_LOG_Z1 - 0.2  # and never below it (it IS a bound)


def test_nested_variational_multistep_smoke():
    """The full multi-step sweep (resampling active, biased VSMC gradient)
    must train stably: finite losses, one compile, moving params."""

    def q_init_d(y):
        loc = P.param("q_loc0", jnp.float32(0.0))
        return {"x": P.sample("x", dist.Normal(loc, P0))}

    def q_step_d(carry, y):
        g = P.param("q_gain", jnp.float32(0.5))
        return {"x": P.sample("x", dist.Normal(g * carry["x"], S_TRANS))}

    loss = NestedVariational(
        model_init, model_step,
        proposal_init=q_init_d, proposal_step=q_step_d, num_inner=8,
    )
    svi = SVI(
        sequential_pair(model_init, model_step),
        sequential_pair(q_init_d, q_step_d),
        Adam(5e-3),
        loss,
    )
    state = svi.init(jax.random.PRNGKey(9), YS)
    losses = []
    for _ in range(60):
        state, val = svi.update_jit(state, YS)
        losses.append(float(val))
    assert all(np.isfinite(losses))
    assert svi.num_traces == 1
    pT = svi.optim.get_params(state.optim_state)
    assert float(pT["q_loc0"]) != 0.0 or float(pT["q_gain"]) != 0.5
    # -E[log Zhat] is bounded below by -log Z
    assert np.mean(losses[-10:]) > -LOG_Z - 1.0


def test_smc_squared_as_composition():
    """SMC^2 needs no new machinery: the outer particle's carry holds an
    inner population whose per-step evidence increment enters the outer
    weight through `P.factor` — everything rides the same sweep."""
    from repro.infer import smc_sweep
    from repro.infer.combinators import primitive, resample

    N_INNER = 64

    def outer_init(y):
        # static latent for the outer level: the transition gain
        a = P.sample("a", dist.Uniform(0.5, 1.0))
        # inner population: iid prior x-particles, reweighted by y_0
        with P.plate("inner", N_INNER):
            x = P.sample("x", dist.Normal(0.0, P0))
        lw = dist.Normal(x, S_OBS).log_prob(y)
        incr = jax.scipy.special.logsumexp(lw) - jnp.log(float(N_INNER))
        P.factor("evidence", incr)
        return {"a": a, "x": x, "lw": lw - jax.scipy.special.logsumexp(lw)}

    def outer_step(carry, y):
        a, x, lw = carry["a"], carry["x"], carry["lw"]
        # Rao-Blackwellized inner propagation under gain `a`: transition
        # noise folds into the predictive variance, the inner evidence
        # increment enters the outer weight through the factor site
        x = a * x
        pred_lw = dist.Normal(x, jnp.sqrt(S_TRANS**2 + S_OBS**2)).log_prob(y)
        incr = (
            jax.scipy.special.logsumexp(lw + pred_lw)
            - jax.scipy.special.logsumexp(lw)
        )
        P.factor("evidence", incr)
        lw = lw + pred_lw
        lw = lw - jax.scipy.special.logsumexp(lw)
        return {"a": a, "x": x, "lw": lw}

    step_prog = resample(primitive(outer_step), ess_threshold=0.5)
    result = smc_sweep(
        primitive(outer_init), step_prog,
        jax.random.PRNGKey(9), YS, num_particles=128,
    )
    assert np.isfinite(float(result.log_evidence))
    # the outer weighted posterior over `a` (held in the carry — `a` is
    # sampled once at init, so it is not in the per-step latent history)
    # stays a proper distribution on its prior support
    w = jax.nn.softmax(result.population.log_weights)
    a_mean = float(jnp.sum(w * result.population.carry["a"]))
    assert 0.5 < a_mean < 1.0
    assert float(jnp.sum(w)) == pytest.approx(1.0, rel=1e-5)
