"""Multi-chain MCMC engine + convergence diagnostics.

Diagnostics are validated against hand-computed references (explicit
numpy transcriptions of the split-R̂ formula) and known asymptotics
(iid chains -> ESS ~ total draws, AR(1) chains -> ESS far below it);
the engine is checked for chain layout, trace-count, sharded/vectorized
bit-identity on a 1-device mesh, and posterior correctness with 4 chains.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import distributions as dist
from repro.core import primitives as P
from repro.distributed import make_mesh
from repro.infer import (
    HMC,
    MCMC,
    NUTS,
    Predictive,
    effective_sample_size,
    split_rhat,
)

DATA = jnp.asarray([1.0, 2.0, 3.0, 2.5, 1.5])
POST_MEAN = float(DATA.sum() / (len(DATA) + 1 / 100.0))
POST_SD = float((1.0 / (len(DATA) + 0.01)) ** 0.5)


def normal_model(data):
    loc = P.sample("loc", dist.Normal(0.0, 10.0))
    with P.plate("N", data.shape[0]):
        P.sample("obs", dist.Normal(loc, 1.0), obs=data)


def small_hmc():
    return HMC(normal_model, max_num_steps=16)


# ---------------------------------------------------------------------------
# diagnostics: split-R̂
# ---------------------------------------------------------------------------


def test_split_rhat_hand_computed():
    """2 chains x 4 draws, reference computed by hand from the split-chain
    formula: split -> 4 half-chains of 2 draws; W = mean within-chain var,
    B/n = var of half-chain means; rhat = sqrt(((n-1)/n * W + B/n) / W)."""
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0], [3.0, 4.0, 5.0, 6.0]])
    halves = np.asarray([[1.0, 2.0], [3.0, 4.0], [3.0, 4.0], [5.0, 6.0]])
    n = halves.shape[1]
    w = halves.var(axis=1, ddof=1).mean()
    b_over_n = halves.mean(axis=1).var(ddof=1)
    expected = np.sqrt(((n - 1) / n * w + b_over_n) / w)
    assert float(split_rhat(x)) == pytest.approx(float(expected), rel=1e-5)


def test_split_rhat_well_mixed_chains_near_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 1000))
    assert float(split_rhat(x)) == pytest.approx(1.0, abs=0.02)


def test_split_rhat_shifted_chains_much_greater_than_one():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 500))
    shifted = x + 10.0 * jnp.arange(4.0)[:, None]
    assert float(split_rhat(shifted)) > 3.0


def test_split_rhat_detects_within_chain_drift():
    """A strong trend inside each chain inflates split-R̂ even though the
    chains agree with each other — that's what the split buys."""
    trend = jnp.linspace(0.0, 8.0, 600)[None, :]
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 600)) + trend
    assert float(split_rhat(x)) > 1.5


def test_split_rhat_event_shapes():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 400, 3))
    r = split_rhat(x)
    assert r.shape == (3,)
    assert np.allclose(np.asarray(r), 1.0, atol=0.05)


# ---------------------------------------------------------------------------
# diagnostics: effective sample size
# ---------------------------------------------------------------------------


def test_ess_iid_close_to_total_draws():
    m, n = 4, 1000
    x = jax.random.normal(jax.random.PRNGKey(4), (m, n))
    ess = float(effective_sample_size(x))
    assert 0.5 * m * n < ess <= 1.2 * m * n


def test_ess_ar1_far_below_total_draws():
    """AR(1) with rho=0.9 has asymptotic ESS factor (1-rho)/(1+rho) ~ 0.053;
    the estimate must come out far below the raw draw count."""
    m, n, rho = 4, 1000, 0.9
    eps = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (m, n)))
    x = np.zeros((m, n))
    x[:, 0] = eps[:, 0]
    for t in range(1, n):
        x[:, t] = rho * x[:, t - 1] + np.sqrt(1 - rho**2) * eps[:, t]
    ess = float(effective_sample_size(jnp.asarray(x)))
    assert ess < 0.3 * m * n
    # and in the right ballpark of the theoretical factor
    assert ess == pytest.approx(m * n * (1 - rho) / (1 + rho), rel=1.0)


def test_tail_ess_iid_reasonable():
    m, n = 4, 1000
    x = jax.random.normal(jax.random.PRNGKey(6), (m, n))
    tail = float(effective_sample_size(x, kind="tail"))
    assert 0.2 * m * n < tail <= 1.2 * m * n


def test_ess_kind_validation():
    x = jnp.zeros((2, 10))
    with pytest.raises(ValueError):
        effective_sample_size(x, kind="bogus")


# ---------------------------------------------------------------------------
# diagnostics: degenerate inputs must give documented values, not garbage
# ---------------------------------------------------------------------------


def test_diagnostics_single_chain_well_defined():
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 400))
    assert float(split_rhat(x)) == pytest.approx(1.0, abs=0.05)
    assert float(effective_sample_size(x)) > 100
    assert float(effective_sample_size(x, kind="tail")) > 50


def test_diagnostics_length_one_chain_nan_not_crash():
    x = jnp.ones((2, 1))
    assert bool(jnp.isnan(split_rhat(x)))
    for kind in ("bulk", "tail", "raw"):
        assert bool(jnp.isnan(effective_sample_size(x, kind=kind)))


def test_diagnostics_too_few_draws_nan():
    # split halves need >= 2 draws each; below 4 everything is documented NaN
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 3))
    assert bool(jnp.isnan(split_rhat(x)))
    assert bool(jnp.isnan(effective_sample_size(x)))


def test_diagnostics_constant_chain():
    const = jnp.full((4, 100), 2.5)
    # no variance at all: R̂ undefined (NaN), ESS = total draws by convention
    assert bool(jnp.isnan(split_rhat(const)))
    for kind in ("bulk", "tail", "raw"):
        assert float(effective_sample_size(const, kind=kind)) == 400.0


def test_diagnostics_constant_distinct_chains_inf_rhat():
    # chains frozen at different values: maximally unconverged -> +inf
    x = jnp.broadcast_to(jnp.arange(4.0)[:, None], (4, 100))
    assert bool(jnp.isinf(split_rhat(x)))


def test_diagnostics_nan_draws_propagate():
    """A NaN draw (e.g. a diverged chain) must surface as NaN diagnostics —
    rank-normalization and tail indicators would otherwise silently convert
    it into a finite, trustworthy-looking number."""
    x = jax.random.normal(jax.random.PRNGKey(12), (4, 100)).at[1, 3].set(jnp.nan)
    assert bool(jnp.isnan(split_rhat(x)))
    for kind in ("bulk", "tail", "raw"):
        assert bool(jnp.isnan(effective_sample_size(x, kind=kind)))
    # event-shaped input: only the poisoned column goes NaN
    y = jax.random.normal(jax.random.PRNGKey(13), (4, 100, 2)).at[0, 0, 1].set(jnp.nan)
    ess = effective_sample_size(y)
    assert not bool(jnp.isnan(ess[0]))
    assert bool(jnp.isnan(ess[1]))


# ---------------------------------------------------------------------------
# engine: chain layout, trace count, sharding parity
# ---------------------------------------------------------------------------


def test_multichain_shapes_and_grouping():
    mcmc = MCMC(small_hmc(), num_warmup=50, num_samples=40, num_chains=3)
    flat = mcmc.run(jax.random.PRNGKey(0), DATA)
    assert flat["loc"].shape == (120,)
    grouped = mcmc.get_samples(group_by_chain=True)
    assert grouped["loc"].shape == (3, 40)
    extras = mcmc.get_extra_fields()
    for name in ("accept_prob", "diverging", "num_steps", "potential_energy"):
        assert extras[name].shape == (3, 40)
    assert mcmc.get_extra_fields(group_by_chain=False)["accept_prob"].shape == (120,)
    # the whole run (init + warmup + collection) traced exactly once
    assert mcmc.num_traces == 1


def test_trace_count_independent_of_num_samples():
    counts = []
    for num_samples in (20, 80):
        mcmc = MCMC(small_hmc(), num_warmup=30, num_samples=num_samples)
        mcmc.run(jax.random.PRNGKey(0), DATA)
        counts.append(mcmc.num_traces)
    assert counts == [1, 1]


def test_thinning_shapes():
    mcmc = MCMC(small_hmc(), num_warmup=30, num_samples=25, thinning=2)
    s = mcmc.run(jax.random.PRNGKey(0), DATA)
    assert s["loc"].shape == (25,)


def test_sharded_matches_vectorized_on_one_device_mesh():
    mesh = make_mesh((1,), ("data",))
    runs = {}
    for method, kw in (("vectorized", {"mesh": None}), ("sharded", {"mesh": mesh})):
        mcmc = MCMC(
            small_hmc(), num_warmup=60, num_samples=50, num_chains=2, **kw,
        )
        mcmc.run(jax.random.PRNGKey(0), DATA)
        runs[method] = (
            mcmc.get_samples(group_by_chain=True),
            mcmc.get_extra_fields(),
        )
    s_vec, e_vec = runs["vectorized"]
    s_sh, e_sh = runs["sharded"]
    assert jnp.array_equal(s_vec["loc"], s_sh["loc"])  # bit-for-bit
    assert jnp.array_equal(e_vec["accept_prob"], e_sh["accept_prob"])


def test_chain_method_validation():
    with pytest.warns(FutureWarning), pytest.raises(ValueError):
        MCMC(small_hmc(), 10, 10, chain_method="pmap")
    with pytest.raises(ValueError):
        MCMC(small_hmc(), 10, 10, mesh="tpu")


def test_fused_sharded_matches_vectorized_with_kernels(monkeypatch):
    """Sharded/vectorized bit-identity must survive the fused path with the
    Pallas kernel body enabled (interpret backend): the sharding constraint
    is a layout annotation, never a math change."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    mesh = make_mesh((1,), ("data",))
    runs = {}
    for method, kw in (("vectorized", {"mesh": None}), ("sharded", {"mesh": mesh})):
        mcmc = MCMC(
            small_hmc(), num_warmup=40, num_samples=30, num_chains=2,
            fused=True, **kw,
        )
        mcmc.run(jax.random.PRNGKey(0), DATA)
        runs[method] = (mcmc.get_samples(group_by_chain=True), mcmc.get_extra_fields())
    s_vec, e_vec = runs["vectorized"]
    s_sh, e_sh = runs["sharded"]
    assert jnp.array_equal(s_vec["loc"], s_sh["loc"])  # bit-for-bit
    assert jnp.array_equal(e_vec["accept_prob"], e_sh["accept_prob"])
    assert jnp.array_equal(e_vec["num_steps"], e_sh["num_steps"])


def test_num_traces_one_under_chees():
    """ChEES cross-chain adaptation must not break the compile-once
    contract: one trace per executable, reused across repeat runs."""
    kernel = HMC(normal_model, max_num_steps=32, adapt_trajectory_length=True)
    mcmc = MCMC(kernel, num_warmup=50, num_samples=40, num_chains=4, fused=True)
    mcmc.run(jax.random.PRNGKey(0), DATA)
    assert mcmc.num_traces == 1
    # same shapes, fresh key/data -> the cached executable is reused
    mcmc.run(jax.random.PRNGKey(1), DATA + 0.5)
    assert mcmc.num_traces == 1


def test_fused_vs_legacy_same_posterior():
    """The fused driver is a new execution strategy, not a new sampler: both
    paths recover the same conjugate posterior."""
    post = {}
    for fused in (False, True):
        mcmc = MCMC(
            HMC(normal_model, max_num_steps=16), num_warmup=150,
            num_samples=150, num_chains=2, fused=fused,
        )
        mcmc.run(jax.random.PRNGKey(3), DATA)
        post[fused] = mcmc.get_samples()["loc"]
    for fused, draws in post.items():
        assert float(draws.mean()) == pytest.approx(POST_MEAN, abs=0.2), fused
        assert float(draws.std()) == pytest.approx(POST_SD, abs=0.15), fused


def test_init_params_broadcast_and_potential_fn():
    mcmc = MCMC(small_hmc(), num_warmup=40, num_samples=30, num_chains=2)
    s = mcmc.run(jax.random.PRNGKey(0), DATA, init_params={"loc": jnp.asarray(0.5)})
    assert s["loc"].shape == (60,)

    def pe(z):
        return 0.5 * jnp.sum(jnp.square(z["x"]))

    kernel = HMC(potential_fn=pe, max_num_steps=16)
    mcmc = MCMC(kernel, num_warmup=40, num_samples=60, num_chains=2)
    with pytest.raises(ValueError):
        mcmc.run(jax.random.PRNGKey(1))
    s = mcmc.run(jax.random.PRNGKey(1), init_params={"x": jnp.zeros(2)})
    assert s["x"].shape == (120, 2)


def test_multichain_posterior_and_diagnostics():
    mcmc = MCMC(
        NUTS(normal_model, max_tree_depth=5),
        num_warmup=150, num_samples=150, num_chains=4,
    )
    mcmc.run(jax.random.PRNGKey(7), DATA)
    g = mcmc.get_samples(group_by_chain=True)["loc"]
    assert float(g.mean()) == pytest.approx(POST_MEAN, abs=0.15)
    assert float(g.std()) == pytest.approx(POST_SD, abs=0.15)
    assert float(split_rhat(g)) < 1.1
    assert float(effective_sample_size(g)) > 50
    stats = mcmc.summary(print_table=False)
    assert set(stats) == {"loc"}
    assert {"mean", "std", "n_eff", "ess_tail", "r_hat"} <= set(stats["loc"])


def test_predictive_chain_shaped_fanout():
    post = {"loc": jnp.zeros((2, 5))}
    out = Predictive(normal_model, posterior_samples=post, batch_ndims=2)(
        jax.random.PRNGKey(8), DATA
    )
    assert out["obs"].shape == (2, 5, len(DATA))
    # flat draws keep working unchanged
    out1 = Predictive(normal_model, posterior_samples={"loc": jnp.zeros(7)})(
        jax.random.PRNGKey(9), DATA
    )
    assert out1["obs"].shape == (7, len(DATA))
