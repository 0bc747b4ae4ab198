"""Enumeration subsystem tests (ISSUE 3 acceptance list).

Hand-computed 2-component GMM marginal likelihood == TraceEnum_ELBO loss;
infer_discrete recovers the exact posterior over assignments; one compiled
trace across SVI steps (retrace counter == 1); mesh-sharded particles
bit-identical to the unsharded path; plate-aware contraction on global
latents, nested plates, and Markov chains vs brute force.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import distributions as dist
from repro import optim
from repro.core import handlers
from repro.core import primitives as P
from repro.distributed import make_mesh
from repro.infer import (
    SVI,
    AutoNormal,
    Trace_ELBO,
    TraceEnum_ELBO,
    config_enumerate,
    discrete_marginals,
    infer_discrete,
)

DATA = jnp.asarray([-1.2, -0.8, 1.9, 2.2, 2.0])
WEIGHTS = jnp.asarray([0.4, 0.6])
LOCS = jnp.asarray([-1.0, 2.0])
SCALE = 0.5


def gmm(data):
    with P.plate("N", data.shape[0]):
        z = P.sample("z", dist.Categorical(WEIGHTS), infer={"enumerate": "parallel"})
        P.sample("obs", dist.Normal(LOCS[z], SCALE), obs=data)


def empty_guide(data):
    pass


def _component_logprobs(data):
    """(N, K) log p(z=k) + log p(x_n | z=k) — the hand-computed joint."""
    return dist.Normal(LOCS, SCALE).log_prob(data[:, None]) + jnp.log(WEIGHTS)


# ---------------------------------------------------------------------------
# hand-computed marginal likelihood == TraceEnum_ELBO loss
# ---------------------------------------------------------------------------


def test_gmm_loss_matches_hand_marginal():
    loss = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, gmm, empty_guide, DATA)
    hand = -jnp.sum(jax.scipy.special.logsumexp(_component_logprobs(DATA), -1))
    assert abs(float(loss) - float(hand)) < 1e-5


def test_gmm_loss_matches_hand_marginalized_trace_elbo():
    """Enumeration == marginalizing by hand with MixtureSameFamily."""

    def marginalized(data):
        with P.plate("N", data.shape[0]):
            P.sample(
                "obs",
                dist.MixtureSameFamily(
                    dist.Categorical(WEIGHTS), dist.Normal(LOCS, SCALE)
                ),
                obs=data,
            )

    enum_loss = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, gmm, empty_guide, DATA)
    hand_loss = Trace_ELBO().loss(
        jax.random.PRNGKey(0), {}, marginalized, empty_guide, DATA
    )
    assert abs(float(enum_loss) - float(hand_loss)) < 1e-5


def test_traceenum_equals_trace_elbo_without_enumeration():
    def plain(data):
        loc = P.sample("loc", dist.Normal(0.0, 10.0))
        with P.plate("N", data.shape[0]):
            P.sample("obs", dist.Normal(loc, 1.0), obs=data)

    guide = AutoNormal(plain)
    svi = SVI(plain, guide, optim.Adam(0.01), Trace_ELBO())
    state = svi.init(jax.random.PRNGKey(0), DATA)
    params = svi.optim.get_params(state.optim_state)
    l1 = Trace_ELBO().loss(jax.random.PRNGKey(7), params, plain, guide, DATA)
    l2 = TraceEnum_ELBO().loss(jax.random.PRNGKey(7), params, plain, guide, DATA)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_global_latent_shared_across_plate():
    """Sum over the global latent must happen OUTSIDE the plate product."""

    def model(data):
        c = P.sample("c", dist.Bernoulli(0.3), infer={"enumerate": "parallel"})
        loc = jnp.where(c > 0, 2.0, -1.0)
        with P.plate("N", data.shape[0]):
            P.sample("obs", dist.Normal(loc, 1.0), obs=data)

    loss = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, model, empty_guide, DATA)
    lp0 = jnp.sum(dist.Normal(-1.0, 1.0).log_prob(DATA)) + jnp.log(0.7)
    lp1 = jnp.sum(dist.Normal(2.0, 1.0).log_prob(DATA)) + jnp.log(0.3)
    assert abs(float(loss) + float(jnp.logaddexp(lp0, lp1))) < 1e-5


def test_nested_plates_and_per_row_mixture():
    rng = np.random.default_rng(3)
    dat = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    w, locs = jnp.asarray([0.3, 0.7]), jnp.asarray([-1.0, 1.0])

    @config_enumerate
    def rowmix(dat):
        with P.plate("rows", 3, dim=-2):
            c = P.sample("c", dist.Categorical(w))
            with P.plate("cols", 4, dim=-1):
                P.sample("x", dist.Normal(locs[c], 1.0), obs=dat)

    loss = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, rowmix, lambda dat: None, dat)
    row_lp = jnp.sum(dist.Normal(locs, 1.0).log_prob(dat[..., None]), axis=1)
    hand = -jnp.sum(jax.scipy.special.logsumexp(row_lp + jnp.log(w), -1))
    assert abs(float(loss) - float(hand)) < 1e-5


def test_markov_chain_matches_brute_force():
    T, K = 4, 3
    rng = np.random.default_rng(0)
    trans = jnp.asarray(rng.dirichlet(np.ones(K), size=K))
    init_p = jnp.asarray(rng.dirichlet(np.ones(K)))
    locs = jnp.asarray([-2.0, 0.0, 2.0])
    obs = jnp.asarray([-1.8, 0.2, 1.9, 2.1])

    @config_enumerate
    def hmm(obs):
        z = P.sample("z_0", dist.Categorical(init_p))
        P.sample("x_0", dist.Normal(locs[z], 1.0), obs=obs[0])
        for t in range(1, T):
            z = P.sample(f"z_{t}", dist.Categorical(trans[z]))
            P.sample(f"x_{t}", dist.Normal(locs[z], 1.0), obs=obs[t])

    loss = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, hmm, lambda obs: None, obs)
    total = -jnp.inf
    best, best_lp = None, -np.inf
    for zs in itertools.product(range(K), repeat=T):
        lp = jnp.log(init_p[zs[0]]) + dist.Normal(locs[zs[0]], 1.0).log_prob(obs[0])
        for t in range(1, T):
            lp = lp + jnp.log(trans[zs[t - 1], zs[t]])
            lp = lp + dist.Normal(locs[zs[t]], 1.0).log_prob(obs[t])
        total = jnp.logaddexp(total, lp)
        if float(lp) > best_lp:
            best, best_lp = list(zs), float(lp)
    assert abs(float(loss) + float(total)) < 1e-4

    # MAP decoding == brute-force Viterbi
    dec = infer_discrete(hmm, temperature=0, rng_key=jax.random.PRNGKey(2))
    tr = handlers.trace(handlers.seed(dec, jax.random.PRNGKey(3))).get_trace(obs)
    assert [int(tr[f"z_{t}"]["value"]) for t in range(T)] == best


# ---------------------------------------------------------------------------
# infer_discrete: exact posterior over assignments
# ---------------------------------------------------------------------------


def test_discrete_marginals_exact():
    margs = discrete_marginals(gmm, jax.random.PRNGKey(1), DATA)
    hand = jax.nn.log_softmax(_component_logprobs(DATA), -1)
    np.testing.assert_allclose(np.asarray(margs["z"]), np.asarray(hand), atol=1e-6)


def test_marginals_with_global_local_coupling():
    """Marginal of a plate-local site must weight the global latent by the
    evidence from ALL slices (dice-factor gradient identity), not just its
    own slice."""
    n = 4
    data = jnp.asarray([-1.5, 0.3, 1.8, -0.2])
    pc = jnp.asarray([0.35, 0.65])
    pz_c = jnp.asarray([[0.8, 0.2], [0.3, 0.7]])
    locs = jnp.asarray([-1.0, 1.5])

    @config_enumerate
    def model(data):
        c = P.sample("c", dist.Categorical(pc))
        with P.plate("N", n):
            z = P.sample("z", dist.Categorical(pz_c[c]))
            P.sample("x", dist.Normal(locs[z], 1.0), obs=data)

    m = discrete_marginals(model, jax.random.PRNGKey(0), data)
    hand_c = jnp.asarray(
        [
            jnp.log(pc[c])
            + jnp.sum(
                jax.scipy.special.logsumexp(
                    jnp.log(pz_c[c]) + dist.Normal(locs, 1.0).log_prob(data[:, None]),
                    -1,
                )
            )
            for c in range(2)
        ]
    )
    hand_c = jax.nn.log_softmax(hand_c)
    np.testing.assert_allclose(np.asarray(m["c"]), np.asarray(hand_c), atol=1e-6)
    hand_z = sum(
        jnp.exp(hand_c[c])
        * jax.nn.softmax(
            jnp.log(pz_c[c]) + dist.Normal(locs, 1.0).log_prob(data[:, None]), -1
        )
        for c in range(2)
    )
    np.testing.assert_allclose(np.exp(np.asarray(m["z"])), np.asarray(hand_z), atol=1e-6)


def test_infer_discrete_map_assignments():
    dec = infer_discrete(gmm, temperature=0, rng_key=jax.random.PRNGKey(2))
    tr = handlers.trace(handlers.seed(dec, jax.random.PRNGKey(3))).get_trace(DATA)
    expected = jnp.argmax(_component_logprobs(DATA), -1)
    np.testing.assert_array_equal(np.asarray(tr["z"]["value"]), np.asarray(expected))


def test_infer_discrete_sampling_frequencies():
    """temperature=1 draws from the exact posterior: empirical assignment
    frequencies converge to the hand posterior (vmapped over keys)."""

    def draw(key):
        dec = infer_discrete(gmm, temperature=1, rng_key=key)
        tr = handlers.trace(handlers.seed(dec, jax.random.PRNGKey(0))).get_trace(DATA)
        return tr["z"]["value"]

    zs = jax.vmap(draw)(jax.random.split(jax.random.PRNGKey(4), 2000))
    freq1 = np.asarray((zs == 1).mean(0))
    post1 = np.exp(np.asarray(jax.nn.log_softmax(_component_logprobs(DATA), -1))[:, 1])
    np.testing.assert_allclose(freq1, post1, atol=0.05)


# ---------------------------------------------------------------------------
# jit stability + sharding
# ---------------------------------------------------------------------------


def gmm_learnable(data):
    w = P.param("w", jnp.asarray([0.5, 0.5]), constraint=dist.constraints.simplex)
    locs = P.param("locs", jnp.asarray([-0.5, 0.5]))
    with P.plate("N", data.shape[0]):
        z = P.sample("z", dist.Categorical(w), infer={"enumerate": "parallel"})
        P.sample("obs", dist.Normal(locs[z], SCALE), obs=data)


def test_compiles_exactly_once_across_steps():
    elbo = TraceEnum_ELBO()
    svi = SVI(gmm_learnable, empty_guide, optim.Adam(0.05), elbo)
    state = svi.init(jax.random.PRNGKey(0), DATA)
    elbo.num_traces = 0
    for i in range(12):
        # fresh same-shape data each step must reuse the compiled executable
        state, loss = svi.update_jit(state, DATA + 0.01 * i)
    assert elbo.num_traces == 1
    assert np.isfinite(float(loss))


def test_sharded_particles_bit_identical():
    mesh = make_mesh((1, 1), ("data", "model"))
    guide = AutoNormal(latent_gmm)

    def run(elbo, svi_mesh):
        svi = SVI(latent_gmm, guide, optim.Adam(0.05), elbo, mesh=svi_mesh)
        state = svi.init(jax.random.PRNGKey(0), DATA)
        for _ in range(5):
            state, loss = svi.update_jit(state, DATA)
        return float(loss)

    loss_plain = run(TraceEnum_ELBO(num_particles=4), None)
    loss_shard = run(
        TraceEnum_ELBO(num_particles=4, mesh=mesh, particle_axis="data"), mesh
    )
    assert loss_plain == loss_shard  # bit-identical on a 1-device mesh


def latent_gmm(data):
    locs = P.sample("locs", dist.Normal(0.0, 5.0).expand((2,)).to_event(1))
    with P.plate("N", data.shape[0]):
        z = P.sample("z", dist.Categorical(WEIGHTS), infer={"enumerate": "parallel"})
        P.sample("obs", dist.Normal(locs[z], SCALE), obs=data)


def test_svi_with_autoguide_learns_gmm():
    """AutoNormal skips enumerated sites; TraceEnum_ELBO marginalizes them."""
    rng = np.random.default_rng(1)
    data = jnp.concatenate(
        [
            jnp.asarray(rng.normal(-1.0, 0.5, 30), jnp.float32),
            jnp.asarray(rng.normal(2.0, 0.5, 60), jnp.float32),
        ]
    )
    guide = AutoNormal(latent_gmm)
    svi = SVI(latent_gmm, guide, optim.Adam(0.05), TraceEnum_ELBO(num_particles=2))
    state = svi.init(jax.random.PRNGKey(0), data)
    first = None
    for i in range(150):
        state, loss = svi.update_jit(state, data)
        if first is None:
            first = float(loss)
    assert float(loss) < first
    locs = sorted(np.asarray(svi.get_params(state)["auto_locs_loc"]).tolist())
    assert abs(locs[0] - (-1.0)) < 0.4 and abs(locs[1] - 2.0) < 0.4


# ---------------------------------------------------------------------------
# messenger mechanics + error paths
# ---------------------------------------------------------------------------


def test_enum_messenger_allocates_dims_left_of_plates():
    with handlers.enum(first_available_dim=-2):
        tr = handlers.trace(handlers.seed(gmm, jax.random.PRNGKey(0))).get_trace(DATA)
    site = tr["z"]
    assert site["infer"]["_enumerate_dim"] == -2
    assert site["infer"]["_enumerate_cardinality"] == 2
    assert site["value"].shape == (2, 1)  # enum dim left of the plate dim
    assert tr["obs"]["fn"].log_prob(tr["obs"]["value"]).shape == (2, 5)


def test_config_enumerate_annotates_discrete_only():
    def model():
        P.sample("z", dist.Bernoulli(0.5))
        P.sample("x", dist.Normal(0.0, 1.0))
        P.sample("y", dist.Bernoulli(0.5), infer={"enumerate": "sequential"})

    tr = handlers.trace(
        handlers.seed(config_enumerate(model), jax.random.PRNGKey(0))
    ).get_trace()
    assert tr["z"]["infer"]["enumerate"] == "parallel"
    assert "enumerate" not in tr["x"]["infer"]
    assert tr["y"]["infer"]["enumerate"] == "sequential"  # explicit wins


def test_infinite_support_raises_actionable_error():
    def model(data):
        P.sample("g", dist.Geometric(0.5), infer={"enumerate": "parallel"})

    with pytest.raises(NotImplementedError, match="truncate"):
        TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, model, empty_guide, DATA)


def test_guide_side_enumeration_raises():
    def model(data):
        P.sample("z", dist.Bernoulli(0.5), infer={"enumerate": "parallel"})

    def guide(data):
        P.sample("z", dist.Bernoulli(0.5), infer={"enumerate": "parallel"})

    with pytest.raises(NotImplementedError, match="guide"):
        TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, model, guide, DATA)


def test_sequential_strategy_raises():
    def model(data):
        P.sample("z", dist.Bernoulli(0.5), infer={"enumerate": "sequential"})

    with pytest.raises(NotImplementedError, match="parallel"):
        TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, model, empty_guide, DATA)


def test_subsample_scale_outside_enum_logsumexp():
    """Minibatch scale must multiply the marginalized per-slice density:
    s*logsumexp(lp), never logsumexp(s*lp)."""
    data = jnp.asarray([-0.4, 0.1, 0.5, -0.2, 0.3, 0.0, -0.1, 0.6])
    w, locs = jnp.asarray([0.4, 0.6]), jnp.asarray([-0.5, 0.5])

    def gmm_sub(data):
        with P.plate("N", 8, subsample_size=4) as idx:
            z = P.sample("z", dist.Categorical(w), infer={"enumerate": "parallel"})
            P.sample("obs", dist.Normal(locs[z], 1.0), obs=data[idx])

    model = handlers.substitute(gmm_sub, data={"N": jnp.arange(4)})
    loss = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, model, empty_guide, data)
    comp = dist.Normal(locs, 1.0).log_prob(data[:4, None]) + jnp.log(w)
    correct = -2.0 * jnp.sum(jax.scipy.special.logsumexp(comp, -1))
    wrong = -jnp.sum(jax.scipy.special.logsumexp(2.0 * comp, -1))
    assert abs(float(correct) - float(wrong)) > 0.1  # forms genuinely differ here
    assert abs(float(loss) - float(correct)) < 1e-5


def test_masked_enumerated_site_contributes_zero():
    """A masked-out enumerated site is neutral (0), not +log K."""

    def fully_masked(data):
        with handlers.mask(mask=False):
            P.sample("z", dist.Bernoulli(0.5), infer={"enumerate": "parallel"})

    loss = TraceEnum_ELBO().loss(
        jax.random.PRNGKey(0), {}, fully_masked, empty_guide, DATA
    )
    assert abs(float(loss)) < 1e-7

    def masked_obs(data):
        with P.plate("N", 4):
            z = P.sample("z", dist.Categorical(WEIGHTS), infer={"enumerate": "parallel"})
            with handlers.mask(mask=False):
                P.sample("obs", dist.Normal(LOCS[z], 0.5), obs=data[:4])

    loss = TraceEnum_ELBO().loss(
        jax.random.PRNGKey(0), {}, masked_obs, empty_guide, DATA
    )
    assert abs(float(loss)) < 1e-6  # z marginalizes to exactly 1


def test_masked_distribution_wrapper_is_neutral_too():
    """.mask() on an enumerated site must behave like handlers.mask: a
    masked-out slice contributes 0, not +log K."""
    m = jnp.asarray([True, False, True, True])

    locs = jnp.asarray([-1.0, 2.0])

    def model(data):
        with P.plate("N", 4):
            z = P.sample(
                "z", dist.Bernoulli(0.4).mask(m), infer={"enumerate": "parallel"}
            )
            loc = locs[jnp.asarray(z, jnp.int32)]
            P.sample("obs", dist.Normal(loc, 0.5).mask(m), obs=data[:4])

    def via_handler(data):
        with P.plate("N", 4):
            with handlers.mask(mask=m):
                z = P.sample("z", dist.Bernoulli(0.4), infer={"enumerate": "parallel"})
                loc = locs[jnp.asarray(z, jnp.int32)]
                P.sample("obs", dist.Normal(loc, 0.5), obs=data[:4])

    l1 = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, model, empty_guide, DATA)
    l2 = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, via_handler, empty_guide, DATA)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_plain_elbos_reject_unconsumed_enumerate_annotation():
    """An enumerate-annotated model latent absent from the guide must fail
    loudly under Trace_ELBO-family estimators instead of silently training a
    wrong (prior-sampled) objective."""

    def model(data):
        P.sample("z", dist.Bernoulli(0.5), infer={"enumerate": "parallel"})

    with pytest.raises(ValueError, match="TraceEnum_ELBO"):
        Trace_ELBO().loss(jax.random.PRNGKey(0), {}, model, empty_guide, DATA)


def test_infer_discrete_fresh_draws_from_ambient_seed():
    """Without an explicit rng_key, the decode keys off the enclosing seed
    handler — different seeds give different posterior draws."""

    def gmm_wide(data):
        with P.plate("N", data.shape[0]):
            z = P.sample("z", dist.Categorical(WEIGHTS), infer={"enumerate": "parallel"})
            P.sample("obs", dist.Normal(LOCS[z], 1.5), obs=data)

    dec = infer_discrete(gmm_wide, temperature=1)
    draws = set()
    for s in range(8):
        tr = handlers.trace(handlers.seed(dec, jax.random.PRNGKey(s))).get_trace(
            jnp.zeros(5)
        )
        draws.add(tuple(int(v) for v in tr["z"]["value"]))
    assert len(draws) > 1


def test_shared_infer_dict_across_sites():
    """A single infer= dict reused by several sites must not alias the
    per-site enum dim bookkeeping (make_message copies it)."""
    cfg = {"enumerate": "parallel"}

    def shared():
        a = P.sample("a", dist.Bernoulli(0.3), infer=cfg)
        b = P.sample("b", dist.Bernoulli(0.9), infer=cfg)
        P.sample("obs", dist.Normal(a + 2 * b, 0.5), obs=2.0)

    def literal():
        a = P.sample("a", dist.Bernoulli(0.3), infer={"enumerate": "parallel"})
        b = P.sample("b", dist.Bernoulli(0.9), infer={"enumerate": "parallel"})
        P.sample("obs", dist.Normal(a + 2 * b, 0.5), obs=2.0)

    l1 = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, shared, lambda: None)
    l2 = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, literal, lambda: None)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-7)
    assert "enumerate" in cfg and "_enumerate_dim" not in cfg


def test_infer_discrete_pins_free_continuous_latents():
    """The replayed execution must be one coherent joint draw: discrete
    decodes are conditioned on the SAME continuous values the caller sees."""

    def model():
        mu = P.sample("mu", dist.Normal(0.0, 10.0))
        z = P.sample(
            "z", dist.Bernoulli(jax.nn.sigmoid(mu)), infer={"enumerate": "parallel"}
        )
        P.sample("obs", dist.Normal(z * mu, 0.1), obs=0.0)

    dec = infer_discrete(model, temperature=0, rng_key=jax.random.PRNGKey(0))
    tr = handlers.trace(handlers.seed(dec, jax.random.PRNGKey(7))).get_trace()
    mu, z = float(tr["mu"]["value"]), float(tr["z"]["value"])
    lp1 = float(jax.nn.log_sigmoid(jnp.asarray(mu)) + dist.Normal(mu, 0.1).log_prob(0.0))
    lp0 = float(jax.nn.log_sigmoid(jnp.asarray(-mu)) + dist.Normal(0.0, 0.1).log_prob(0.0))
    assert z == float(lp1 > lp0)  # MAP given the RETURNED mu, not a stale draw


def test_binomial_enumeration():
    """Binomial's finite support enumerates: marginal over {0..3} by hand."""
    p_z, p_obs = 0.3, jnp.asarray([0.1, 0.3, 0.6, 0.9])

    def model():
        z = P.sample("z", dist.Binomial(3, probs=p_z), infer={"enumerate": "parallel"})
        P.sample("obs", dist.Bernoulli(p_obs[jnp.asarray(z, jnp.int32)]), obs=1.0)

    loss = TraceEnum_ELBO().loss(jax.random.PRNGKey(0), {}, model, lambda: None)
    zs = jnp.arange(4.0)
    hand = jax.scipy.special.logsumexp(
        dist.Binomial(3, probs=p_z).log_prob(zs) + jnp.log(p_obs)
    )
    assert abs(float(loss) + float(hand)) < 1e-6
