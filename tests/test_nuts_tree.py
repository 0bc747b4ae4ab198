"""The fused NUTS tree: the U-turn check inside a subtree runs over its
balanced sub-trees, so its verdict does not depend on the direction the
subtree was built in, and the transition leaves its target invariant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.infer import NUTS
from repro.infer.mcmc import _subtree_leaf


def _turns(z_a, r_a, z_b, r_b):
    """The endpoint U-turn criterion of a trajectory piece from a to b, in
    trajectory order, unit mass."""
    dz = z_b - z_a
    return (np.sum(dz * r_a, -1) < 0) | (np.sum(dz * r_b, -1) < 0)


def _build(z, r, direction):
    """Feed leaves (n, C, D) to `_subtree_leaf` in order; whether any leaf
    closed a sub-tree that turns."""
    n, C, D = z.shape
    z_ck = r_ck = jnp.zeros((max(n.bit_length() - 1, 1), C, D))
    dirs = jnp.full((C,), direction, jnp.float32)
    turned = np.zeros(C, bool)
    for t in range(n):
        z_ck, r_ck, turn = _subtree_leaf(z_ck, r_ck, z[t], r[t], jnp.int32(t), dirs,
                                         jnp.ones((C, D)))
        turned |= np.asarray(turn)
    return turned


@pytest.mark.parametrize("depth", [1, 2, 3, 4])
def test_subtree_uturn_verdict_is_the_same_built_either_way(depth):
    n, C, D = 2 ** depth, 512, 3
    rng = np.random.default_rng(depth)
    # momenta that drift, so some trajectories turn and some do not
    r = rng.standard_normal((1, C, D)) + 0.6 * rng.standard_normal((n, C, D)).cumsum(0)
    z = r.cumsum(0) + rng.standard_normal((1, C, D))
    r, z = r.astype(np.float32), z.astype(np.float32)
    forward = _build(jnp.asarray(z), jnp.asarray(r), 1.0)
    backward = _build(jnp.asarray(z[::-1]), jnp.asarray(r[::-1]), -1.0)
    np.testing.assert_array_equal(forward, backward)
    # brute force: every aligned block of 2^m leaves, m >= 1, end to end
    brute = np.zeros(C, bool)
    for m in range(1, depth + 1):
        for a in range(0, n, 2 ** m):
            b = a + 2 ** m - 1
            brute |= _turns(z[a], r[a], z[b], r[b])
    np.testing.assert_array_equal(forward, brute)
    assert 0 < forward.sum() < C  # both verdicts occur


def test_balanced_check_where_the_prefix_check_disagrees():
    # four leaves in the plane, unit mass: only the last pair (2, 3) turns
    z = np.asarray([[0, 0], [1, 0], [2, 0], [2, 1]], np.float32)[:, None]
    r = np.asarray([[1, 0], [1, 0], [1, 1], [1, -1]], np.float32)[:, None]
    # the prefix rule checks each leaf against the first one built: it
    # finds no turn built forward, and one built backward from leaf 3
    assert not any(_turns(z[0], r[0], z[t], r[t])[0] for t in (1, 2, 3))
    assert _turns(z[2], r[2], z[3], r[3])[0]
    # the balanced rule checks (0, 1), (2, 3) and (0, 3) either way
    assert _build(jnp.asarray(z), jnp.asarray(r), 1.0)[0]
    assert _build(jnp.asarray(z[::-1]), jnp.asarray(r[::-1]), -1.0)[0]


def test_fused_nuts_keeps_a_correlated_gaussian_stationary():
    """Chains started from exact draws, no warmup, a fixed step size: every
    draw is a draw of the target, so the pooled chain means of x and x^2 are
    within a few standard errors of the truth. The first-leaf (prefix)
    subtree check this replaced read z of 8-11 on the variances here."""
    C, D, N = 4096, 2, 20
    scale = np.asarray([0.5, 2.0])
    cov = np.asarray([[1.0, 0.99], [0.99, 1.0]]) * np.outer(scale, scale)
    prec = jnp.asarray(np.linalg.inv(cov), jnp.float32)

    def pe(z):
        return 0.5 * z @ prec @ z

    kernel = NUTS(potential_fn=pe, step_size=0.05, max_tree_depth=8)
    rng = np.random.default_rng(0)
    z0 = jnp.asarray(rng.standard_normal((C, D)) @ np.linalg.cholesky(cov).T, jnp.float32)
    state = kernel.fused_init_state(jax.random.PRNGKey(0), z0, jax.vmap(pe)(z0))

    @jax.jit
    def draws(state):
        def body(s, _):
            s = kernel.fused_sample_step(s, pe, 0, backend="reference")
            return s, s.z

        return jax.lax.scan(body, state, None, length=N)[1]

    x = np.asarray(draws(state), np.float64)  # (N, C, D)
    for f, want in ((x, np.zeros(D)), (x * x, np.diag(cov))):
        chain_means = f.mean(axis=0)  # (C, D): independent across chains
        se = chain_means.std(axis=0, ddof=1) / np.sqrt(C)
        z = (chain_means.mean(axis=0) - want) / se
        assert np.all(np.abs(z) < 5), z
