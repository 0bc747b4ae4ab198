"""Systematic-resampling kernel conformance (ISSUE 10).

`ops.resample` dispatches the sorted-uniform/cumsum counting kernel
(`kernels/resample.py`) against the pure-jnp searchsorted oracle
(`kernels/ref.systematic_resample_ref`): the interpret backend (Pallas body
on CPU) must be bit-identical to the reference backend at every size, the
semantics must be the textbook systematic resampler (sorted ancestors, grid
(u0+i)/N against the weight cumsum), and the documented edge cases — equal
weights, one surviving particle, all-(-inf) log-weights — must hit their
specified outputs exactly. The custom VJP is pinned to zero (ancestor
selection is piecewise constant — the standard VSMC stop-gradient).

The counting kernel is O(N^2) under the interpret backend (the whole grid
runs unrolled on CPU), so interpret-backend rows stay at N <= 4096; the
reference backend carries the large-N conformance in tests/test_smc.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels import ref


@pytest.fixture(params=["interpret", "reference"])
def backend(request, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", request.param)
    return request.param


def random_log_weights(n, seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0.0, scale, (n,)).astype(np.float32))


# ---------------------------------------------------------------------------
# backend parity + oracle equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 3, 64, 257, 1000, 4096])
def test_interpret_matches_reference_bit_identical(n, monkeypatch):
    lw = random_log_weights(n, seed=n)
    u0 = 0.37
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")
    a_ref = ops.resample(lw, u0)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    a_int = ops.resample(lw, u0)
    assert a_ref.dtype == a_int.dtype == jnp.int32
    assert jnp.array_equal(a_ref, a_int)


@pytest.mark.parametrize("n", [2, 17, 512])
def test_matches_pure_oracle(n, backend):
    lw = random_log_weights(n, seed=n + 1)
    u0 = 0.61
    ancestors = ops.resample(lw, u0)
    oracle = ref.systematic_resample_ref(lw, jnp.float32(u0))
    assert jnp.array_equal(ancestors, oracle)


def test_ancestors_sorted_and_in_range(backend):
    lw = random_log_weights(513, seed=7)
    a = np.asarray(ops.resample(lw, 0.25))
    assert (np.diff(a) >= 0).all()  # systematic ancestors are sorted
    assert a.min() >= 0 and a.max() < 513


def test_counts_match_weights_statistically(backend):
    """Offspring counts of the systematic resampler are within 1 of N*w_i
    (the defining low-variance property: floor(Nw) <= count <= ceil(Nw))."""
    n = 256
    lw = random_log_weights(n, seed=3, scale=1.5)
    w = np.asarray(jax.nn.softmax(lw))
    a = np.asarray(ops.resample(lw, 0.5))
    counts = np.bincount(a, minlength=n)
    assert (counts >= np.floor(n * w)).all()
    assert (counts <= np.ceil(n * w)).all()


# ---------------------------------------------------------------------------
# specified edge cases
# ---------------------------------------------------------------------------


def test_equal_weights_identity(backend):
    """Equal weights: every particle gets exactly one offspring — the
    systematic resampler is the identity permutation."""
    n = 512
    lw = jnp.zeros(n)
    a = ops.resample(lw, 0.5)
    assert jnp.array_equal(a, jnp.arange(n, dtype=jnp.int32))


def test_one_surviving_particle(backend):
    """One particle with all the mass: every ancestor is that index."""
    lw = jnp.full(64, -jnp.inf).at[7].set(0.0)
    a = ops.resample(lw, 0.123)
    assert jnp.array_equal(a, jnp.full(64, 7, dtype=jnp.int32))


def test_all_neg_inf_falls_back_to_uniform(backend):
    """Degenerate -inf weights (a dead population) fall back to uniform
    weights rather than NaN: the identity permutation comes back."""
    n = 32
    lw = jnp.full(n, -jnp.inf)
    a = ops.resample(lw, 0.5)
    assert jnp.array_equal(a, jnp.arange(n, dtype=jnp.int32))


def test_zero_weight_particles_never_selected(backend):
    n = 128
    lw = random_log_weights(n, seed=9)
    dead = [0, 5, 77, 127]
    lw = lw.at[jnp.asarray(dead)].set(-jnp.inf)
    a = np.asarray(ops.resample(lw, 0.5))
    assert not np.isin(a, dead).any()


def test_u0_endpoints(backend):
    """u0 in [0, 1): both endpoints produce valid indices (u0=0 puts the
    first grid point at exactly 0; the count is clipped into range)."""
    lw = random_log_weights(100, seed=11)
    for u0 in (0.0, 0.999999):
        a = np.asarray(ops.resample(lw, u0))
        assert a.min() >= 0 and a.max() < 100


# ---------------------------------------------------------------------------
# gradient + validation contracts
# ---------------------------------------------------------------------------


def test_custom_vjp_zero_gradient(backend):
    """Ancestor selection is piecewise constant in the weights: the custom
    VJP returns exactly zero, so VSMC losses get the standard biased
    stop-gradient-through-ancestry estimator instead of a trace error."""
    lw = random_log_weights(32, seed=13)

    def loss(lw):
        a = ops.resample(lw, 0.5)
        return jnp.sum(a.astype(jnp.float32)) + jnp.sum(lw)

    g = jax.grad(loss)(lw)
    assert jnp.array_equal(g, jnp.ones_like(lw))  # only the direct term


def test_validates_rank_and_size(backend):
    with pytest.raises(ValueError):
        ops.resample(jnp.zeros((4, 4)), 0.5)
    with pytest.raises(ValueError):
        ops.resample(jnp.zeros((0,)), 0.5)


def test_jit_and_vmap_compatible(backend):
    lw = random_log_weights(64, seed=17)
    direct = ops.resample(lw, 0.5)
    jitted = jax.jit(lambda w: ops.resample(w, 0.5))(lw)
    assert jnp.array_equal(direct, jitted)
    batch = jnp.stack([lw, lw + 1.0])  # +const leaves normalized weights alone
    vmapped = jax.vmap(lambda w: ops.resample(w, 0.5))(batch)
    assert jnp.array_equal(vmapped[0], direct)
    assert jnp.array_equal(vmapped[1], direct)


@pytest.mark.parametrize("backend", ["interpret", "reference"])
def test_on_a_mesh_matches_unsharded(backend):
    """Each device counts its slice of the grid against the whole cumsum;
    on one device that is the unsharded call, bit for bit."""
    from repro.distributed import make_mesh

    lw = 2.0 * jax.random.normal(jax.random.PRNGKey(7), (512,))
    mesh = make_mesh((1,), ("data",))
    local = ops.resample(lw, 0.3, backend=backend)
    sharded = ops.resample(lw, 0.3, backend=backend, mesh=mesh)
    assert jnp.array_equal(local, sharded)
