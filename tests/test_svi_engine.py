"""Sharded multi-particle SVI engine + kernel backend dispatch (PR 1).

Covers the ISSUE acceptance list: sharded vs single-device ELBO bit-for-bit
on a 1-device mesh; plate subsampling rescaling under the jitted update with
indices in the pure signature (no per-step retracing); kernel dispatch
falling back to the reference backend on CPU; and the unified particle path
(RenyiELBO num_particles == 1 guard)."""
import jax
import jax.numpy as jnp
import pytest

from repro import distributions as dist
from repro import optim
from repro.core import primitives as P
from repro.distributed import make_mesh
from repro.infer import (
    SVI,
    AutoNormal,
    RenyiELBO,
    Trace_ELBO,
    TraceGraph_ELBO,
    TraceMeanField_ELBO,
)
from repro.kernels import ops
from repro.kernels.ref import categorical_logprob_ref, flash_attention_ref

DATA = jnp.asarray([1.0, 2.0, 3.0, 2.5, 1.5])


def normal_model(data):
    loc = P.sample("loc", dist.Normal(0.0, 10.0))
    with P.plate("N", data.shape[0]):
        P.sample("obs", dist.Normal(loc, 1.0), obs=data)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def trained_params():
    guide = AutoNormal(normal_model)
    svi = SVI(normal_model, guide, optim.Adam(0.05), Trace_ELBO())
    state, _ = svi.run(jax.random.PRNGKey(0), 100, DATA)
    return guide, svi.optim.get_params(state.optim_state)


# -- sharded particle path ---------------------------------------------------


@pytest.mark.parametrize("Loss", [Trace_ELBO, TraceMeanField_ELBO, TraceGraph_ELBO])
def test_sharded_elbo_bitwise_equals_local_on_1device_mesh(mesh, trained_params, Loss):
    guide, params = trained_params
    key = jax.random.PRNGKey(42)
    local = Loss(num_particles=8).loss(key, params, normal_model, guide, DATA)
    sharded = Loss(num_particles=8, mesh=mesh).loss(key, params, normal_model, guide, DATA)
    assert float(local) == float(sharded)  # bit-for-bit


def test_sharded_renyi_bitwise_equals_local(mesh, trained_params):
    guide, params = trained_params
    key = jax.random.PRNGKey(43)
    local = RenyiELBO(num_particles=8).loss(key, params, normal_model, guide, DATA)
    sharded = RenyiELBO(num_particles=8, mesh=mesh).loss(
        key, params, normal_model, guide, DATA
    )
    assert float(local) == float(sharded)


def test_indivisible_particle_count_still_correct(mesh, trained_params):
    """Particle counts that don't divide the mesh axis replicate instead of
    failing, and the value is unchanged."""
    guide, params = trained_params
    key = jax.random.PRNGKey(44)
    local = Trace_ELBO(num_particles=3).loss(key, params, normal_model, guide, DATA)
    sharded = Trace_ELBO(num_particles=3, mesh=mesh).loss(
        key, params, normal_model, guide, DATA
    )
    assert float(local) == float(sharded)


def test_renyi_single_particle_unified_guard(trained_params):
    """num_particles == 1 flows through the shared path: the Renyi bound
    degenerates to the plain one-sample ELBO, bitwise."""
    guide, params = trained_params
    key = jax.random.PRNGKey(45)
    l_trace = Trace_ELBO(num_particles=1).loss(key, params, normal_model, guide, DATA)
    l_renyi = RenyiELBO(num_particles=1).loss(key, params, normal_model, guide, DATA)
    assert float(l_trace) == float(l_renyi)


def test_elbo_rejects_bad_particle_count():
    with pytest.raises(ValueError):
        Trace_ELBO(num_particles=0)
    with pytest.raises(ValueError):
        RenyiELBO(alpha=1.0)


# -- subsampling + jit-stable update signature -------------------------------


N_FULL, N_BATCH = 12, 4


def subsampled_model(data):
    loc = P.sample("loc", dist.Normal(0.0, 10.0))
    with P.plate("N", N_FULL, subsample_size=N_BATCH) as idx:
        P.sample("obs", dist.Normal(loc, 1.0), obs=data[idx])


def full_model(data):
    loc = P.sample("loc", dist.Normal(0.0, 10.0))
    with P.plate("N", N_FULL):
        P.sample("obs", dist.Normal(loc, 1.0), obs=data)


def test_plate_subsampling_rescales_under_jitted_update():
    """With constant data the N/B-rescaled minibatch ELBO equals the
    full-data ELBO for any index set — checked through the jitted update."""
    data = jnp.full((N_FULL,), 1.5)
    key = jax.random.PRNGKey(0)

    guide_s = AutoNormal(subsampled_model)
    svi_s = SVI(subsampled_model, guide_s, optim.Adam(0.05), Trace_ELBO())
    state_s = svi_s.init(key, data)
    idx = jnp.asarray([2, 5, 7, 11])
    _, loss_sub = svi_s.update_jit(state_s, data, subsample={"N": idx})

    guide_f = AutoNormal(full_model)
    svi_f = SVI(full_model, guide_f, optim.Adam(0.05), Trace_ELBO())
    state_f = svi_f.init(key, data)
    _, loss_full = svi_f.update_jit(state_f, data)

    assert float(loss_sub) == pytest.approx(float(loss_full), rel=1e-6)


def test_update_jit_no_retrace_across_minibatches():
    """Fresh subsample indices each step reuse one compiled executable."""
    data = jnp.arange(float(N_FULL))
    guide = AutoNormal(subsampled_model)
    svi = SVI(subsampled_model, guide, optim.Adam(0.05), Trace_ELBO(num_particles=2))
    state = svi.init(jax.random.PRNGKey(0), data)
    for i in range(6):
        idx = jax.random.choice(
            jax.random.fold_in(jax.random.PRNGKey(1), i), N_FULL, (N_BATCH,), replace=False
        )
        state, loss = svi.update_jit(state, data, subsample={"N": idx})
        assert jnp.isfinite(loss)
    assert svi.update_jit._cache_size() == 1


def test_run_reuses_one_executable():
    guide = AutoNormal(normal_model)
    svi = SVI(normal_model, guide, optim.Adam(0.05), Trace_ELBO(num_particles=2))
    svi.run(jax.random.PRNGKey(0), 10, DATA)
    svi.run(jax.random.PRNGKey(1), 10, DATA)  # second run: same cache entry
    assert svi.update_jit._cache_size() == 1


def test_sharded_svi_end_to_end(mesh):
    """mesh= turns on sharded state + sharded particles; converges on the
    1-device mesh exactly like the local path."""
    guide = AutoNormal(normal_model)
    svi = SVI(normal_model, guide, optim.Adam(0.05), Trace_ELBO(num_particles=4), mesh=mesh)
    state, losses = svi.run(jax.random.PRNGKey(0), 300, DATA)
    assert losses[-1] < losses[0]
    assert svi.update_jit._cache_size() == 1
    post_mean = float(DATA.sum() / (len(DATA) + 1 / 100.0))
    assert float(svi.get_params(state)["auto_loc_loc"]) == pytest.approx(post_mean, abs=0.2)


def test_python_scalar_param_init():
    """P.param with a python-float init must survive SVI.init's leaf
    canonicalization and still train compile-once."""

    def model():
        P.sample("x", dist.Normal(0.0, 1.0), obs=jnp.asarray(0.7))

    def guide():
        P.param("loc", 0.0)

    svi = SVI(model, guide, optim.Adam(0.05), Trace_ELBO())
    state = svi.init(jax.random.PRNGKey(0))
    state, loss = svi.update_jit(state)
    state, loss = svi.update_jit(state)
    assert jnp.isfinite(loss) and svi.update_jit._cache_size() == 1


def test_mesh_without_data_axis_works():
    """Generic mesh axis names fall back to the first axis instead of
    crashing on a missing 'data' axis."""
    odd_mesh = make_mesh((1,), ("x",))
    guide = AutoNormal(normal_model)
    svi = SVI(normal_model, guide, optim.Adam(0.05), Trace_ELBO(num_particles=4), mesh=odd_mesh)
    state = svi.init(jax.random.PRNGKey(0), DATA)
    state, loss = svi.update_jit(state, DATA)
    assert jnp.isfinite(loss)


def test_mesh_svi_does_not_mutate_shared_loss(mesh):
    """SVI(mesh=...) must not bind the caller's estimator to its mesh."""
    shared = Trace_ELBO(num_particles=4)
    SVI(normal_model, AutoNormal(normal_model), optim.Adam(0.05), shared, mesh=mesh)
    assert shared.mesh is None


def test_bad_subsample_shape_raises():
    data = jnp.arange(float(N_FULL))
    guide = AutoNormal(subsampled_model)
    svi = SVI(subsampled_model, guide, optim.Adam(0.05), Trace_ELBO())
    state = svi.init(jax.random.PRNGKey(0), data)
    with pytest.raises(ValueError, match="subsample indices"):
        svi.update(state, data, subsample={"N": jnp.asarray([0, 1])})  # wrong length


def test_typod_subsample_key_raises():
    """A subsample key naming no plate must fail loudly, not silently train
    on the plate's own random indices (or corrupt a sample site)."""
    data = jnp.arange(float(N_FULL))
    guide = AutoNormal(subsampled_model)
    svi = SVI(subsampled_model, guide, optim.Adam(0.05), Trace_ELBO())
    state = svi.init(jax.random.PRNGKey(0), data)
    with pytest.raises(KeyError, match="match no plate"):
        svi.update(state, data, subsample={"n": jnp.arange(N_BATCH)})  # 'n' != 'N'
    with pytest.raises(KeyError, match="match no plate"):
        svi.update(state, data, subsample={"loc": jnp.arange(N_BATCH)})  # latent name


# -- kernel backend dispatch -------------------------------------------------


def test_backend_resolves_to_reference_on_cpu(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert jax.default_backend() != "tpu"
    assert ops.resolve_backend() == "reference"


def test_backend_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert ops.resolve_backend() == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "ref")
    assert ops.resolve_backend() == "reference"
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.warns(FutureWarning, match="REPRO_KERNEL_BACKEND"):
        assert ops.resolve_backend() == "interpret"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    with pytest.warns(FutureWarning, match="deprecated"):
        assert ops.resolve_backend() == "tpu"


def test_legacy_flag_warns_on_surprising_values(monkeypatch):
    """Any REPRO_PALLAS_INTERPRET value other than 0/false means interpret —
    historically silently. The resolution is unchanged (compatibility) but now
    warns, naming the value, what it resolved to, and the replacement env var."""
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    for value, expect in [("interpret", "interpret"), ("2", "interpret"),
                          ("tpu", "interpret"), ("false", "tpu")]:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
        with pytest.warns(FutureWarning, match="REPRO_PALLAS_INTERPRET"):
            assert ops.resolve_backend() == expect, value
    # the modern env var takes precedence and never warns
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        assert ops.resolve_backend() == "reference"


def test_backend_rejects_unknown():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        ops.resolve_backend("mosaic-gpu")


def test_reference_dispatch_matches_oracle_bitwise(monkeypatch):
    """On CPU the default path IS ref.py — outputs must be identical."""
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (16, 64))
    toks = jax.random.randint(jax.random.fold_in(key, 1), (16,), 0, 64)
    out = ops.categorical_logprob(logits, toks)
    assert jnp.array_equal(out, jax.jit(categorical_logprob_ref)(logits, toks))


def test_reference_flash_attention_matches_interpret():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 4, 64, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 32))
    ref_out = ops.flash_attention(q, k, v, backend="reference")
    interp_out = ops.flash_attention(q, k, v, block_q=32, block_k=32, backend="interpret")
    assert jnp.allclose(ref_out, interp_out, atol=1e-4)
    assert jnp.allclose(ref_out, flash_attention_ref(q, k, v), atol=1e-6)


def test_backend_support_matrix_complete():
    m = ops.backend_support_matrix()
    assert set(m) == {
        "flash_attention",
        "categorical_logprob",
        "ssd_scan",
        "semiring_matmul",
        "hmm_scan",
        "leapfrog",
        "gaussian_combine",
        "gaussian_scan",
        "resample",
    }
    for row in m.values():
        assert set(row) == set(ops.BACKENDS)
