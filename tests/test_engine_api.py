"""The unified `InferenceEngine` surface + FutureWarning aliases (ISSUE 10).

One protocol for the sample-producing engines — ``run(key, *args)``,
``get_samples(group_by_chain=...)``, ``num_traces`` — and the kwarg
reconciliation behind it: `mesh=` is the canonical sharding spelling
everywhere (the legacy `MCMC(chain_method=...)` warns), `num_particles`
the canonical particle count (the legacy `Importance(num_samples=...)`
warns). Every alias is pinned to produce bit-identical results through the
old and the new spelling (the PR-9 config-alias playbook).
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import distributions as dist
from repro.core import primitives as P
from repro.distributed import make_mesh
from repro.infer import (
    HMC,
    MCMC,
    SMC,
    Importance,
    ImportanceSampling,
    InferenceEngine,
    Predictive,
    SVI,
)
from repro.retrace import RetraceCounted

DATA = jnp.asarray([0.3, -0.2, 0.5, 0.1])


def normal_model(y):
    loc = P.sample("loc", dist.Normal(0.0, 1.0))
    P.sample("obs", dist.Normal(loc, 1.0), obs=y)


def ssm_init(y):
    x = P.sample("x", dist.Normal(0.0, 1.0))
    P.sample("y", dist.Normal(x, 0.5), obs=y)
    return {"x": x}


def ssm_step(carry, y):
    x = P.sample("x", dist.Normal(0.9 * carry["x"], 0.3))
    P.sample("y", dist.Normal(x, 0.5), obs=y)
    return {"x": x}


# ---------------------------------------------------------------------------
# protocol conformance
# ---------------------------------------------------------------------------


def test_engines_satisfy_protocol_structurally():
    engines = [
        MCMC(HMC(normal_model), num_warmup=10, num_samples=10),
        SMC(ssm_init, ssm_step, num_particles=32),
        ImportanceSampling(normal_model, num_particles=32),
    ]
    for eng in engines:
        assert isinstance(eng, InferenceEngine), type(eng).__name__
        assert isinstance(eng, RetraceCounted), type(eng).__name__


def test_uniform_run_get_samples_surface():
    """The same three calls drive every engine; group_by_chain=True always
    prepends the chain/population axis."""
    ys = jnp.asarray([0.4, 0.2, 0.1])
    cases = [
        (MCMC(HMC(normal_model), num_warmup=30, num_samples=20), (DATA,), "loc"),
        (SMC(ssm_init, ssm_step, num_particles=64), (ys,), "x"),
        (ImportanceSampling(normal_model, num_particles=64), (DATA,), "loc"),
    ]
    for eng, args, site in cases:
        eng.run(jax.random.PRNGKey(0), *args)
        flat = eng.get_samples()[site]
        chained = eng.get_samples(group_by_chain=True)[site]
        assert chained.ndim == flat.ndim + 1, type(eng).__name__
        assert chained.shape[0] * chained.shape[1] == flat.shape[0] or (
            chained.shape[1:] == flat.shape  # particle engines: 1 x N
        ), type(eng).__name__
        assert eng.num_traces >= 1


# ---------------------------------------------------------------------------
# Importance -> ImportanceSampling alias
# ---------------------------------------------------------------------------


def test_importance_warns_futurewarning():
    with pytest.warns(FutureWarning, match="ImportanceSampling"):
        Importance(normal_model, num_samples=8)


def test_importance_alias_bit_parity():
    """Old and new spellings must produce bit-identical weights and samples
    from the same key (same key structure, same log-prob filter order)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", FutureWarning)
        old = Importance(normal_model, num_samples=256)
    new = ImportanceSampling(normal_model, num_particles=256)
    old.run(jax.random.PRNGKey(1), DATA)
    new.run(jax.random.PRNGKey(1), DATA)
    assert jnp.array_equal(old.log_weights, new.log_weights)
    assert jnp.array_equal(old.get_samples()["loc"], new.get_samples()["loc"])
    assert old.num_samples == old.num_particles == 256


def test_importance_alias_with_guide_bit_parity():
    def guide(y):
        P.sample("loc", dist.Normal(0.2, 0.7))

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", FutureWarning)
        old = Importance(normal_model, guide, num_samples=128)
    new = ImportanceSampling(normal_model, guide, num_particles=128)
    old.run(jax.random.PRNGKey(2), DATA)
    new.run(jax.random.PRNGKey(2), DATA)
    assert jnp.array_equal(old.log_weights, new.log_weights)


# ---------------------------------------------------------------------------
# MCMC chain_method -> mesh alias
# ---------------------------------------------------------------------------


def test_chain_method_warns_futurewarning():
    with pytest.warns(FutureWarning, match="mesh="):
        MCMC(HMC(normal_model), 10, 10, chain_method="vectorized")


@pytest.mark.parametrize(
    "old_kw,new_kw",
    [
        ({"chain_method": "vectorized"}, {"mesh": None}),
        ({"chain_method": "sharded"}, {"mesh": "auto"}),
    ],
    ids=["vectorized", "sharded"],
)
def test_chain_method_alias_bit_parity(old_kw, new_kw):
    runs = {}
    for label, kw in (("old", old_kw), ("new", new_kw)):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", FutureWarning)
            mcmc = MCMC(
                HMC(normal_model), num_warmup=40, num_samples=30,
                num_chains=2, **kw,
            )
        mcmc.run(jax.random.PRNGKey(3), DATA)
        runs[label] = mcmc
    assert runs["old"].chain_method == runs["new"].chain_method
    assert jnp.array_equal(
        runs["old"].get_samples(group_by_chain=True)["loc"],
        runs["new"].get_samples(group_by_chain=True)["loc"],
    )


def test_mesh_auto_resolves_to_default_mesh():
    mcmc = MCMC(HMC(normal_model), 10, 10, mesh="auto")
    assert mcmc.mesh is not None
    assert mcmc.chain_method == "sharded"
    assert MCMC(HMC(normal_model), 10, 10).mesh is None


def test_explicit_mesh_object_accepted():
    mesh = make_mesh((jax.device_count(),), ("data",))
    mcmc = MCMC(HMC(normal_model), 10, 10, mesh=mesh)
    assert mcmc.mesh is mesh
    assert mcmc.chain_method == "sharded"


def test_mesh_helper_axes_are_auto():
    """`with_sharding_constraint` (every mesh= path) refuses Explicit axes,
    which bare `jax.make_mesh` now defaults to."""
    from jax.sharding import AxisType

    from repro.distributed.sharding import default_mesh

    for mesh in (make_mesh((1, 1), ("data", "model")), default_mesh()):
        assert all(t == AxisType.Auto for t in mesh.axis_types)


def test_bad_mesh_string_rejected():
    with pytest.raises(ValueError, match="mesh must be"):
        MCMC(HMC(normal_model), 10, 10, mesh="tpu")


def test_chain_method_sharded_with_explicit_mesh_keeps_it():
    mesh = make_mesh((jax.device_count(),), ("data",))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", FutureWarning)
        mcmc = MCMC(HMC(normal_model), 10, 10, chain_method="sharded", mesh=mesh)
    assert mcmc.mesh is mesh


# ---------------------------------------------------------------------------
# canonical spellings elsewhere (no aliases needed — pinned so they don't
# drift apart again)
# ---------------------------------------------------------------------------


def test_predictive_num_samples_is_canonical():
    pred = Predictive(normal_model, num_samples=7)
    out = pred(jax.random.PRNGKey(4), DATA)
    assert out["obs"].shape[0] == 7


def test_particle_engines_share_mesh_kwarg():
    """`mesh=` means the same thing on every engine: constrain the
    parallel axis (chains or particles) onto the mesh."""
    mesh = make_mesh((jax.device_count(),), ("data",))
    ys = jnp.asarray([0.4, 0.2])
    for eng in (
        SMC(ssm_init, ssm_step, num_particles=32, mesh=mesh),
        ImportanceSampling(normal_model, num_particles=32, mesh=mesh),
    ):
        eng.run(jax.random.PRNGKey(5), *((ys,) if isinstance(eng, SMC) else (DATA,)))
        assert np.isfinite(float(jnp.sum(eng.log_weights)))
