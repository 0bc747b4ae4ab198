"""`repro.telemetry` (spans, their JAX compile events, the bound on what is
kept) and the work counters the fused MCMC driver returns in its
`mcmc.run` span, checked by brute force on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import distributions as dist
from repro import telemetry
from repro.core import primitives as P
from repro.infer import HMC, MCMC, NUTS
from repro.kernels import ops

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


# -- spans ---------------------------------------------------------------------


def test_spans_nest_with_parent_ids():
    with telemetry.span("t.outer") as outer:
        with telemetry.span("t.inner") as inner:
            inner["counters"]["n"] = 3
        assert inner["end"] is not None and outer["end"] is None
    assert outer["parent"] is None
    assert inner["parent"] == outer["id"]
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]
    kept = telemetry.spans()
    assert kept.index(inner) < kept.index(outer)  # kept as they close
    assert telemetry.spans("t.inner")[-1]["counters"] == {"n": 3}


def test_span_is_recorded_when_the_block_raises():
    with pytest.raises(ValueError):
        with telemetry.span("t.raises"):
            raise ValueError("boom")
    assert telemetry.spans("t.raises")[-1]["end"] is not None


def test_kept_spans_are_bounded():
    first = None
    for i in range(telemetry.MAX_SPANS + 10):
        with telemetry.span("t.bound") as rec:
            first = first or rec["id"]
    kept = telemetry.spans()
    assert len(kept) == telemetry.MAX_SPANS
    ids = [r["id"] for r in telemetry.spans("t.bound")]
    assert first not in ids and ids == sorted(ids)  # the oldest went first


# -- JAX's compile events --------------------------------------------------------


def test_compile_events_go_to_the_innermost_open_span():
    x = jnp.arange(3.0)
    with telemetry.span("t.parent") as parent:
        with telemetry.span("t.before"):
            pass
        with telemetry.span("t.compiles") as child:
            jax.jit(lambda v: v * 7.0 + 1.0)(x).block_until_ready()
    before = telemetry.spans("t.before")[-1]
    assert before["jax_events"] == {}
    assert child["jax_events"][TRACE] > 0 and child["jax_events"][COMPILE] > 0
    for event, seconds in child["jax_events"].items():
        assert parent["jax_events"][event] >= seconds  # handed up on close
    assert child["jax_events"][TRACE] <= child["end"] - child["start"]


def _jax_event(event, seconds, nested=()):
    """Replay what JAX reports for one compile event: its start, the events
    nested in it, its duration."""
    telemetry._on_start(event, 0.0)
    for inner in nested:
        _jax_event(*inner)
    telemetry._on_duration(event, seconds)


def test_nested_durations_are_counted_once():
    with telemetry.span("t.outer") as outer:
        _jax_event(TRACE, 1.0)
        with telemetry.span("t.nest") as rec:
            # a trace holding an inner jit's trace, and a lowering that
            # traces a kernel
            _jax_event(TRACE, 3.0, [(TRACE, 0.5), (LOWER, 1.0, [(TRACE, 0.25)])])
        telemetry._on_duration(LOWER, 2.0)  # no start seen: all its own
    assert rec["jax_events"][TRACE] == pytest.approx(0.5 + 0.25 + (3.0 - 0.5 - 1.0))
    assert rec["jax_events"][LOWER] == pytest.approx(1.0 - 0.25)
    assert sum(rec["jax_events"].values()) == pytest.approx(3.0)
    assert outer["jax_events"] == pytest.approx({TRACE: 1.0 + 2.25, LOWER: 0.75 + 2.0})
    assert telemetry._stack("jax") == []


def test_cache_hits_are_counted_in_the_open_span():
    with telemetry.span("t.hits") as outer:
        with telemetry.span("t.hit") as inner:
            telemetry._on_event(telemetry.CACHE_HIT)
            telemetry._on_event("/jax/some/other/event")
    assert inner["jax_events"] == {telemetry.CACHE_HIT: 1}
    assert outer["jax_events"][telemetry.CACHE_HIT] == 1


def test_events_outside_any_span_are_dropped():
    telemetry._on_duration(TRACE, 1.0)
    telemetry._on_event(telemetry.CACHE_HIT)
    with telemetry.span("t.after") as rec:
        pass
    assert rec["jax_events"] == {}


# -- the op's own report of its gradient evaluations ----------------------------------


def _quadratic(z):
    return 0.5 * jnp.sum(z * z)


def _expected_evals(num_steps, max_steps, backend, block=8):
    """Brute force: what each backend's loop evaluates on each row."""
    n = np.minimum(np.asarray(num_steps), max_steps)
    if backend == "reference":
        return np.full(n.shape, 2 * n.max() + 1)
    out = np.empty_like(n)
    for b in range(0, n.size, block):
        out[b:b + block] = 2 + n[b:b + block].max()
    return out


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("max_steps", [1, 4])
def test_leapfrog_reports_its_evaluations(backend, max_steps):
    C, D = 12, 3
    z = jnp.ones((C, D))
    num_steps = jnp.asarray([0, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], jnp.int32)
    num_steps = num_steps.at[9].set(3)
    args = (z, z, jnp.ones((C, D)), jnp.full((C,), 0.1), num_steps)
    with ops.leapfrog_reports() as reports:
        out = ops.leapfrog(*args, _quadratic, max_steps=max_steps, backend=backend)
    assert len(out) == 3 and len(reports) == 1
    np.testing.assert_array_equal(
        reports[0], _expected_evals(num_steps, max_steps, backend))
    plain = ops.leapfrog(*args, _quadratic, max_steps=max_steps, backend=backend)
    for a, b in zip(out, plain):  # reporting changes nothing it returns
        np.testing.assert_array_equal(a, b)


# -- the fused driver's counters ----------------------------------------------------


def _model(y):
    mu = P.sample("mu", dist.Normal(0.0, 5.0))
    with P.plate("N", y.shape[0]):
        P.sample("obs", dist.Normal(mu, 2.0), obs=y)


Y = jnp.asarray([1.0, 3.0, -2.0, 0.5])


@pytest.fixture
def backend(request, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", request.param)
    return request.param


def _leapfrog_logging_its_steps(monkeypatch, calls):
    """Wrap `ops.leapfrog` so each call's `num_steps` reaches the host."""
    leapfrog = ops.leapfrog

    def logged(z, r, inv_mass, step_size, num_steps, potential_fn, **kwargs):
        jax.debug.callback(lambda n: calls.append(np.asarray(n)), num_steps)
        return leapfrog(z, r, inv_mass, step_size, num_steps, potential_fn, **kwargs)

    monkeypatch.setattr(ops, "leapfrog", logged)


def _run_counters():
    rec = telemetry.spans("mcmc.run")[-1]
    return {k: np.asarray(v) for k, v in rec["counters"].items()}


@pytest.mark.parametrize("backend", ["reference", "interpret"], indirect=True)
@pytest.mark.parametrize("warmup", [0, 3])
def test_fused_nuts_counters(backend, warmup, monkeypatch):
    C, S, depth = 12, 4, 4
    calls = []
    _leapfrog_logging_its_steps(monkeypatch, calls)
    # a step size at which trees end before the depth limit
    mcmc = MCMC(NUTS(_model, max_tree_depth=depth, step_size=0.5), num_warmup=warmup,
                num_samples=S, num_chains=C)
    mcmc.run(jax.random.PRNGKey(1), Y)
    jax.effects_barrier()
    c = _run_counters()
    num_steps = np.asarray(mcmc.get_extra_fields()["num_steps"])  # (C, S)
    steps = num_steps.sum(axis=1)
    if warmup == 0:
        np.testing.assert_array_equal(c["leapfrog_steps"], steps)
    else:
        assert np.all(c["leapfrog_steps"] > steps)
    # each depth level j runs while a chain is still growing: as many calls
    # as the longest of the chains' steps in that level
    assert int(c["leapfrog_calls"]) == len(calls) < (warmup + S) * (2 ** depth - 1)
    if warmup == 0:
        longest = sum(np.clip(num_steps - (2 ** j - 1), 0, 2 ** j).max(axis=0).sum()
                      for j in range(depth))
        assert int(c["leapfrog_calls"]) == longest
    assert int(sum(n.sum() for n in calls)) == int(c["leapfrog_steps"].sum())
    brute = sum(_expected_evals(n, 1, backend) for n in calls)
    np.testing.assert_array_equal(c["grad_evals"], brute)
    assert c["leapfrog_steps"].dtype == c["grad_evals"].dtype == np.int32
    mcmc.run(jax.random.PRNGKey(2), Y)  # a fresh key reuses the executable
    assert mcmc.num_traces == 1


@pytest.mark.parametrize("backend", ["reference", "interpret"], indirect=True)
def test_fused_hmc_counters(backend):
    C, W, S = 12, 2, 3
    mcmc = MCMC(HMC(_model, max_num_steps=8, trajectory_length=1.0),
                num_warmup=W, num_samples=S, num_chains=C)
    mcmc.run(jax.random.PRNGKey(3), Y)
    c = _run_counters()
    assert int(c["leapfrog_calls"]) == W + S
    assert np.all(c["leapfrog_steps"] >= W + S)
    assert np.all(c["grad_evals"] > c["leapfrog_steps"])


def test_mcmc_run_spans_and_no_counters_off_the_fused_path():
    mcmc = MCMC(NUTS(_model, max_tree_depth=3), num_warmup=2, num_samples=2,
                num_chains=2, fused=False)
    mcmc.run(jax.random.PRNGKey(4), Y)
    run = telemetry.spans("mcmc.run")[-1]
    children = [s for s in telemetry.spans() if s["parent"] == run["id"]]
    assert [s["name"] for s in children] == ["mcmc.model_setup", "mcmc.call"]
    assert run["counters"] == {}
    assert run["jax_events"][TRACE] >= children[1]["jax_events"][TRACE] > 0
