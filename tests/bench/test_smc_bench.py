"""The SMC cells of the benchmark, on the CPU at small sizes: the
stochastic-volatility configuration and its float64 reference, the SMC
driver's whole run minus the look for a chip, its planted faults and its
bfloat16 control, the work counts, and the per-layer readers.

Modules are loaded by file path; nothing here loads the TPU library.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.append(str(BENCH))  # `bench/drivers` and `bench/metrics` import `lib`


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


run_mod = _load(BENCH / "run.py", "bench_run_smc_under_test")
sv = _load(BENCH / "configs" / "sv_sp500.py", "bench_sv_under_test")
driver = _load(BENCH / "drivers" / "smc.py", "bench_smc_driver_under_test")
jaxpr_count = _load(BENCH / "lib" / "jaxpr_count.py", "bench_jaxpr_count_under_test")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
SV_SPEC = json.loads((BENCH / "configs" / "sv_sp500.json").read_text())
CELLS = ("smc.sv2516.p65536", "smc.sv2516.p1024")
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

# the test-only small cell: the p1024 cell's traffic over the first T = 256
# steps of a series simulated as the configuration's is; log Z-hat's
# standard deviation there is 0.2025 (CPU, 200 sweeps of 1024 particles)
SMALL_T = 256
SMALL_LOGZ_SD = 0.2


def _small_cell():
    cell = run_mod.Cell("smc.sv2516.p1024", BENCHMARK)
    limits = dict(cell.spec["limits"], logz_sd={"1024": SMALL_LOGZ_SD})
    cell.spec = dict(cell.spec, T=SMALL_T, limits=limits)
    return cell


def _correct(cell, record) -> bool:
    return run_mod.result_line(cell, record, DEVICE, False)["correct"]


# -- the configuration and its reference --------------------------------------------


def test_data_are_one_series_for_every_seed():
    a = sv.make_data(SV_SPEC, 0)
    b = sv.make_data(SV_SPEC, 2**31 + 5)
    assert a["y"].shape == (2516,) and a["y"].dtype == np.float32
    assert np.all(np.isfinite(a["y"])) and np.all(a["y"] != 0)
    np.testing.assert_array_equal(a["y"], b["y"])
    # daily returns of about 1%, as the parameters say
    assert 0.003 < float(np.std(a["y"])) < 0.03
    assert SV_SPEC["T"] == 2516 and SV_SPEC["reduced"] == []


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_log_obs_ref_matches_program(seed):
    import jax.numpy as jnp

    from repro import distributions as dist

    gen = np.random.default_rng(seed)
    h = gen.normal(-9.2, 1.0, size=64)
    y = sv.make_data(SV_SPEC, seed)["y"][:64]
    want = np.asarray(dist.Normal(0.0, jnp.exp(jnp.asarray(h, jnp.float32) / 2.0))
                      .log_prob(jnp.asarray(y)))
    got = sv.log_obs_ref(h.astype(np.float32), y, np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _kalman(spec, y, r):
    """Exact log p(y_1..T) and filtering means and variances of the AR(1)
    state observed as y_t ~ N(h_t, r)."""
    mu, phi, sigma = sv.params(spec)
    m, v = mu, sv.stationary_sd(spec) ** 2
    log_z, means, variances = 0.0, [], []
    for t, yt in enumerate(y):
        if t:
            m, v = mu + phi * (m - mu), phi * phi * v + sigma * sigma
        s = v + r * r
        log_z += -0.5 * (math.log(2 * math.pi * s) + (yt - m) ** 2 / s)
        k = v / s
        m, v = m + k * (yt - m), (1 - k) * v
        means.append(m)
        variances.append(v)
    return log_z, np.array(means), np.array(variances)


def test_grid_filter_is_exact_on_a_linear_gaussian_model():
    r = 0.4
    spec = dict(SV_SPEC, T=300)
    gen = np.random.default_rng(11)
    h = sv.make_data(spec, 0)["h"]
    y = h + r * gen.standard_normal(h.shape)

    def log_obs(x, yt, dtype):
        z = (yt - x) / r
        return -0.5 * z * z - math.log(r) - sv.LOG_SQRT_2PI

    got = sv.grid_filter_ref(spec, {"y": y}, SV_SPEC["grid_points"], log_obs=log_obs)
    log_z, means, variances = _kalman(spec, y, r)
    assert got["log_z"] == pytest.approx(log_z, abs=1e-6)
    np.testing.assert_allclose(got["mean"], means, atol=1e-6)
    np.testing.assert_allclose(got["var"], variances, atol=1e-6)


def test_grid_filter_k_against_2k():
    data = sv.make_data(SV_SPEC, 0)
    K = SV_SPEC["grid_points"]
    a = sv.grid_filter_ref(SV_SPEC, data, K)
    b = sv.grid_filter_ref(SV_SPEC, data, 2 * K)
    # far inside the limits: log Z-hat's standard deviation is 0.53 at 1024
    # particles, the filtering means' standard errors above 1e-3
    assert abs(a["log_z"] - b["log_z"]) < 1e-9
    np.testing.assert_allclose(a["mean"], b["mean"], atol=1e-10)
    np.testing.assert_allclose(a["var"], b["var"], atol=1e-10)
    assert np.all(a["var"] > 0)


def test_work_counts_by_hand():
    # transition 5, observation 7, weight 1, logsumexp 4, ESS 4, cumsum 1,
    # filtering mean 2
    assert sv.flops_per_particle_step(SV_SPEC) == 24
    # N float32 weights in, N int32 ancestors out
    assert sv.resample_bytes_per_call(1024) == 8192
    assert sv.resample_bytes_per_call(65536) == 524288


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_resample_calls_are_counted_alike_under_each_backend(monkeypatch, backend):
    """The counts the metrics read are functions of (N, T) alone: the
    program's resampling op is called once a step after the first, under
    the reference backend and the kernel's alike."""
    import jax
    import jax.numpy as jnp

    from repro.infer import SMC

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    spec = dict(SV_SPEC, T=40)
    engine = SMC(*sv.program(spec), num_particles=256, ess_threshold=0.5,
                 resample_method="systematic")
    ys = jnp.asarray(sv.make_data(spec, 0)["y"])
    calls = jaxpr_count.calls_per_run(lambda k: engine.run(k, ys), jax.random.PRNGKey(0),
                                      name=driver.RESAMPLE_OP)
    assert calls == spec["T"] - 1


def test_count_calls_through_scans_and_not_through_branches():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def op(x):
        return x + 1.0

    def scanned(x):
        def body(c, _):
            return op(op(c)), None
        return jax.lax.scan(body, x, None, length=7)[0]

    def branched(x):
        return jax.lax.cond(x > 0, op, lambda v: v, x)

    def looped(x):
        return jax.lax.while_loop(lambda v: v < 5.0, op, x)

    x = jnp.float32(0.0)
    assert jaxpr_count.calls_per_run(scanned, x, name="op") == 14
    assert jaxpr_count.calls_per_run(lambda v: op(scanned(v)), x, name="op") == 15
    assert jaxpr_count.calls_per_run(branched, x, name="op") is None
    assert jaxpr_count.calls_per_run(looped, x, name="op") is None
    assert jaxpr_count.calls_per_run(lambda v: v * 2.0, x, name="op") == 0


def test_weighted_mean_reference():
    import ml_dtypes

    gen = np.random.default_rng(5)
    h = gen.normal(-9.2, 0.5, size=(3, 1000))
    lw = gen.normal(0.0, 3.0, size=(3, 1000))
    w = np.exp(lw - lw.max(axis=1, keepdims=True))
    want = np.sum(w * h, axis=1) / np.sum(w, axis=1)
    np.testing.assert_allclose(driver.weighted_mean(h, lw, np.float64), want, rtol=1e-13)
    low = driver.weighted_mean(h, lw, ml_dtypes.bfloat16).astype(np.float64)
    # bfloat16 holds -9.2 to within 1/32
    assert 1e-3 < np.max(np.abs(low - want)) < 0.2


# -- rehearsals: the whole run minus the chip, and its faults ------------------------


@pytest.fixture(scope="module")
def meter():
    from lib.compile_meter import CompileMeter

    return CompileMeter()


@pytest.fixture(scope="module")
def sound(meter):
    """One run of the small cell, as `bench/run.py` makes it."""
    cell = _small_cell()
    record = run_mod.measure(cell, 2**31 + 4321, 0.5, False, meter, None)
    return cell, record


def test_smc_rehearsal_is_correct(sound):
    cell, record = sound
    out = run_mod.result_line(cell, record, DEVICE, False)
    assert out["correct"], out["checked"]
    assert set(out["checked"]) == {"lw_gap", "mean_gap", "logz_gap"}  # filter_z: 65536 only
    assert list(out)[-1] == "checked"
    c = record["counters"]
    assert out["attempted"] == c["sweeps"] >= driver.CHECKED_SWEEPS
    assert c["particle_steps"] == 1024 * SMALL_T * c["sweeps"]
    assert c["resample_calls"] == (SMALL_T - 1) * c["sweeps"]
    assert 0 < c["resampled_steps"] < c["resample_calls"]
    assert out["metrics"]["smc_particle_steps_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"smc_particle_steps_per_s", "setup_s"}


@pytest.fixture(scope="module")
def small_run(meter):
    """The small cell's run, its window closed, as `bench/control.py`
    holds it."""
    cell = _small_cell()
    run = cell.make_run(2**31 + 555)
    run.setup()
    before = meter.snapshot()["compiles"]
    run.window(0.5)
    assert meter.snapshot()["compiles"] == before, "compiled inside the window"
    assert run.num_traces == 1
    run.finish()
    return cell, run


def _run_correct(cell, run, control: bool = False) -> bool:
    record = {"setup_s": 1.0, "setup_compile_s": 0.0, "memory_peak_bytes": 0,
              "counters": run.counters, "trace": None, "checks": run.check(control=control)}
    return _correct(cell, record)


class _Device:
    """A device queue for the window's arithmetic: each sweep's answer is
    ready `length` seconds after the sweep ahead of it ends."""

    def __init__(self, length: float):
        self.length, self.free_at = length, 0.0
        self.ends: list = []  # each dispatched sweep's end
        self.depth: list = []  # sweeps not yet ended, at each dispatch

    def sweep(self, engine, key, history):
        import time

        now = time.perf_counter()
        self.free_at = max(now, self.free_at) + self.length
        self.depth.append(sum(t > now for t in self.ends) + 1)
        self.ends.append(self.free_at)
        return {"log_z": _Answer(self.free_at)}


class _Answer:
    def __init__(self, ready_at: float):
        self.ready_at = ready_at

    def block_until_ready(self):
        import time

        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        return self


@pytest.mark.parametrize("length,seconds,sweeps,depth", [
    (0.4, 0.5, (2,), (2,)),              # long sweeps: one waits behind the one that runs
    (0.06, 0.62, range(9, 14), (3, 4)),  # short: QUEUE_S (0.15 s here) of them wait
])
def test_window_keeps_sweeps_queued(monkeypatch, length, seconds, sweeps, depth):
    """Every sweep expected to start inside the window is dispatched and
    completed, with about `QUEUE_S` seconds of sweeps waiting behind the
    running one, and the span runs to the last completion; a window holds
    at least the checked sweeps."""
    monkeypatch.setattr(driver, "QUEUE_S", 0.15)
    device = _Device(length)
    run = driver.Run(sv, SV_SPEC, {"num_particles": 8}, 0)
    run.engine, run.keys, run._sweep = None, list(range(100)), device.sweep
    run.window(seconds)
    assert len(run.outputs) in sweeps and len(run.outputs) == len(device.ends)
    assert max(device.depth) in depth
    assert run.span_s >= device.ends[-1] - device.ends[0] + length - 0.02


def test_checked_sweeps_are_drawn_from_the_seed(small_run):
    _, run = small_run
    assert len(set(run.checked)) == driver.CHECKED_SWEEPS
    assert all(0 <= i < run.counters["sweeps"] for i in run.checked)
    assert len(run.history) == driver.HISTORY_SWEEPS
    assert [a.shape for a in run.history[0]] == [(SMALL_T, 1024)] * 3


def test_bfloat16_control_is_not_correct(small_run):
    cell, run = small_run
    assert _run_correct(cell, run)
    assert not _run_correct(cell, run, control=True)
    readings = dict((n, v) for n, v, _ in run.check(control=True))
    assert readings["lw_gap"] > 1e-2 and readings["mean_gap"] > 1e-3
    assert readings["logz_gap"] > 100


def test_planted_answers_fail_as_control_py_reads_them(small_run):
    """`bench/control.py`'s path: a copy of the closed run, with a fault
    planted underneath and the checked sweeps run again."""
    import copy

    cell, run = small_run
    broken = copy.deepcopy(run, memo={id(run.config): run.config})
    broken.plant("half_step")
    assert not _run_correct(cell, broken)
    assert _run_correct(cell, run)


@pytest.mark.parametrize("fault", driver.FAULTS)
def test_run_with_the_program_broken_is_not_correct(meter, fault):
    """A whole run minus the look for a chip, with the timed path broken
    underneath (see `driver.planted`)."""
    cell = _small_cell()
    with driver.planted(fault):
        record = run_mod.measure(cell, 2**31 + 77, 0.2, False, meter, None)
    out = run_mod.result_line(cell, record, DEVICE, False)
    assert out["correct"] is False, (fault, out["checked"])


def test_planted_faults_restore_the_program():
    from repro.infer import combinators as C
    from repro.infer import smc
    from repro.kernels import ops

    def program():
        return (ops.resample, C.Resample.run_population, C.Program.run_population,
                smc._weighted_means)

    before = program()
    for fault in driver.FAULTS:
        with driver.planted(fault):
            assert program() != before
    assert program() == before
    with pytest.raises(ValueError, match="unknown fault"):
        with driver.planted("no such fault"):
            pass


# -- the per-layer readers ------------------------------------------------------------

# the resample kernel's event as a v5e trace names it: its HLO instruction
# text, cut before its metadata (from the compiled sweep for a described v5e)
KERNEL_EVENT = ('%_resample.6 = s32[1,65536]{1,0:T(1,128)S(1)} custom-call(%copy.9, '
                '%add_multiply_fusion.2), custom_call_target="tpu_custom_call", '
                'operand_layout_constraints={f32[65536,1]{1,0}, f32[1,65536]{1,0}}, '
                'frontend_attributes={kernel_metadata={}}')
NAMED_KERNEL = ('%closed_call.3 = s32[1,1024]{1,0} custom-call(%a, %b), '
                'custom_call_target="tpu_custom_call", '
                'frontend_attributes={kernel_metadata={\n"name":"repro.resample"\n}}')
OTHER_OPS = {"%fusion.4 = f32[65536]{0} fusion(%p)": 0.5,
             "%closed_call.9 = (f32[8,10]) custom-call(%a), custom_call_target=\"tpu_custom_call\"": 0.25}


def _reader(name: str):
    return _load(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def _record(op_seconds=None, **counters):
    c = {"sweeps": 2, "span_s": 4.0, "num_particles": 65536, "T": 2516,
         "particle_steps": 65536 * 2516 * 2, "resampled_steps": 400,
         "resample_calls": 2515 * 2, "flops_per_particle_step": 24,
         "resample_bytes_per_call": 524288}
    c.update(counters)
    trace = None
    if op_seconds is not None:
        trace = {"op_seconds": op_seconds, "busy_s": 3.0, "window_s": 4.0}
    return {"counters": c, "trace": trace, "peaks": PEAKS}


def test_resample_roofline_reads_the_kernel_by_name():
    read = _reader("resample_roofline")
    least = 2515 * 2 * 524288 / 819e9
    assert read(_record(dict(OTHER_OPS, **{KERNEL_EVENT: 50.0}))) == pytest.approx(
        100.0 * least / 50.0)
    assert read(_record(dict(OTHER_OPS, **{NAMED_KERNEL: 2.0}))) == pytest.approx(
        100.0 * least / 2.0)
    # no kernel in the trace, no trace, or no count: nothing to read
    assert read(_record(OTHER_OPS)) is None
    assert read(_record()) is None
    assert read(_record({KERNEL_EVENT: 1.0}, resample_calls=None)) is None


def test_whole_step_and_counter_readers():
    rec = _record({KERNEL_EVENT: 1.0})
    assert _reader("mfu.smc")(rec) == pytest.approx(
        100.0 * 65536 * 2516 * 2 * 24 / 4.0 / 197e12)
    assert _reader("smc.resample_useful_share")(rec) == pytest.approx(100.0 * 400 / 5030)
    assert _reader("smc.resample_useful_share")(_record(resample_calls=None)) is None
    assert _reader("smc_particle_steps_per_s")(rec) == pytest.approx(65536 * 2516 * 2 / 4.0)
    assert _reader("device_idle.smc")(rec) == pytest.approx(25.0)
    assert _reader("device_idle.smc")(_record()) is None
    # the NUTS counters have none of these
    nuts = {"counters": {"inferences": 3, "span_s": 1.0}, "trace": None, "peaks": PEAKS}
    for name in ("mfu.smc", "smc.resample_useful_share", "smc_particle_steps_per_s",
                 "resample_roofline"):
        assert _reader(name)(nuts) is None


def test_smc_cells_report_their_metrics():
    for name in CELLS:
        cell = run_mod.Cell(name, BENCHMARK)
        assert {m["name"] for m in cell.end_to_end} == {"smc_particle_steps_per_s", "setup_s"}
        assert {"resample_roofline", "mfu.smc", "smc.resample_useful_share",
                "device_idle.smc"} <= {m["name"] for m in cell.per_layer}
        assert cell.workload["chips"] == 1
        assert cell.traffic["driver"] == "smc"
    nuts = run_mod.Cell("nuts.eight_schools.c1024", BENCHMARK)
    assert "smc_particle_steps_per_s" not in {m["name"] for m in nuts.end_to_end}
