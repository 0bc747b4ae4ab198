"""The benchmark harness under `bench/`, on the CPU at tiny sizes.

Modules are loaded by file path; nothing here loads the TPU library. The
driver's rehearsal runs the timed path, the window and the check exactly as
`bench/run.py` does, minus the look for a chip, so a broken driver fails
here and not on the chip. The fault tests break the program underneath the
timed path (or its answers, as `bench/control.py` does on the chip) and see
`correct`, as `run.result_line` decides it, come out false.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.append(str(BENCH))  # for the driver's `lib` imports


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


run_mod = _load(BENCH / "run.py", "bench_run_under_test")
trace_mod = _load(BENCH / "lib" / "trace.py", "bench_trace_under_test")
ess_mod = _load(BENCH / "lib" / "ess.py", "bench_ess_under_test")
schools = _load(BENCH / "configs" / "eight_schools.py", "bench_schools_under_test")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
SCHOOLS_SPEC = json.loads((BENCH / "configs" / "eight_schools.json").read_text())
CELL = "nuts.eight_schools.c1024"
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

# tiny traffic for the CPU rehearsals: the cell's own, with fewer chains and
# iterations (the posterior is the published one)
TINY = {"num_chains": 8, "num_warmup": 30, "num_samples": 30}


def _tiny_cell(name: str = CELL):
    cell = run_mod.Cell(name, BENCHMARK)
    cell.traffic = dict(cell.traffic, **TINY)
    return cell


def _correct(cell, run, control: bool = False) -> bool:
    """`correct` as the result line reports it, for a run that has closed
    its window."""
    record = {"setup_s": 1.0, "setup_compile_s": 0.0, "memory_peak_bytes": 0,
              "counters": run.counters, "trace": None, "checks": run.check(control=control)}
    return run_mod.result_line(cell, record, DEVICE, False)["correct"]


# -- found by name --------------------------------------------------------------


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cells_found_by_name(cell):
    c = run_mod.Cell(cell, BENCHMARK)
    assert c.spec["name"] == c.workload["config"]
    assert hasattr(c.driver, "Run")
    assert {"setup_s"} <= {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(c.reader(m["name"]).read)


def test_metrics_move_what_their_cells_report():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    for m in BENCHMARK["end_to_end"]:
        assert set(m.get("workloads", [])) <= cells
    for m in BENCHMARK["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", []):
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"], (m["name"], cell)
    for c in BENCHMARK["configs"]:
        assert (ROOT / c["file"]).is_file() and (ROOT / c["file"]).with_suffix(".py").is_file()


def test_unknown_cell_and_missing_files_are_refused(tmp_path):
    with pytest.raises(run_mod.Refused):
        run_mod.Cell("no.such.cell", BENCHMARK)
    with pytest.raises(run_mod.Refused):
        run_mod.read_json(tmp_path / "absent.json")


def test_unknown_device_kind_is_refused():
    assert run_mod.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(run_mod.Refused):
        run_mod.peaks_for("no such chip")


# -- the copied arithmetic --------------------------------------------------------


@pytest.mark.parametrize("chains,draws,rho", [(4, 200, 0.0), (4, 200, 0.9), (16, 50, 0.5)])
def test_ess_copy_matches_program_diagnostics(chains, draws, rho):
    from repro.infer.diagnostics import effective_sample_size

    gen = np.random.default_rng(7)
    e = gen.normal(size=(chains, draws))
    x = np.empty_like(e)
    x[:, 0] = e[:, 0]
    for t in range(1, draws):
        x[:, t] = rho * x[:, t - 1] + np.sqrt(1 - rho * rho) * e[:, t]
    x = x.astype(np.float32)
    got = ess_mod.bulk_ess(x)
    want = float(effective_sample_size(x, kind="bulk"))
    assert got == pytest.approx(want, rel=1e-4)


def test_flop_and_byte_counts_by_hand():
    # J = 2: value 8 * 2 + 9, gradient 6 * 2 + 8
    assert schools.flops_per_grad({"J": 2}) == 45
    assert schools.flops_per_grad({"J": 8}) == 129
    # D = 4: z, r, inv_mass in and z, r out (20 floats), eps, n, potential (3)
    assert schools.leapfrog_bytes_per_step({"D": 4}) == 92


# -- the configuration and its reference --------------------------------------------


def test_schools_data_are_the_published_eight():
    data = schools.make_data(SCHOOLS_SPEC, 2**31 + 5)
    np.testing.assert_array_equal(data["y"], [28, 8, -3, 7, -1, 1, 18, 12])
    np.testing.assert_array_equal(data["sigma"], [15, 10, 16, 11, 9, 11, 10, 18])
    assert SCHOOLS_SPEC["J"] == 8 and SCHOOLS_SPEC["D"] == 10
    np.testing.assert_array_equal(data["y"], schools.make_data(SCHOOLS_SPEC, 3)["y"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_schools_reference_potential_matches_program(seed):
    import jax

    from repro.infer.util import initialize_model

    data = schools.make_data(SCHOOLS_SPEC, seed)
    model, args = schools.program(SCHOOLS_SPEC, data)
    pe, transforms, _ = initialize_model(jax.random.PRNGKey(seed), model, args)
    gen = np.random.default_rng(seed)
    position = {"mu": gen.normal(0, 5, size=3), "log_tau": gen.normal(0, 2, size=3),
                "theta_trans": gen.normal(size=(3, 8))}
    # the program's unconstrained site for tau is log(tau)
    assert float(transforms["tau"](np.float32(0.0))) == 1.0
    site = {"mu": "mu", "log_tau": "tau", "theta_trans": "theta_trans"}
    want = [float(pe({site[k]: v[i].astype(np.float32) for k, v in position.items()}))
            for i in range(3)]
    np.testing.assert_allclose(schools.potential_ref(position, data), want, rtol=1e-5)
    back = schools.unconstrained(schools.constrained(position))
    for k, v in position.items():
        np.testing.assert_allclose(back[k], v, rtol=1e-6, atol=1e-6)


def test_schools_moments_against_importance_sampling():
    data = schools.make_data(SCHOOLS_SPEC, 0)
    m = schools.moments_ref(data)
    gen = np.random.default_rng(0)
    n = 400_000
    mu = gen.normal(0, 5, n)
    tau = np.abs(5.0 * np.tan(np.pi * (gen.uniform(size=n) - 0.5)))
    v = tau[:, None] ** 2 + data["sigma"].astype(np.float64) ** 2
    logw = -0.5 * np.sum(np.log(v) + (data["y"] - mu[:, None]) ** 2 / v, axis=1)
    w = np.exp(logw - logw.max())
    w /= w.sum()
    assert np.sum(w * mu) == pytest.approx(m["mu"][0], abs=0.05)
    assert np.sum(w * np.log(tau)) == pytest.approx(m["log_tau"][0], abs=0.05)
    var_lt = np.sum(w * (np.log(tau) - m["log_tau"][0]) ** 2)
    assert var_lt == pytest.approx(m["log_tau"][1], rel=0.05)


# -- trace reduction ----------------------------------------------------------------


def test_trace_reduction_on_recorded_trace():
    rec = json.loads((BENCH / "data" / "trace_small.json").read_text())
    device_ops = {k: [tuple(e) for e in v] for k, v in rec["device_ops"].items()}
    host = [tuple(e) for e in rec["host_spans"]]
    lo, hi = rec["window"]
    red = trace_mod.reduce_events(device_ops, host, (lo, hi))
    want = rec["expected"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert trace_mod.idle_percent(red) == pytest.approx(want["idle_percent"])
    sec, n = trace_mod.kernel_seconds(red, want["kernel_pattern"])
    assert sec == pytest.approx(want["kernel_s"]) and n >= 1
    assert red["idle_gaps"][0][0] == want["longest_gap_span"]


def test_self_times_of_nested_ops():
    events = [("while", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 4.0, 1.0), ("inner", 4.2, 0.3)]
    got = {n: (own, leaf) for n, _, _, own, leaf in trace_mod.self_times(events)}
    assert got["while"] == (pytest.approx(7.0), False)
    assert got["b"] == (pytest.approx(0.7), False)
    assert got["a"] == (2.0, True) and got["inner"] == (0.3, True)
    assert trace_mod.op_family("%fusion.12 = f32[8] fusion(x)") == "fusion"


def test_tracer_records_and_removes_its_window(tmp_path):
    import jax.numpy as jnp

    tracer = trace_mod.Tracer(str(tmp_path / "trace"))
    with tracer.window():
        (jnp.ones(4) * 2.0).block_until_ready()
    reduced = tracer.reduce()
    assert reduced is None or reduced["window_s"] > 0
    assert not (tmp_path / "trace").exists()


def test_union_length_merges_and_clips():
    length, merged = trace_mod.union_length([(0.0, 2.0), (1.0, 3.0), (5.0, 9.0)], 0.5, 6.0)
    assert length == pytest.approx(3.5)
    assert merged == [(0.5, 3.0), (5.0, 6.0)]


# -- refusal without a chip ---------------------------------------------------------


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


# -- rehearsals: the whole run minus the chip, and its faults ------------------------


@pytest.fixture(scope="module")
def meter():
    from lib.compile_meter import CompileMeter

    return CompileMeter()


@pytest.fixture(scope="module")
def nuts_run(meter):
    cell = _tiny_cell()
    run = cell.make_run(2**31 + 12345)
    run.setup()
    before = meter.snapshot()["compiles"]
    run.window(1.0)
    assert meter.snapshot()["compiles"] == before, "compiled inside the window"
    run.finish()
    return run


def test_nuts_rehearsal_is_correct(nuts_run):
    assert _correct(_tiny_cell(), nuts_run), nuts_run.check()
    c = nuts_run.counters
    assert c["inferences"] >= 1 and c["ess_sum"] > 0 and c["leapfrog_steps"] > 0


@pytest.mark.parametrize("fault", ["frozen", "half", "altered"])
def test_nuts_planted_fault_is_not_correct(nuts_run, fault):
    run = copy.copy(nuts_run)
    run.draws = copy.deepcopy(nuts_run.draws)
    run.positions = copy.deepcopy(nuts_run.positions)
    run.plant(fault)
    assert not _correct(_tiny_cell(), run)


def test_bfloat16_control_is_not_correct(nuts_run):
    assert not _correct(_tiny_cell(), nuts_run, control=True)


def _step_that_returns_its_state(step):
    def broken(self, state, *args, **kwargs):
        return state
    return broken


def _step_that_leaves_half_behind(step):
    def broken(self, state, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        new = step(self, state, *args, **kwargs)
        C = state.z.shape[0]
        keep = jnp.arange(C) < C // 2

        def pick(old, upd):
            if jnp.ndim(old) >= 1 and old.shape[0] == C:
                return jnp.where(keep.reshape((C,) + (1,) * (old.ndim - 1)), old, upd)
            return upd
        return jax.tree_util.tree_map(pick, state, new)
    return broken


def _leapfrog_that_alters_its_potential(leapfrog):
    def broken(*args, **kwargs):
        z, r, pe = leapfrog(*args, **kwargs)
        return z, r, pe * 1.01
    return broken


@pytest.mark.parametrize("fault,target,attr,wrap", [
    ("frozen", "repro.infer.mcmc:NUTS", "fused_sample_step", _step_that_returns_its_state),
    ("half", "repro.infer.mcmc:NUTS", "fused_sample_step", _step_that_leaves_half_behind),
    ("altered", "repro.kernels.ops:", "leapfrog", _leapfrog_that_alters_its_potential),
])
def test_run_with_the_program_broken_is_not_correct(meter, monkeypatch, fault, target, attr, wrap):
    """A whole run minus the look for a chip, with the timed path broken
    underneath: each transition returns its state (`frozen`), half of the
    chains never move (`half`), or the integrator's potential is off by 1%
    where it is produced (`altered`)."""
    import importlib

    module, _, cls = target.partition(":")
    owner = importlib.import_module(module)
    owner = getattr(owner, cls) if cls else owner
    monkeypatch.setattr(owner, attr, wrap(getattr(owner, attr)))
    cell = _tiny_cell()
    record = run_mod.measure(cell, 2**31 + 77, 0.5, False, meter, None)
    out = run_mod.result_line(cell, record, DEVICE, False)
    assert out["correct"] is False, out["checked"]


def test_measure_end_to_end_and_compile_guard(meter, monkeypatch):
    cell = _tiny_cell()
    record = run_mod.measure(cell, 5, 0.5, False, meter, None)
    out = run_mod.result_line(cell, record, DEVICE, False)
    assert out["correct"], out["checked"]
    assert record["setup_s"] > 0 and out["attempted"] >= 1
    assert out["metrics"]["nuts_ess_per_s"]["value"] > 0
    assert list(out)[-1] == "checked"

    driver = cell.driver.Run
    window = driver.window

    def window_that_compiles(self, seconds):
        import jax

        jax.jit(lambda v: v * 3.0 + seconds)(np.float32(1.0)).block_until_ready()
        window(self, seconds)

    monkeypatch.setattr(driver, "window", window_that_compiles)
    with pytest.raises(RuntimeError, match="compiled inside the window"):
        run_mod.measure(cell, 6, 0.2, False, meter, None)
