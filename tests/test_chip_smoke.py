"""`chip_smoke.py` rehearsed off the chip: every phase at tiny sizes on the
CPU with the Pallas kernels in interpret mode, the four-chip phase on four
virtual CPU devices, and the script's refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY = dict(
    lm_full=False, lm_batch=2, lm_seq=128, schools=8, chains=8, warmup=100,
    draws=100, hmm_model_T=12, hmm_T=24, hmm_K=4, enum_steps=2, kalman_T=16,
    particles=1024, smc_T=12, smc_runs=4, serve_rows=(1, 2, 3, 5),
    mesh_chains=8, mesh_particles=1024,
)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    # off the TPU the planner rolls chains into a scan; pin the tree
    # lowering the chip takes, so the enum/gaussian phases reach the kernels
    monkeypatch.setenv("REPRO_ENUM_CHAIN_LOWER", "tree")


@pytest.mark.parametrize("name", [name for name, _ in chip_smoke.PHASES])
def test_phase_at_tiny_size(name, kernels_interpreted):
    check = dict(chip_smoke.PHASES)[name](chip_smoke.Sizes(**TINY))
    json.dumps(check)  # each phase line is printed as JSON


def test_phase_line_reports_compiles(capsys):
    import jax

    chip_smoke.run_phase("add", lambda sz: {"v": float(jax.jit(lambda a: a + 1)(1.0))},
                         chip_smoke.Sizes(**TINY))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "add" and line["check"] == {"v": 2.0}
    assert line["compile_s"] >= 0 and line["cache_hits"] >= 0
    from repro import telemetry

    assert telemetry.spans("chip_smoke.add")[-1]["jax_events"], "no compile event seen"


def test_compile_meter_attributes_cache_hits(capsys):
    """A phase's line counts the compile seconds and cache hits that JAX
    reports while the phase runs, and nothing from before it."""
    from repro import telemetry

    telemetry._on_event(telemetry.CACHE_HIT)  # outside any phase: no one's

    def phase(sz):
        telemetry._on_event(telemetry.CACHE_HIT)
        telemetry._on_duration(chip_smoke.BACKEND_COMPILE, 3.0, fun_name="hit")
        return {}

    chip_smoke.run_phase("hits", phase, chip_smoke.Sizes(**TINY))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["cache_hits"] == 1
    assert line["compile_s"] == pytest.approx(3.0)


def test_mesh_phase_on_four_virtual_devices():
    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        "REPRO_KERNEL_BACKEND": "interpret",
    }
    code = (
        "import json, chip_smoke as cs; "
        f"print(json.dumps(cs.phase_mesh(cs.Sizes(**{TINY!r}))))"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["mcmc"]["spanned"] == 4 and out["smc"]["spanned"] == 4
    assert out["svi"]["all_reduces"] > 0


def _refused(cmd, cwd) -> bool:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True,
                       timeout=300)
    return r.returncode != 0 and '"ok"' not in r.stdout


def test_refuses_a_non_tpu_platform():
    assert _refused([sys.executable, str(REPO / "chip_smoke.py")], REPO)


def test_refuses_to_run_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    assert _refused([sys.executable, "chip_smoke.py"], tmp_path)
