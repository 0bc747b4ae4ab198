"""The per-layer metrics that read the program's own telemetry
(`bench/lib/spans.py`), on synthetic records: the window's `mcmc.run` spans
are the last ``counters["inferences"]`` of them, the set-up's warm one is
the span just before, and a program without the telemetry reports nothing."""
from __future__ import annotations

import collections
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.append(str(BENCH))  # for the readers' `lib` imports

from repro import telemetry  # noqa: E402

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# event names as a v5e trace gives them (operands cut): the kernel, and an
# op reshaping one of its outputs, both with the kernel's metadata
KERNEL_EVENT = ('%closed_call.402 = (f32[1024,10]{1,0:T(8,128)S(1)}, s32[1024,1]{1,0:T(8,128)S(1)}) '
                'custom-call(f32[1024,10]{1,0:T(8,128)S(1)} %get-tuple-element.27096), '
                'custom_call_target="tpu_custom_call", operand_layout_constraints={f32[1024,10]{1,0}}, '
                'frontend_attributes={kernel_metadata={\n"name":"repro.leapfrog"\n}}')
RESHAPE_EVENT = ('%reduce.1142 = f32[1024]{0:T(1024)S(1)} reduce(f32[1024,1]{1,0:T(8,128)S(1)} '
                 '%pallas_call.734), dimensions={1}, frontend_attributes={kernel_metadata={\n'
                 '"name":"repro.leapfrog"\n}}')
UNNAMED_KERNEL = '%closed_call.7 = (f32[8,10]) custom-call(%a), custom_call_target="tpu_custom_call"'


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def fresh_telemetry(monkeypatch):
    monkeypatch.setattr(telemetry, "_records", collections.deque(maxlen=telemetry.MAX_SPANS))


def _run_span(steps, evals, events=None):
    """One `mcmc.run` record as `MCMC.run` leaves it: per-chain counters."""
    with telemetry.span("mcmc.run") as rec:
        rec["counters"].update(leapfrog_steps=np.asarray(steps, np.int32),
                               grad_evals=np.asarray(evals, np.int32),
                               leapfrog_calls=np.int32(1023))
    rec["jax_events"] = dict(events or {})
    return rec


def _record(inferences, op_seconds=None):
    return {"counters": {"inferences": inferences, "flops_per_step": 129,
                         "bytes_per_step": 212, "span_s": 1.0},
            "trace": None if op_seconds is None else {"op_seconds": op_seconds},
            "peaks": PEAKS}


def _fill():
    _run_span([99, 99], [1, 1], {TRACE: 50.0})            # an older run: ignored
    _run_span([1, 1], [9, 9], {TRACE: 6.0, LOWER: 2.5, COMPILE: 3.0})  # the warm one
    _run_span([3, 5], [1000, 1000])
    _run_span([2, 6], [1000, 1000])


def test_grad_useful_share_reads_the_window(fresh_telemetry):
    _fill()
    read = _reader("nuts.grad_useful_share")
    assert read(_record(2)) == pytest.approx(100.0 * 16 / 4000)
    assert read(_record(1)) == pytest.approx(100.0 * 8 / 2000)


def test_trace_seconds_read_the_warm_span(fresh_telemetry):
    _fill()
    read = _reader("setup.trace_s")
    assert read(_record(2)) == pytest.approx(8.5)  # trace + lowering, no compile
    assert read(_record(3)) == pytest.approx(50.0)
    assert read(_record(4)) is None  # no span before the window's


def test_counted_roofline_uses_the_program_counts(fresh_telemetry):
    _fill()
    read = _reader("leapfrog_roofline.counted")
    steps = 16
    least = max(steps * 129 / PEAKS["bf16_flops"], steps * 212 / PEAKS["hbm_bytes_per_s"])
    events = {KERNEL_EVENT: 2.0, RESHAPE_EVENT: 0.5, UNNAMED_KERNEL: 1.0,
              "%fusion.3 = f32[8]": 5.0}  # only the named kernel counts
    assert read(_record(2, events)) == pytest.approx(100.0 * least / 2.0)
    assert read(_record(2, {UNNAMED_KERNEL: 1.0, RESHAPE_EVENT: 0.5})) is None
    assert read(_record(2)) is None  # no trace


@pytest.mark.parametrize("name", ["nuts.grad_useful_share", "leapfrog_roofline.counted",
                                  "setup.trace_s"])
def test_readers_report_nothing_without_telemetry(fresh_telemetry, monkeypatch, name):
    read = _reader(name)
    record = _record(1, {KERNEL_EVENT: 1.0})
    assert read(record) is None  # no spans at all
    _run_span([1], [2])
    with telemetry.span("mcmc.run"):
        pass  # a run without counters
    if name != "setup.trace_s":
        assert read(record) is None
    import repro

    monkeypatch.delattr(repro, "telemetry")  # a program without the module
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert read(_record(1, {KERNEL_EVENT: 1.0})) is None
