"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name. `BENCHMARK.json` names the cell's
configuration and traffic; the configuration is ``bench/configs/<config>.json``
(sizes, limits) with ``<config>.py`` beside it (model, data from the seed,
the float64 reference, the work counts); the traffic is
``bench/traffic/<traffic>.json``, whose ``driver`` names
``bench/drivers/<driver>.py``; each metric is read by
``bench/metrics/<metric>.py``. Adding a configuration, a cell or a metric
adds files and entries and edits none.

A run: set-up (data from the seed, the engine, one warm call at the cell's
exact shapes, with JAX's persistent compilation cache at
``<checkout>/.bench_cache/jax``), then the window of ``--seconds``, then the
check against the reference. With ``--trace 1`` a shorter window
(the traffic's ``trace_seconds``) runs under the profiler and the per-layer
metrics are reported instead of the end-to-end ones. Nothing may compile
inside the window: a compile there, or a retrace of the engine, fails the
run. Without a TPU, or with fewer chips than the cell asks for, the run
prints no result and exits non-zero.

The last line of standard output is the result as one JSON object; the
numbers the check compared, each beside its limit, are the last lines of
standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CACHE = CHECKOUT / ".bench_cache"


class Refused(Exception):
    """The run cannot be measured here; no result is printed."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise Refused(f"missing {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path}")
    return json.loads(path.read_text())


class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench if bench is not None else read_json(CHECKOUT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise Refused(f"no workload {name!r} in BENCHMARK.json")
        self.workload = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        entry = configs[self.workload["config"]]
        cfg_file = CHECKOUT / entry["file"]
        self.spec = read_json(cfg_file)
        self.config = load_module(cfg_file.with_suffix(".py"), f"bench_config_{cfg_file.stem}")
        self.traffic = read_json(BENCH / "traffic" / f"{self.workload['traffic']}.json")
        self.driver = load_module(BENCH / "drivers" / f"{self.traffic['driver']}.py",
                                  f"bench_driver_{self.traffic['driver']}")
        self.end_to_end = [m for m in bench["end_to_end"] if self._reports(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)]

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def reader(self, metric: str):
        return load_module(BENCH / "metrics" / f"{metric}.py", f"bench_metric_{metric}")

    def make_run(self, seed: int):
        return self.driver.Run(self.config, self.spec, self.traffic, seed)


def prepare() -> None:
    """Before JAX starts: keep its persistent compilation cache at the fixed
    path inside the checkout, so that every run of this checkout finds what
    the first one compiled, and put the program and `bench/` on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    for path in (CHECKOUT / "src", BENCH):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX sees {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def peaks_for(kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def measure(cell: Cell, seed: int, seconds: float, trace: bool, meter, logdir: Path) -> dict:
    """Set-up, window, check: the record the metric readers read.
    Device-independent: the look for a chip is the caller's."""
    from lib.trace import Tracer

    run = cell.make_run(seed)
    t_setup = time.perf_counter()
    run.setup()
    setup_s = time.perf_counter() - T_START
    before = meter.snapshot()
    traces = run.num_traces
    tracer = None
    if trace:
        tracer = Tracer(str(logdir))
        with tracer.window():
            run.window(cell.traffic["trace_seconds"])
    else:
        run.window(seconds)
    after = meter.snapshot()
    if after["compiles"] != before["compiles"] or run.num_traces != traces:
        raise RuntimeError(
            f"compiled inside the window: {after['compiles'] - before['compiles']} "
            f"compiles, {run.num_traces - traces} retraces")
    # the peak is read before the reference runs, and the program's state is
    # freed (`finish`) before it does
    record = {"setup_s": setup_s, "setup_compile_s": before["compile_s"],
              "setup_phases": dict(run.setup_phases, start_s=t_setup - T_START,
                                   compile_s=before["compile_s"],
                                   compiles=before["compiles"], cache_hits=before["cache_hits"]),
              "memory_peak_bytes": memory_peak_bytes(cell.workload["chips"])}
    run.finish()
    record["counters"] = run.counters
    record["trace"] = tracer.reduce() if tracer else None
    record["checks"] = run.check()
    return record


def result_line(cell: Cell, record: dict, device: dict, trace: bool) -> dict:
    metrics = {}
    record = dict(record, peaks=peaks_for(device["kind"]))
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.reader(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = record["checks"]
    c = record["counters"]
    out = {
        "correct": all(v <= lim for _, v, lim in checks),
        "attempted": c.get("inferences", c.get("sweeps", 0)),
        "failed": 0,
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=record["memory_peak_bytes"]),
    }
    if trace:
        tr = record["trace"]
        if tr is None:
            raise RuntimeError("the traced window recorded no device operation")
        out["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    out["checked"] = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(args.workload)
        prepare()
        device = device_info(cell.workload["chips"])
        peaks_for(device["kind"])
        from lib.compile_meter import CompileMeter

        meter = CompileMeter()
        record = measure(cell, args.seed, args.seconds, bool(args.trace), meter,
                         CACHE / "trace" / args.workload)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = result_line(cell, record, device, bool(args.trace))
    print(f"setup {json.dumps(record['setup_phases'])}", file=sys.stderr)
    for name, v in out["checked"].items():
        print(f"check {name} = {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
