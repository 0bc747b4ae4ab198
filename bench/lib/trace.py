"""From a JAX profiler trace to the numbers the per-layer metrics read.

`Tracer` records one window with `jax.profiler` and reduces the resulting
``.xplane.pb`` with `jax.profiler.ProfileData` (nothing but JAX). Device
planes are ``/device:<KIND>:<n>``; their ``XLA Ops`` line holds one event
per operation run on the device. Host planes hold the benchmark's own
spans (`jax.profiler.TraceAnnotation`, named ``bench.*``) on the same
clock, so an idle gap on the device is named by what the host was doing.

`reduce_events` is the pure arithmetic, kept apart so that the tests can
check it on a small recorded trace (`bench/data/trace_small.json`).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
NAMED_GAPS = 256
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+)+$")

Interval = Tuple[float, float]  # (start_s, end_s)


def op_family(name: str) -> str:
    """An op's instruction name without XLA's numeric suffixes: the event
    ``%fusion.12 = f32[8] fusion(...)`` is of the family ``fusion``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def self_times(events: List[Tuple[str, float, float]]) -> List[Tuple[str, float, float, float, bool]]:
    """(name, start, duration, self time, is a leaf) per event. Ops nest on
    the device (a ``while`` spans the ops of its body), so an op's own time
    is its duration less that of the ops directly inside it, and a leaf is
    an op with none inside it."""
    order = sorted(range(len(events)), key=lambda k: (events[k][1], -events[k][2]))
    child = [0.0] * len(events)
    leaf = [True] * len(events)
    stack: List[int] = []
    for k in order:
        _, s, d = events[k]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += d
            leaf[stack[-1]] = False
        stack.append(k)
    return [(n, s, d, d - c, f) for (n, s, d), c, f in zip(events, child, leaf)]


def union_length(intervals: Iterable[Interval], lo: float, hi: float) -> Tuple[float, List[Interval]]:
    """Length of the union of `intervals` clipped to [lo, hi], and the
    merged intervals themselves, sorted."""
    merged: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]],
                  window: Interval) -> dict:
    """The trace's numbers for one window.

    device_ops: {device id: [(op name, start_s, duration_s), ...]};
    host_spans: [(span name, start_s, duration_s), ...] from the host;
    window: the traced window on the same clock.

    Returns window_s, busy_s (the union of the intervals of leaf ops, those
    with no op inside them, averaged over the devices: the time a loop's
    own bookkeeping takes between the ops of its body counts as idle),
    op_seconds ({op name: summed self time over all devices}),
    device_ops (the 10 op families that took most time) and idle_gaps (the
    `NAMED_GAPS` longest idle gaps of device 0, summed by the innermost host
    span that covers each gap's middle, 10 largest)."""
    lo, hi = window
    busy, merged0 = [], None
    op_seconds: Dict[str, float] = {}
    for dev in sorted(device_ops):
        timed = self_times(device_ops[dev])
        length, merged = union_length(((s, s + d) for _, s, d, _, f in timed if f), lo, hi)
        busy.append(length)
        if merged0 is None:
            merged0 = merged
        for name, s, d, own, _ in timed:
            if s >= lo and s + d <= hi:
                op_seconds[name] = op_seconds.get(name, 0.0) + own
    families: Dict[str, float] = {}
    for name, sec in op_seconds.items():
        fam = op_family(name)
        families[fam] = families.get(fam, 0.0) + sec
    # name the longest idle gaps of device 0 by the innermost host event
    # covering each one's middle
    edges = [lo] + [x for iv in (merged0 or []) for x in iv] + [hi]
    idle = sorted(((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s),
                  reverse=True)[:NAMED_GAPS]
    spans = sorted(host_spans, key=lambda h: h[1])
    starts = [h[1] for h in spans]
    gaps: Dict[str, float] = {}
    for length, s, e in idle:
        mid = 0.5 * (s + e)
        covering = [(d, n) for n, hs, d in spans[:bisect.bisect_right(starts, mid)]
                    if mid <= hs + d]
        name = min(covering)[1] if covering else "(no host span)"
        gaps[name] = gaps.get(name, 0.0) + length
    top = sorted(families.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(busy),
        "op_seconds": op_seconds,
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": [[n, s] for n, s in top_gaps],
    }


def kernel_seconds(reduced: dict, pattern: str) -> Tuple[float, int]:
    """Summed device seconds of the ops whose event name matches the regular
    expression `pattern` (from its start), and how many op names matched."""
    rx = re.compile(pattern)
    hits = {n: s for n, s in reduced["op_seconds"].items() if rx.match(n)}
    return sum(hits.values()), len(hits)


def load_xplane(path: str):
    """(device_ops, host_spans) from an .xplane.pb, times in seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, list] = {}
    host_spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device_ops.setdefault(m.group(1), []).extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                host_spans.extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
    return device_ops, host_spans


class Tracer:
    """Trace one window into `logdir` and reduce it.

        with tracer.window():
            ...  # the traced work
        reduced = tracer.reduce()
    """

    def __init__(self, logdir: str):
        self.logdir = logdir

    @contextlib.contextmanager
    def window(self):
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        jax.profiler.start_trace(self.logdir)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        found = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"), recursive=True)
        if not found:
            return None
        device_ops, host_spans = load_xplane(found[0])
        shutil.rmtree(self.logdir, ignore_errors=True)
        windows = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
        if not windows or not device_ops:
            return None
        return reduce_events(device_ops, host_spans, windows[0])


def idle_percent(reduced: Optional[dict]) -> Optional[float]:
    """Percent of the traced window in which no op ran on the device."""
    if reduced is None or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
