"""In-program telemetry: named host spans, their counters, and the JAX
compile work done inside them.

    with telemetry.span("mcmc.run") as rec:
        ...
        rec["counters"]["grad_evals"] = evals   # device arrays stay on the device
    telemetry.spans("mcmc.run")[-1]

A span also opens ``jax.profiler.TraceAnnotation("repro." + name)``, so a
profiler trace shows it on the device trace's clock. Its record is a dict:
``id``, ``name``, ``parent`` (the id of the span open around it on the same
thread, or None), ``start`` and ``end`` (``time.perf_counter`` seconds),
``counters`` (whatever the code inside put there) and ``jax_events``.

``jax_events`` maps each ``/jax/core/compile/*`` event to the seconds spent
in it while the span was open, and ``/jax/compilation_cache/cache_hits`` to
a count. The module's JAX-monitoring listeners, registered on import (the
program's only ones), credit each event to the innermost open span of the
calling thread; a span hands its
totals to its parent when it closes, so a record counts its children's work
too. JAX reports work nested in other work as events of their own: a jit
traced inside another's trace, a Pallas kernel traced while its caller is
lowered. JAX also reports each event's start, so the listener keeps the
events in progress on a stack and credits each with its own seconds, its
duration less that of the events nested in it: the seconds of all events
add up to the time spent in any of them.

Closed records go into a deque of `MAX_SPANS`, oldest dropped first. A span
costs a few microseconds of host time with no profiler running, and never
waits for the device.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Deque, Iterator, List, Optional

import jax

MAX_SPANS = 4096
COMPILE_EVENTS = "/jax/core/compile/"
CACHE_HIT = "/jax/compilation_cache/cache_hits"

_records: Deque[dict] = collections.deque(maxlen=MAX_SPANS)
_ids = itertools.count(1)
_local = threading.local()


def _stack(name: str = "spans") -> list:
    """A per-thread stack: the open spans' records, or (``"jax"``) the JAX
    compile events in progress as [event, seconds nested in it]."""
    stack = getattr(_local, name, None)
    if stack is None:
        stack = []
        setattr(_local, name, stack)
    return stack


@contextlib.contextmanager
def span(name: str) -> Iterator[dict]:
    """Record the block as the span `name`; yields its record."""
    stack = _stack()
    record = {"id": next(_ids), "name": name,
              "parent": stack[-1]["id"] if stack else None,
              "start": time.perf_counter(), "end": None,
              "counters": {}, "jax_events": {}}
    stack.append(record)
    try:
        with jax.profiler.TraceAnnotation("repro." + name):
            yield record
    finally:
        record["end"] = time.perf_counter()
        stack.pop()
        if stack:
            _add(stack[-1]["jax_events"], record["jax_events"])
        _records.append(record)


def _add(into: dict, events: dict) -> None:
    for event, value in events.items():
        into[event] = into.get(event, 0) + value


def spans(name: Optional[str] = None) -> List[dict]:
    """The kept records of closed spans, oldest first; only those called
    `name` when it is given."""
    return [r for r in list(_records) if name is None or r["name"] == name]


def _on_start(event: str, value: float, **kwargs) -> None:
    if event.startswith(COMPILE_EVENTS):  # JAX reports a start as a scalar
        _stack("jax").append([event, 0.0])


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if not event.startswith(COMPILE_EVENTS):
        return
    running = _stack("jax")
    nested = running.pop()[1] if running and running[-1][0] == event else 0.0
    if running:
        running[-1][1] += duration
    spans_open = _stack()
    if spans_open:
        _add(spans_open[-1]["jax_events"], {event: duration - nested})


def _on_event(event: str, **kwargs) -> None:
    spans_open = _stack()
    if event == CACHE_HIT and spans_open:
        _add(spans_open[-1]["jax_events"], {CACHE_HIT: 1})


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
