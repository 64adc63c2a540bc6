"""Host spans and counters on the profiler's clock.

``span(name, **attrs)`` opens a ``jax.profiler.TraceAnnotation`` — so the
span lands on the profiler's host plane whenever a trace is being taken —
and records ``(name, start_ns, end_ns, span_id, parent_id, attrs)`` in a
bounded ring (oldest dropped). Timestamps come from ``time.time_ns()``,
the clock the profiler stamps its events with; a trace file holds them
less the profile's start, which it records as ``profile_start_time``, so
a recorded interval shifted by that start lies on the device trace.
``parent_id`` is the
enclosing span (0 at the top), so every span of one served batch hangs
off that batch's ``search`` span.

``count(name, value)`` adds to a named counter. A value may be a device
scalar: values are kept unsummed and reduced only when read, so counting
never waits on the chip.

Every program JAX compiles or loads from its compile cache is recorded
as a ``compile`` span (end = when JAX reports it, start = end minus the
reported duration) and counted under ``compile.programs``.

Spans and counters go to the active ``Recorder``: a process-wide default,
or the one a ``recording(rec)`` block activates. Span only host code
that runs the work: inside a ``jit`` or ``shard_map`` trace a span would
time the tracing, not the running.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import time
from collections import deque
from typing import Dict, List

import jax
import numpy as np

RING = 65536
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPAN_FIELDS = ("name", "start_ns", "end_ns", "id", "parent", "attrs")
_FOLD = 1024     # pending values per counter before older ones are summed


def _total(values) -> float | int:
    return sum(np.asarray(v).item() for v in jax.device_get(values))


class Recorder:
    """A bounded ring of spans and a table of counters."""

    def __init__(self, capacity: int = RING):
        self.spans: deque = deque(maxlen=capacity)
        self._counts: Dict[str, List] = {}

    def count(self, name: str, value) -> None:
        vals = self._counts.setdefault(name, [])
        vals.append(value)
        if len(vals) > _FOLD:
            # the older values belong to batches already read back, so
            # fetching them does not wait on the chip
            vals[:-1] = [_total(vals[:-1])]

    def snapshot(self) -> dict:
        """{"spans": [{name, start_ns, end_ns, id, parent, attrs}, ...],
        "counters": {name: total}}; reading the counters fetches them."""
        return {"spans": [dict(zip(SPAN_FIELDS, s)) for s in self.spans],
                "counters": {k: _total(v) for k, v in self._counts.items()}}


_ids = itertools.count(1)
_DEFAULT = Recorder()
# (active recorder, id of the innermost open span)
_active = contextvars.ContextVar("repro_tracing", default=(_DEFAULT, 0))


@contextlib.contextmanager
def recording(rec: Recorder):
    """Send the spans and counters of the block to ``rec``."""
    token = _active.set((rec, 0))
    try:
        yield rec
    finally:
        _active.reset(token)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the block as a span of the active recorder; yields its id."""
    rec, parent = _active.get()
    sid = next(_ids)
    token = _active.set((rec, sid))
    start = time.time_ns()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield sid
    finally:
        end = time.time_ns()
        _active.reset(token)
        rec.spans.append((name, start, end, sid, parent, attrs))


def count(name: str, value) -> None:
    """Add ``value`` (a number or a device scalar) to a named counter."""
    _active.get()[0].count(name, value)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    rec, parent = _active.get()
    end = time.time_ns()
    rec.spans.append(("compile", end - int(duration_secs * 1e9), end,
                      next(_ids), parent,
                      {"program": kwargs.get("fun_name", "")}))
    rec.count("compile.programs", 1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
