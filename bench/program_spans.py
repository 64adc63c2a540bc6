"""The program's own spans and counters, read from
``SearchEngine.stats()["trace"]`` (``repro.tracing``).

The program stamps its spans with the profiler's clock (``time.time_ns``,
nanoseconds since the epoch); the trace file holds the same clock less
the profile's start. Each program ``search`` span runs inside one
``bench.search`` span, so every pair bounds that start from both sides:
``intervals`` takes the middle of the bounds, which is off by at most
half the host time between the two spans' ends, and maps the program's
spans onto the trace. Spans are clipped to ``bench.window``, and device
idle time is averaged over the chips. A program that records no spans
(``stats()`` without ``trace``) reads as None.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np

from bench.trace import SEARCH_SPAN, Interval, covered, merge

PROGRAM_SEARCH = "search"


def recorded(ctx) -> Optional[dict]:
    """The program's ``{"spans": [...], "counters": {...}}``, or None."""
    return (ctx.stats or {}).get("trace")


def trace_start_ns(ctx) -> Optional[int]:
    """The profile's start on the program's clock, from the pairs of
    ``bench.search`` and program ``search`` spans; None where they do not
    pair up one to one or bound no common start."""
    rec = recorded(ctx)
    if ctx.trace is None or rec is None:
        return None
    outer = sorted(ctx.trace.spans_named(SEARCH_SPAN))
    inner = sorted((s["start_ns"], s["end_ns"]) for s in rec["spans"]
                   if s["name"] == PROGRAM_SEARCH)
    if not outer or len(outer) != len(inner):
        return None
    lo = max(e - b for (_, b), (_, e) in zip(outer, inner))
    hi = min(s - a for (a, _), (s, _) in zip(outer, inner))
    return None if lo > hi else (lo + hi) // 2


def intervals(ctx, names: Iterable[str]) -> Optional[List[Interval]]:
    """The program's spans named in ``names`` on the trace's clock,
    clipped to the window; None without a trace, without program spans
    or without a start to map them by."""
    start = trace_start_ns(ctx)
    if start is None:
        return None
    names = set(names)
    lo, hi = ctx.trace.window
    out = [(max(s["start_ns"] - start, lo),
            min(s["end_ns"] - start, hi))
           for s in recorded(ctx)["spans"] if s["name"] in names]
    return [(s, e) for s, e in out if e > s]


def idle_ms_per_batch(ctx, names: Iterable[str]) -> Optional[float]:
    """Device idle ms per traced batch while the host was inside the
    program's spans named in ``names`` (0 where there are none)."""
    spans = intervals(ctx, names)
    n = ctx.traced_batches()
    if spans is None or not n or not ctx.trace.devices:
        return None
    span_ns = sum(e - s for s, e in merge(spans))
    idle = np.mean([span_ns - covered(d.busy(), spans)
                    for d in ctx.trace.devices])
    return 1e-6 * float(idle) / n
