"""Reduction of a profiler trace to what the per-layer readers need.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``; on
each, the ``XLA Ops`` line holds one event per operation run on the chip
and the ``XLA Modules`` line one event per program execution (a jitted
function or an eager operation). The benchmark's own host spans are
``jax.profiler.TraceAnnotation`` events named ``bench.*`` on the host
planes, on the same clock.

Everything is measured inside the benchmark's ``bench.window`` span and,
where the trace holds several chips, averaged over them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
SEARCH_SPAN = "bench.search"

Interval = Tuple[int, int]          # (start_ns, end_ns)
Event = Tuple[int, int, str]        # (start_ns, end_ns, name)


@dataclasses.dataclass
class Device:
    """One chip's operations and program executions inside the window."""
    ops: List[Event]
    modules: List[Event]

    def busy(self) -> List[Interval]:
        """The union of the operation intervals, merged and sorted."""
        return merge([(s, e) for s, e, _ in self.ops])


@dataclasses.dataclass
class Trace:
    """The reduced trace: the window, the chips and the host spans."""
    window: Interval
    devices: List[Device]
    spans: List[Event]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def spans_named(self, name: str) -> List[Interval]:
        return [(s, e) for s, e, n in self.spans if n == name]

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        return float(np.mean([covered(d.busy(), [self.window])
                              for d in self.devices])) * 1e-9

    def idle_share_in(self, spans: Sequence[Interval]) -> Optional[float]:
        """Share of the time inside ``spans`` with no operation running,
        averaged over the chips; None where the spans are empty."""
        total = sum(e - s for s, e in spans)
        if total <= 0 or not self.devices:
            return None
        idle = [1.0 - covered(d.busy(), spans) / total for d in self.devices]
        return float(np.mean(idle))

    def module_time_s(self, pattern: str) -> float:
        """Device seconds of program executions whose name matches
        ``pattern``, averaged over the chips."""
        rx = re.compile(pattern)
        return float(np.mean([sum(e - s for s, e, n in d.modules
                                  if rx.search(n)) for d in self.devices])
                     ) * 1e-9

    def launches(self) -> float:
        """Program executions in the window, averaged over the chips."""
        return float(np.mean([len(d.modules) for d in self.devices]))

    def top_modules(self, n: int = 10) -> List[List]:
        """The ``n`` programs that took most device time: [name, s]."""
        tot: Dict[str, int] = defaultdict(int)
        for d in self.devices:
            for s, e, name in d.modules:
                tot[program_name(name)] += e - s
        k = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps of the first chip inside the window,
        each named by the innermost benchmark span open at its middle."""
        if not self.devices:
            return []
        gaps = complement(self.devices[0].busy(), self.window)
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((s + e) // 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]

    def span_at(self, t: int) -> str:
        """The shortest benchmark span (other than the window) open at t."""
        open_ = [(e - s, n) for s, e, n in self.spans
                 if s <= t < e and n != WINDOW_SPAN]
        return min(open_)[1] if open_ else "bench.outside_spans"


def program_name(name: str) -> str:
    """A program's name without the run-specific id the trace appends."""
    return re.sub(r"\(\d+\)$", "", name)


def merge(iv: Sequence[Interval]) -> List[Interval]:
    """Sorted union of intervals."""
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(busy: Sequence[Interval], spans: Sequence[Interval]) -> int:
    """Nanoseconds of ``spans`` (merged) covered by ``busy`` (merged)."""
    if not busy:
        return 0
    b = np.asarray(busy, np.int64)
    tot = 0
    for lo, hi in merge(spans):
        tot += int(np.clip(np.minimum(b[:, 1], hi) - np.maximum(b[:, 0], lo),
                           0, None).sum())
    return tot


def complement(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The gaps of ``window`` that ``busy`` (merged) leaves."""
    lo, hi = window
    out, t = [], lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _events(line, lo: int, hi: int) -> List[Event]:
    out = []
    for ev in line.events:
        s = int(ev.start_ns)
        e = s + int(ev.duration_ns)
        if e > lo and s < hi:
            out.append((max(s, lo), min(e, hi), ev.name))
    return out


def reduce(profile) -> Optional[Trace]:
    """Reduce a ``ProfileData`` to a ``Trace``; None without a window span
    or without a device plane."""
    spans: List[Event] = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    devices = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = _events(lines[OPS_LINE], lo, hi) if OPS_LINE in lines else []
        mods = _events(lines[MODULES_LINE], lo, hi) \
            if MODULES_LINE in lines else []
        devices.append(Device(ops=ops, modules=mods))
    if not devices:
        return None
    return Trace(window=(lo, hi), devices=devices, spans=spans)


def load(log_dir: str) -> Optional[Trace]:
    """Reduce the newest ``.xplane.pb`` under a profiler log directory."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    return reduce(ProfileData.from_file(files[-1]))
