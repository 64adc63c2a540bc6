"""Latency percentiles, copied from the program's serving statistics
without its clamp: an empty sample has no percentile, and a tail is
reported only where at least ``MIN_BEYOND`` samples lie beyond it."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

MIN_BEYOND = 10


def percentile_ms(samples_s: Sequence[float], pct: float) -> Optional[float]:
    """The ``pct`` percentile of ``samples_s`` (seconds) in milliseconds,
    or None where fewer than ``MIN_BEYOND`` samples lie beyond it."""
    a = np.asarray(samples_s, np.float64) * 1e3
    if a.size * (100.0 - pct) / 100.0 < MIN_BEYOND:
        return None
    return float(np.percentile(a, pct))
