"""Loop runners, one module per ``loop`` named in a traffic file."""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Served:
    """What a window served: one row per request, in order of arrival."""
    nn: np.ndarray            # (n,) returned neighbour ids
    dist: np.ndarray          # (n,) returned distances
    latency_s: Optional[np.ndarray]   # (n,) completion minus due time
    n_batches: int
    elapsed_s: float          # the window, up to the last completion
    queries: np.ndarray       # (n, T) the requests' series
