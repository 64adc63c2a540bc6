"""Offline loop: full batches back to back until the window closes.

The batch in flight when the window closes is finished and counted, with
its time: throughput is every query answered over the whole time taken.
"""
from __future__ import annotations

import time
from typing import Callable

import numpy as np

from bench.traffic import Served


def drive(serve: Callable, queries: np.ndarray, traffic: dict,
          seconds: float, rng: np.random.Generator, span,
          clock=time.perf_counter, sleep=time.sleep) -> Served:
    """Cycle ``queries`` in batches of ``traffic["batch"]``."""
    batch = int(traffic["batch"])
    n_pool = len(queries) // batch
    nn, dist, rows = [], [], []
    t0 = clock()
    while clock() - t0 < seconds:
        k = len(nn) % n_pool
        Q = queries[k * batch:(k + 1) * batch]
        with span("bench.search"):
            b_nn, b_dist = serve(Q)
        nn.append(np.asarray(b_nn))
        dist.append(np.asarray(b_dist))
        rows.append(k)
    elapsed = clock() - t0
    served = np.concatenate([queries[k * batch:(k + 1) * batch]
                             for k in rows])
    return Served(nn=np.concatenate(nn), dist=np.concatenate(dist),
                  latency_s=None, n_batches=len(nn), elapsed_s=elapsed,
                  queries=served)
