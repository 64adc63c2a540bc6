"""Open loop: requests arrive on a Poisson schedule fixed in the
traffic file, whether or not the server keeps up.

Each request is timed from when it was due, so a stall delays every
request behind it. The server is one host loop: at each turn it takes
every request that is due (up to one batch), pads the batch to its fixed
shape with copies of the last query, and serves it. Requests due inside
the window are all served; those still queued at the window's end are
drained and counted.
"""
from __future__ import annotations

import time
from typing import Callable

import numpy as np

from bench.traffic import Served


def arrivals(n: int, rate_qps: float, rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of ``n`` Poisson arrivals.

    The gaps are the ``n`` midpoint quantiles of the exponential law of
    mean 1 / rate, in an order drawn from ``rng``: every seed offers the
    same set of gaps, so the same total load, in another order."""
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate_qps)
    return np.cumsum(gaps)


def drive(serve: Callable, queries: np.ndarray, traffic: dict,
          seconds: float, rng: np.random.Generator, span,
          clock=time.perf_counter, sleep=time.sleep) -> Served:
    """Serve ``round(rate * seconds)`` requests due over ``seconds``."""
    batch = int(traffic["batch"])
    n = max(1, int(round(traffic["rate_qps"] * seconds)))
    due = arrivals(n, float(traffic["rate_qps"]), rng)
    done = np.empty(n)
    nn = np.empty(n, np.int64)
    dist = np.empty(n)
    n_batches = served = 0
    t0 = clock()
    while served < n:
        now = clock() - t0
        ready = int(np.searchsorted(due, now, side="right"))
        if ready == served:
            with span("bench.wait"):
                sleep(max(due[served] - now, 0.0))
            continue
        take = min(batch, ready - served)
        Q = queries[served:served + take]
        if take < batch:
            Q = np.concatenate([Q, np.repeat(Q[-1:], batch - take, axis=0)])
        with span("bench.search"):
            b_nn, b_dist = serve(Q)
        done[served:served + take] = clock() - t0
        nn[served:served + take] = b_nn[:take]
        dist[served:served + take] = b_dist[:take]
        served += take
        n_batches += 1
    return Served(nn=nn, dist=dist, latency_s=done - due, n_batches=n_batches,
                  elapsed_s=float(max(done[-1], seconds)), queries=queries[:n])
