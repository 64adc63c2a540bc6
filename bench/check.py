"""The comparison that decides ``correct``.

A sample of the requests answered in the window, drawn from the seed, is
answered again by the plain reference (``bench.reference``) over the
whole corpus, on a support the reference learns itself. One number is
compared with its limit:

``answer_err``: over the sample, the largest of
    |served distance - reference best| / reference best and
    (reference distance of the served id - reference best) / reference best.
It reads 1e30 for an id outside the corpus, a distance that is not
finite, or a missing answer.
A wrong neighbour, a wrong distance, a bound that drops the true
neighbour, or a shard merge that loses it, all show here; a tie broken
the other way does not.

The limit was set between the readings of sound runs and of the control
(the reference in bfloat16 put in the program's place); PERF.md gives
both readings.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench import reference

LIMITS = {"answer_err": 2e-4}
SAMPLE = 128
INVALID = 1e30


def sample_rows(n: int, rng: np.random.Generator) -> np.ndarray:
    """Rows of the window's requests to check, in order."""
    return np.sort(rng.choice(n, size=min(n, SAMPLE), replace=False))


def answer_err(nn: np.ndarray, dist: np.ndarray, D_ref: np.ndarray) -> float:
    """The worst relative error of served (nn, dist) rows against the
    reference's (rows, corpus) distance matrix ``D_ref``."""
    nn = np.asarray(nn)
    dist = np.asarray(dist, np.float64)
    D_ref = np.asarray(D_ref, np.float64)
    if nn.shape != (len(D_ref),) or dist.shape != nn.shape:
        return INVALID
    ok = (nn >= 0) & (nn < D_ref.shape[1]) & np.isfinite(dist)
    if not ok.all():
        return INVALID
    best = D_ref.min(axis=1)
    scale = np.maximum(best, 1e-30)
    at_nn = D_ref[np.arange(len(nn)), nn]
    err = np.maximum(np.abs(dist - best), at_nn - best) / scale
    err = float(err.max())
    return err if np.isfinite(err) else INVALID


def compare(served, corpus: np.ndarray, support_series: np.ndarray,
            theta: float, rng: np.random.Generator) -> Dict[str, dict]:
    """Numbers compared, each with its limit: {name: {value, limit}}."""
    rows = sample_rows(len(served.nn), rng)
    support = reference.learn_support(support_series, theta)
    D_ref = reference.distances(served.queries[rows], corpus, support)
    err = answer_err(served.nn[rows], served.dist[rows], D_ref)
    return {"answer_err": {"value": err, "limit": LIMITS["answer_err"]}}


def passed(numbers: Dict[str, dict]) -> bool:
    """True when every number is at or below its limit."""
    return all(v["value"] <= v["limit"] for v in numbers.values())
