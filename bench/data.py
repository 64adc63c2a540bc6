"""Corpus and query generation for the benchmark, from the run's seed.

The benchmark's own copies of the program's synthetic-UCR generator
(Cylinder-Bell-Funnel, Saito 1994) and of its retrieval warp, so that a
change to the program cannot change the data it is measured on. The CBF
draw is vectorised; its distribution is the one of the program's
``repro.data.synthetic_ucr.make_cbf``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Dataset:
    """A train split (the served corpus) and a held-out test split."""
    X_train: np.ndarray   # (n_train, T) float32, z-normalised
    y_train: np.ndarray   # (n_train,) int32
    X_test: np.ndarray
    y_test: np.ndarray


def _znorm(X: np.ndarray) -> np.ndarray:
    mu = X.mean(axis=1, keepdims=True)
    sd = X.std(axis=1, keepdims=True) + 1e-8
    return ((X - mu) / sd).astype(np.float32)


def make_cbf(n_train: int, n_test: int, T: int,
             rng: np.random.Generator) -> Dataset:
    """Cylinder (0), bell (1) and funnel (2): a plateau of amplitude
    6 + N(0, 1) on [a, b], flat, rising or falling, plus unit noise."""
    n = n_train + n_test
    t = np.arange(T)[None, :]
    y = rng.integers(0, 3, size=n)
    a = rng.integers(T // 8, T // 3, size=n)[:, None]
    b = np.minimum(a + rng.integers(T // 4, T // 2, size=n)[:, None], T - 1)
    amp = 6 + rng.normal(size=n)[:, None]
    noise = rng.normal(size=(n, T))
    on = (t >= a) & (t <= b)
    span = np.maximum(b - a, 1)
    shape = np.where(y[:, None] == 0, 1.0,
                     np.where(y[:, None] == 1, (t - a) / span, (b - t) / span))
    X = amp * on * shape + noise
    order = rng.permutation(n)
    X, y = X[order], y[order].astype(np.int32)
    return Dataset(_znorm(X[:n_train]), y[:n_train],
                   _znorm(X[n_train:]), y[n_train:])


def retrieval_queries(corpus: np.ndarray, n: int,
                      rng: np.random.Generator) -> np.ndarray:
    """Warped, renoised corpus entries: each query is a corpus series
    resampled through a monotone jitter of +-3 steps, plus N(0, 0.1)
    noise, z-normalised. Its true neighbour is close, so the bounds prune
    hard (the similarity-search case)."""
    T = corpus.shape[1]
    src = rng.integers(0, len(corpus), n)
    jitter = rng.integers(-3, 4, size=(n, T))
    idx = np.sort(np.clip(np.arange(T)[None, :] + jitter, 0, T - 1), axis=1)
    Q = corpus[src[:, None], idx] + 0.1 * rng.normal(size=(n, T))
    return _znorm(Q)


def classify_queries(ds: Dataset, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    """Held-out test-split series in a seeded order, cycled to ``n``."""
    order = rng.permutation(len(ds.X_test))
    reps = -(-n // len(order))
    return ds.X_test[np.tile(order, reps)[:n]]


QUERY_SOURCES = {"retrieval": lambda ds, n, rng: retrieval_queries(
                     ds.X_train, n, rng),
                 "classify": classify_queries}
