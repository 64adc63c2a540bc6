"""Support learning against the benchmark's plain reference.

``learn_sparse_paths`` must learn, cell for cell, the support that
``bench/reference.learn_support`` learns from the same series: the
served SP-DTW is exact only on the support the check holds it to. The
support series are drawn as the benchmark draws them for its cells
(``bench/run.build``). At T = 500 float32 accumulated costs resolved
near-ties in backtracking differently and moved 8-10 cells across theta
at the FordA seeds below; float64 costs do not.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import data, reference  # noqa: E402
from bench.run import rng  # noqa: E402
from repro.core import learn_sparse_paths  # noqa: E402

# (n_train, n_test, T) of the UCR splits, as in bench/configs
FORDA = (3601, 1320, 500)
ELECTRICDEVICES = (8926, 7711, 96)


# seeds of the size the benchmark draws: each FordA seed costs some 8 s
# on the CPU
FORDA_SEEDS = (1292441283, 2, 3037000493, 2147483659, 3221225473,
               4294967311, 987654321123, 2305843009)
ELECTRICDEVICES_SEEDS = (0, 1, 1292441283)


@pytest.mark.parametrize(
    "split,seed",
    [(FORDA, s) for s in FORDA_SEEDS]
    + [(ELECTRICDEVICES, s) for s in ELECTRICDEVICES_SEEDS],
    ids=[f"forda-{s}" for s in FORDA_SEEDS]
    + [f"electricdevices-{s}" for s in ELECTRICDEVICES_SEEDS])
def test_learned_support_equals_the_reference(split, seed):
    n_train, n_test, T = split
    ds = data.make_cbf(n_train, n_test, T, rng(seed, 0))
    sub = rng(seed, 1).choice(n_train, 32, replace=False)
    X = ds.X_train[np.sort(sub)]
    want = reference.learn_support(X, theta=8.0)
    got = np.asarray(learn_sparse_paths(jnp.asarray(X), theta=8.0).support)
    assert int((got != want).sum()) == 0
