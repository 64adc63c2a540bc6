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


@pytest.mark.parametrize("split,seed", [
    (FORDA, 1292441283), (FORDA, 2),
    (ELECTRICDEVICES, 0), (ELECTRICDEVICES, 1),
    (ELECTRICDEVICES, 1292441283)],
    ids=["forda-1292441283", "forda-2", "electricdevices-0",
         "electricdevices-1", "electricdevices-1292441283"])
def test_learned_support_equals_the_reference(split, seed):
    n_train, n_test, T = split
    ds = data.make_cbf(n_train, n_test, T, rng(seed, 0))
    sub = rng(seed, 1).choice(n_train, 32, replace=False)
    X = ds.X_train[np.sort(sub)]
    want = reference.learn_support(X, theta=8.0)
    got = np.asarray(learn_sparse_paths(jnp.asarray(X), theta=8.0).support)
    assert int((got != want).sum()) == 0
