"""The plain reference against the definitions and against the served
cascade, and the control (the reference in bfloat16) against the limit."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, data, reference, run
from bench.tests import tiny


def _loop_dp(x, y, support):
    """The SP-DTW recurrence cell by cell (float64)."""
    T = len(x)
    D = np.full((T, T), np.inf)
    for i in range(T):
        for j in range(T):
            if not support[i, j]:
                continue
            prev = 0.0 if i == j == 0 else min(
                D[i - 1, j] if i else np.inf, D[i, j - 1] if j else np.inf,
                D[i - 1, j - 1] if i and j else np.inf)
            D[i, j] = (x[i] - y[j]) ** 2 + prev
    return D[-1, -1]


@pytest.fixture(scope="module")
def small():
    ds = data.make_cbf(40, 8, 20, np.random.default_rng(5))
    support = reference.learn_support(ds.X_train[:8], theta=1.0)
    return ds, support


def test_distances_follow_the_recurrence(small):
    ds, support = small
    Q, C = ds.X_test[:3], ds.X_train[:5]
    D = reference.distances(Q, C, support, block_q=2)
    want = np.array([[_loop_dp(q, c, support) for c in C] for q in Q])
    np.testing.assert_allclose(D, want, rtol=1e-5)


def test_support_has_corners_and_a_path(small):
    _, support = small
    assert support[0, 0] and support[-1, -1]
    assert reference._feasible(support)
    assert support.sum() < support.size


def test_support_matches_the_programs(small):
    from repro.core import learn_sparse_paths
    ds, support = small
    sp = learn_sparse_paths(jnp.asarray(ds.X_train[:8]), theta=1.0)
    np.testing.assert_array_equal(np.asarray(sp.support), support)


@pytest.mark.parametrize("traffic", [tiny.OFFLINE, tiny.OPEN],
                         ids=["offline", "open"])
def test_served_cascade_agrees_with_reference(traffic):
    r = run.run_cell(tiny.cell(traffic), 2 ** 40 + 7, 1.0, False,
                     time.perf_counter())
    assert r["correct"], r["check"]
    assert r["check"]["answer_err"]["value"] < 1e-5
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("queries", ["retrieval", "classify"])
def test_control_is_not_correct(queries):
    cell = {"config": tiny.CONFIG, "traffic": {"queries": queries}}
    for seed in (1, 2, 3):
        assert control.control_err(cell, seed) > \
            check.LIMITS["answer_err"]


def test_answer_err_reads_wrong_ids_and_distances():
    D = np.array([[3.0, 1.0, 2.0], [5.0, 4.0, 4.5]])
    assert check.answer_err(np.array([1, 1]), np.array([1.0, 4.0]), D) == 0
    assert check.answer_err(np.array([2, 1]), np.array([2.0, 4.0]), D) \
        == pytest.approx(1.0)
    assert check.answer_err(np.array([1, 1]), np.array([1.0, 4.4]), D) \
        == pytest.approx(0.1)
    assert check.answer_err(np.array([1, 3]), np.array([1.0, 4.0]), D) \
        == check.INVALID
    assert check.answer_err(np.array([1, 1]), np.array([1.0, np.nan]), D) \
        == check.INVALID
