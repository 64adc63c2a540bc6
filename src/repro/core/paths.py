"""Optimal alignment-path extraction (backtracking) for occupancy learning.

The paper's occupancy grid (Section III, Fig. 3-b) needs, for every training
pair, the set of cells visited by *the* optimal DTW path. ``backtrack``
walks a float32 accumulated-cost matrix with a fixed-length ``lax.scan``
(2T-1 steps max) so it jits and vmaps over pairs; ``optimal_path_masks64``
is the host path that learning uses: float64 costs, so that near-ties
between predecessors resolve as they do in exact arithmetic and not by
float32 rounding (at T = 500 the two disagree on enough cells to move a
learned support).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .dtw import INF, _dp_rows, dtw_matrix


def backtrack(D: jnp.ndarray) -> jnp.ndarray:
    """Boolean (Tx, Ty) mask of the optimal path through accumulated costs D.

    Tie convention: when predecessors are equal the move resolves as
    diag > up > left (diagonal preferred, then the vertical step). Both
    preferred branches decrement ``i``, so the row update only needs the
    combined ``best != left``-exclusive test; the column update keeps the
    two-way split (diag and left decrement ``j``, up does not).
    """
    Tx, Ty = D.shape
    n_steps = Tx + Ty - 2  # max path length minus the start cell

    def step(carry, _):
        i, j = carry
        up = jnp.where(i > 0, D[i - 1, j], INF)
        left = jnp.where(j > 0, D[i, j - 1], INF)
        diag = jnp.where((i > 0) & (j > 0), D[i - 1, j - 1], INF)
        best = jnp.minimum(jnp.minimum(diag, up), left)
        # diag and up agree on i-1: one where suffices for the row index
        ni = jnp.where((best == diag) | (best == up), i - 1, i)
        nj = jnp.where(best == diag, j - 1, jnp.where(best == up, j, j - 1))
        done = (i == 0) & (j == 0)
        ni = jnp.where(done, 0, ni)
        nj = jnp.where(done, 0, nj)
        return (ni, nj), (ni, nj)

    (_, _), (ii, jj) = jax.lax.scan(
        step, (jnp.int32(Tx - 1), jnp.int32(Ty - 1)), None, length=n_steps)
    ii = jnp.concatenate([jnp.int32(Tx - 1)[None], ii])
    jj = jnp.concatenate([jnp.int32(Ty - 1)[None], jj])
    mask = jnp.zeros((Tx, Ty), bool).at[ii, jj].set(True)
    return mask


@jax.jit
def optimal_path_mask(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """(Tx, Ty) bool mask of the optimal DTW path between x and y."""
    return backtrack(dtw_matrix(x, y))


def optimal_path_masks64(X: np.ndarray, Y: np.ndarray) -> np.ndarray:
    """(P, T[, d]) x (P, T[, d]) -> (P, T, T) bool optimal DTW paths.

    Accumulated costs D(i, j) = |x_i - y_j|^2 + min(D(i-1, j),
    D(i-1, j-1), D(i, j-1)) in float64 on the host, one anti-diagonal
    at a time for every pair at once; backtracking resolves ties as
    ``backtrack`` does, diag > up > left."""
    P, T = X.shape[:2]
    X = np.asarray(X, np.float64).reshape(P, T, -1)
    Yr = np.asarray(Y, np.float64).reshape(P, T, -1)[:, ::-1]
    # A[:, k, i + 1] = D(i, k - i): anti-diagonal k holds its cells in
    # row order, so each predecessor run is one slice; column 0 (i = -1)
    # and every cell off the grid stay +inf
    A = np.full((P, 2 * T - 1, T + 1), np.inf)
    inf_run = np.full((P, T), np.inf)
    for k in range(2 * T - 1):
        lo, hi = max(0, k - T + 1), min(k, T - 1) + 1     # rows on diag k
        # y index k - i for i in [lo, hi): a forward run of reversed y
        c = np.sum((X[:, lo:hi] - Yr[:, T - 1 - k + lo:T - 1 - k + hi]) ** 2,
                   axis=-1)
        if k == 0:
            A[:, 0, 1] = c[:, 0]
            continue
        up, left = A[:, k - 1, lo:hi], A[:, k - 1, lo + 1:hi + 1]
        diag = A[:, k - 2, lo:hi] if k >= 2 else inf_run[:, :hi - lo]
        A[:, k, lo + 1:hi + 1] = c + np.minimum(np.minimum(up, diag), left)
    p = np.arange(P)
    i = np.full(P, T - 1)
    j = np.full(P, T - 1)
    mask = np.zeros((P, T, T), bool)
    mask[p, i, j] = True
    for _ in range(2 * T - 2):
        k = i + j
        up, left = A[p, k - 1, i], A[p, k - 1, i + 1]
        diag = np.where(k >= 2, A[p, np.maximum(k - 2, 0), i], np.inf)
        best = np.minimum(np.minimum(diag, up), left)
        moving = k > 0
        step_i = moving & ((best == diag) | (best == up))
        step_j = moving & ((best == diag) | (best != up))
        i, j = i - step_i, j - step_j
        mask[p, i, j] = True
    return mask


def path_is_feasible(support: jnp.ndarray) -> jnp.ndarray:
    """True iff the boolean ``support`` admits a monotone (0,0)->(T,T) path.

    Runs the masked DP with unit costs and checks the corner is reachable.
    """
    cost = jnp.where(support, 1.0, INF).astype(jnp.float32)
    return _dp_rows(cost)[-1, -1] < INF
