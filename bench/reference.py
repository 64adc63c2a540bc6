"""Plain reference for exact SP-DTW 1-NN, independent of the program.

Two parts, both written from the paper's definitions (Section III and
Eq. 9 of arXiv:1711.04453) and importing nothing of ``repro``:

* ``learn_support``: the occupancy grid. For every pair of the support
  series, the optimal DTW path (full grid, squared-Euclidean cost,
  float64) is backtracked, ties resolved diagonal, then up, then left;
  each pair adds its path and the path's transpose once. Cells visited
  more than ``theta`` times, plus both corners, form the support; if that
  leaves no monotone path from corner to corner, the main diagonal is
  added. With gamma = 0 every support cell weighs 1.
* ``distances``: the masked DP D(i, j) = phi(x_i, y_j) + min(D(i-1, j),
  D(i-1, j-1), D(i, j-1)) over the support, +inf outside it, for every
  query against every corpus series. No plan, bounds, tiles or kernels:
  the DP walks the anti-diagonals of the grid, vectorised over pairs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _dtw_skewed(X: np.ndarray, Y: np.ndarray) -> np.ndarray:
    """(P, T) x (P, T) -> (P, 2T-1, T) float64 accumulated DTW costs,
    anti-diagonal major: [p, k, i] holds cell (i, k - i), +inf off the
    grid."""
    P, T = X.shape
    # along anti-diagonal k, row i meets Y[k - i]: reversed and padded, Y
    # gives that run as one contiguous slice per k
    Yr = np.pad(Y[:, ::-1], ((0, 0), (T, T)))
    i = np.arange(T)
    D = np.full((P, 2 * T - 1, T), np.inf)
    inf_col = np.full((P, 1), np.inf)
    for k in range(2 * T - 1):
        on_grid = (k - i >= 0) & (k - i < T)
        c = np.where(on_grid, (X - Yr[:, 2 * T - 1 - k:3 * T - 1 - k]) ** 2,
                     np.inf)
        if k == 0:
            D[:, 0] = c
            continue
        d1 = D[:, k - 1]
        d2 = D[:, k - 2] if k >= 2 else np.full((P, T), np.inf)
        up = np.concatenate([inf_col, d1[:, :-1]], axis=1)     # (i-1, j)
        diag = np.concatenate([inf_col, d2[:, :-1]], axis=1)   # (i-1, j-1)
        D[:, k] = c + np.minimum(np.minimum(up, d1), diag)     # d1: (i, j-1)
    return D


def _paths(D: np.ndarray) -> np.ndarray:
    """(P, 2T-1, T) skewed costs -> (P, T, T) bool optimal-path masks."""
    P, _, T = D.shape
    p = np.arange(P)

    def at(i, j, ok):
        return np.where(ok, D[p, np.maximum(i + j, 0), np.maximum(i, 0)],
                        np.inf)

    i = np.full(P, T - 1)
    j = np.full(P, T - 1)
    mask = np.zeros((P, T, T), bool)
    mask[p, i, j] = True
    for _ in range(2 * T - 2):
        diag = at(i - 1, j - 1, (i > 0) & (j > 0))
        up = at(i - 1, j, i > 0)
        left = at(i, j - 1, j > 0)
        best = np.minimum(np.minimum(diag, up), left)
        take_diag = best == diag
        take_up = ~take_diag & (best == up)
        moving = (i > 0) | (j > 0)
        ni = np.where(moving & (take_diag | take_up), i - 1, i)
        nj = np.where(moving & ~take_up, j - 1, j)
        i, j = ni, np.maximum(nj, 0)
        mask[p, i, j] = True
    return mask


def _feasible(support: np.ndarray) -> bool:
    """True when a monotone path joins (0, 0) to (T-1, T-1) on ``support``."""
    T = support.shape[0]
    reach = np.zeros_like(support)
    for i in range(T):
        for j in range(T):
            if not support[i, j]:
                continue
            reach[i, j] = (i == 0 and j == 0) or (
                i > 0 and reach[i - 1, j]) or (j > 0 and reach[i, j - 1]) or (
                i > 0 and j > 0 and reach[i - 1, j - 1])
    return bool(reach[-1, -1])


def learn_support(X: np.ndarray, theta: float,
                  pairs_per_block: int = 64) -> np.ndarray:
    """(n, T) support series -> (T, T) bool learned support."""
    X = np.asarray(X, np.float64)
    n, T = X.shape
    iu, ju = np.triu_indices(n, k=1)
    counts = np.zeros((T, T), np.int64)
    for s in range(0, len(iu), pairs_per_block):
        a, b = iu[s:s + pairs_per_block], ju[s:s + pairs_per_block]
        m = _paths(_dtw_skewed(X[a], X[b]))
        counts += (m | m.transpose(0, 2, 1)).sum(axis=0)
    support = counts > theta
    support[0, 0] = support[-1, -1] = True
    if not _feasible(support):
        support |= np.eye(T, dtype=bool)
    return support


def _skew(support: np.ndarray) -> np.ndarray:
    """(T, T) -> (2T-1, T): row k holds cells (i, k - i), 0 off the grid."""
    T = support.shape[0]
    out = np.zeros((2 * T - 1, T), bool)
    for k in range(2 * T - 1):
        i = np.arange(max(0, k - T + 1), min(k, T - 1) + 1)
        out[k, i] = support[i, k - i]
    return out


@functools.partial(jax.jit, static_argnames=("dtype",))
def _dp(Q, C, skew, dtype):
    """(q, T), (c, T), (2T-1, T) -> (q, c) masked-DP distances."""
    T = Q.shape[1]
    Q = Q.astype(dtype)
    inf = jnp.array(jnp.inf, dtype)
    # along anti-diagonal k, row i meets corpus step k - i: reversed and
    # padded, the corpus gives that run as one contiguous slice per k
    Cr = jnp.pad(C.astype(dtype)[:, ::-1], ((0, 0), (T, T)))

    def cost(k):
        yk = jax.lax.dynamic_slice_in_dim(Cr, 2 * T - 1 - k, T, axis=1)
        diff = Q[:, None, :] - yk[None, :, :]
        return jnp.where(skew[k], diff * diff, inf)

    def shift(d):   # d[..., i - 1], +inf at i = 0
        return jnp.concatenate([jnp.full(d.shape[:-1] + (1,), inf, dtype),
                                d[..., :-1]], axis=-1)

    def step(carry, k):
        d1, d2 = carry                  # anti-diagonals k - 1 and k - 2
        dk = cost(k) + jnp.minimum(jnp.minimum(shift(d1), d1), shift(d2))
        return (dk, d1), None

    d0 = cost(0)
    dm1 = jnp.full(d0.shape, inf, dtype)
    (last, _), _ = jax.lax.scan(step, (d0, dm1), jnp.arange(1, 2 * T - 1))
    return last[..., T - 1].astype(jnp.float32)


def distances(Q: np.ndarray, C, support: np.ndarray, *,
              dtype=jnp.float32, block_q: int = 16) -> np.ndarray:
    """(q, T) queries x (c, T) corpus -> (q, c) SP-DTW distances."""
    skew = jnp.asarray(_skew(np.asarray(support)))
    C = jnp.asarray(C, jnp.float32)
    out = []
    for s in range(0, len(Q), block_q):
        Qb = np.asarray(Q[s:s + block_q], np.float32)
        pad = block_q - len(Qb)
        Qb = np.concatenate([Qb, np.repeat(Qb[-1:], pad, axis=0)])
        out.append(np.asarray(_dp(jnp.asarray(Qb), C, skew, dtype))[
            :block_q - pad])
    return np.concatenate(out)
