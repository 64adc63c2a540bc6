"""Admissible lower bounds for (SP-)DTW similarity search (DESIGN.md §4).

The serving stack never wants to pay the masked DP for a candidate that
provably cannot be the nearest neighbour. This module supplies the cheap,
*admissible* bounds that feed the cascade in ``repro.kernels.ops`` — every
bound b(q, c) satisfies b(q, c) <= SP-DTW(q, c), so pruning on
``b > threshold`` can never discard the true 1-NN (exactness by
construction, in the spirit of LB_Kim / LB_Keogh / PrunedDTW).

Both bounds are sparsity-aware: the learned support restricts every
admissible alignment path, so the per-row column windows (``support
extents``) it induces tighten the classic envelopes far beyond the
Sakoe-Chiba band they were invented for.

Bound 1 — endpoints (LB_Kim-style, O(1) per pair):
    every path contains the cells (0, 0) and (T-1, T-1), so

        SP-DTW(q, c) >= w[0,0] * (q_0 - c_0)^2 + w[-1,-1] * (q_T - c_T)^2.

Bound 2 — support-windowed envelopes (LB_Keogh-style, O(T) per pair):
    a monotone path visits *every* row i, at some column j inside the
    support's row window [lo_i, hi_i], paying at least

        min_{j in supp row i} w[i,j] * (q_i - c_j)^2
            >= wmin_i * penalty(q_i; L_i, U_i)

    where (L_i, U_i) is the envelope of c over the window and ``penalty``
    the usual one-sided squared excess. Summing over rows is admissible
    because path cost is a sum of non-negative cell costs and rows are
    disjoint. The transposed variant bounds through the *columns* (the
    candidate's rows), with the query enveloped instead; the max of the
    two (and of bound 1) is again admissible.

All functions are pure jnp (jit/vmap/shard_map friendly); the static
window/weight vectors are derived host-side once per learned support.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dtw import INF


def support_extents(support) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row column windows [lo_i, hi_i] of a boolean (T, T) support.

    Host-side (the support is concrete, learned once per dataset). Empty
    rows — only possible with ``repair=False`` — get the inverted window
    (lo=T, hi=-1); downstream bounds turn those rows into +INF, which is
    admissible because a support with an empty row admits no path at all.
    """
    sup = np.asarray(support, bool)
    T = sup.shape[1]
    any_row = sup.any(axis=1)
    j = np.arange(T)
    lo = np.where(any_row, np.where(sup, j[None, :], T).min(axis=1), T)
    hi = np.where(any_row, np.where(sup, j[None, :], -1).max(axis=1), -1)
    return lo.astype(np.int32), hi.astype(np.int32)


def row_min_weights(weights) -> np.ndarray:
    """Min positive weight per row of a (T, T) weight grid (host-side).

    The weighted local cost of any supported cell in row i is at least
    ``wmin_i`` times its unweighted cost, so scaling the envelope penalty
    by ``wmin_i`` keeps the bound admissible for arbitrary positive
    weights (gamma > 0 grids included). Empty rows map to +INF.
    """
    w = np.asarray(weights, np.float32)
    pos = w > 0
    wmin = np.where(pos, w, np.float32(INF)).min(axis=1)
    return np.where(pos.any(axis=1), wmin, np.float32(INF)).astype(np.float32)


def envelopes(C: jnp.ndarray, lo, hi) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Windowed running envelopes of each series in C under [lo_i, hi_i].

    C: (N, T) or (N, T, d). Returns (L, U), both shaped like C:
    L[n, i] = min_{j in [lo_i, hi_i]} C[n, j] (and U the max) — the
    row-window envelope every admissible alignment of row i is confined
    to; for multivariate series the envelope is per channel (each channel
    of the aligned column lies in its own [L, U] box). Rows with inverted
    windows (empty support rows) get (+INF, -INF) so any query point pays
    an infinite penalty there.
    """
    C = jnp.asarray(C, jnp.float32)
    T = C.shape[1]
    j = jnp.arange(T)
    win = (j[None, :] >= jnp.asarray(lo)[:, None]) & \
          (j[None, :] <= jnp.asarray(hi)[:, None])        # (T, T) [row, col]
    big = jnp.float32(INF)
    if C.ndim == 3:
        Cw = C[:, None, :, :]                             # (N, 1, T, d)
        winb = win[None, :, :, None]
        L = jnp.min(jnp.where(winb, Cw, big), axis=2)     # (N, T, d)
        U = jnp.max(jnp.where(winb, Cw, -big), axis=2)
        return L, U
    L = jnp.min(jnp.where(win[None], C[:, None, :], big), axis=2)
    U = jnp.max(jnp.where(win[None], C[:, None, :], -big), axis=2)
    return L, U


def _sq_dist(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Squared distance of broadcast point batches: channels summed for
    multivariate points (trailing axis), plain square for scalars."""
    dd = (a - b) ** 2
    return jnp.sum(dd, axis=-1) if dd.ndim > 2 else dd


def lb_kim_cross(Q: jnp.ndarray, C: jnp.ndarray,
                 w00: float = 1.0, wTT: float = 1.0) -> jnp.ndarray:
    """(Nq, Nc) endpoint lower bound (LB_Kim-style, O(1) per pair).

    Q: (Nq, T) or (Nq, T, d); C likewise (channels are summed into the
    squared endpoint distances, matching the dependent-DTW local cost).
    """
    Q = jnp.asarray(Q, jnp.float32)
    C = jnp.asarray(C, jnp.float32)
    d0 = _sq_dist(Q[:, None, 0], C[None, :, 0])
    d1 = _sq_dist(Q[:, None, -1], C[None, :, -1])
    return jnp.minimum(jnp.float32(w00) * d0 + jnp.float32(wTT) * d1, INF)


def lb_kim_band_cross(Q: jnp.ndarray, C: jnp.ndarray, lo, hi, wmin,
                      w00: float = 1.0, wTT: float = 1.0,
                      ell: int = 3, max_width: int = 32) -> jnp.ndarray:
    """(Nq, Nc) banded LB_Kim: exact endpoints + first/last-``ell`` rows.

    Every monotone path visits row i at some supported column
    j in [lo_i, hi_i], paying at least wmin_i * min_j dist2(q_i, c_j);
    rows are disjoint, so summing the per-row minima over the prefix rows
    {1..ell-1} and suffix rows {T-ell..T-2} on top of the exact-weight
    endpoint terms stays admissible under per-row weight floors. Near the
    corners the support windows are narrow (every path is pinned there),
    which is what makes the row minima cheap *and* tight — rows whose
    window exceeds ``max_width`` columns are skipped (dropping a
    non-negative term only loosens the bound). Empty support rows
    (wmin == +INF) admit no path at all and force the bound to +INF.
    Q: (Nq, T) or (Nq, T, d); C likewise. lo/hi/wmin are the host-side
    support extents / weight floors of ``CorpusIndex``.
    """
    Q = jnp.asarray(Q, jnp.float32)
    C = jnp.asarray(C, jnp.float32)
    T = Q.shape[1]
    out = lb_kim_cross(Q, C, w00, wTT)
    lo = np.asarray(lo)
    hi = np.asarray(hi)
    wmin = np.asarray(wmin, np.float32)
    band = sorted(set(range(1, min(ell, T - 1))) |
                  set(range(max(T - ell, 1), T - 1)))
    for i in band:
        # host-side floats only: INF is a jnp constant and comparing with
        # it would build a traced bool under jit/shard_map traces
        if float(wmin[i]) >= 1e29 or lo[i] > hi[i]:
            out = jnp.full_like(out, INF)   # empty row: no admissible path
            break
        width = int(hi[i]) - int(lo[i]) + 1
        if width > max_width:
            continue
        Cw = C[:, int(lo[i]):int(hi[i]) + 1]        # (Nc, width[, d])
        dd = (Q[:, i][:, None, None] - Cw[None]) ** 2
        if dd.ndim == 4:
            dd = jnp.sum(dd, axis=-1)               # (Nq, Nc, width)
        out = out + jnp.float32(wmin[i]) * jnp.min(dd, axis=-1)
    return jnp.minimum(out, INF)


def _keogh_penalty(Q: jnp.ndarray, L: jnp.ndarray, U: jnp.ndarray,
                   wmin: jnp.ndarray) -> jnp.ndarray:
    """Σ_i wmin_i * one-sided squared excess of Q_i outside [L_i, U_i].

    Q: (Nq, T) or (Nq, T, d); L, U: like the candidate set (Nc, T[, d]);
    wmin: (T,). Returns (Nq, Nc). Channels sum their excesses before the
    weight multiply — admissible because the dependent-DTW local cost
    sums channel squares and each channel's aligned value lies in its own
    envelope slab. Rows whose window is empty (wmin == +INF) force the
    whole bound to +INF.
    """
    wmin = jnp.asarray(wmin, jnp.float32)
    above = jnp.maximum(Q[:, None] - U[None], 0.0)
    below = jnp.maximum(L[None] - Q[:, None], 0.0)
    pen = above * above + below * below               # (Nq, Nc, T[, d])
    if pen.ndim == 4:
        pen = jnp.sum(pen, axis=-1)                   # (Nq, Nc, T)
    dead = wmin >= INF
    term = jnp.where(dead[None, None, :], INF,
                     jnp.where(dead, 0.0, wmin)[None, None, :] * pen)
    return jnp.minimum(jnp.sum(term, axis=2), INF)


def lb_keogh_cross(Q: jnp.ndarray, env_lo: jnp.ndarray, env_hi: jnp.ndarray,
                   wmin: jnp.ndarray, block_q: int = 256) -> jnp.ndarray:
    """(Nq, Nc) support-windowed LB_Keogh against precomputed candidate
    envelopes (the index side of the bound). Chunked over queries to bound
    the (block_q, Nc, T) intermediate."""
    Q = jnp.asarray(Q, jnp.float32)
    rows = [_keogh_penalty(Q[s:s + block_q], env_lo, env_hi, wmin)
            for s in range(0, Q.shape[0], block_q)]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def cascade_bounds(Q: jnp.ndarray, C: jnp.ndarray, env_lo: jnp.ndarray,
                   env_hi: jnp.ndarray, *, lo, hi, wmin_rows, lo_t, hi_t,
                   wmin_cols, w00: float, wTT: float
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The cascade's bound stage as one compiled program: (lb1, lb2).

    lb1 is the banded LB_Kim; lb2 the max of lb1, LB_Keogh against the
    candidate envelopes (env_lo, env_hi), and LB_Keogh in the candidate
    orientation against the query envelopes. Q: (Nq, T[, d]); C and the
    envelopes: (Nc, T[, d]). The host-side support vectors (lo .. wTT,
    a ``CorpusIndex``'s static fields) drive Python control flow, so they
    are static to the program: one program per support and batch shape.
    The arrays stay arguments, so sliced or refreshed corpora reuse it;
    under an outer jit or shard_map trace the program inlines.
    """
    statics = tuple(tuple(np.asarray(v).tolist()) for v in
                    (lo, hi, wmin_rows, lo_t, hi_t, wmin_cols))
    return _cascade_bounds(jnp.asarray(Q, jnp.float32),
                           jnp.asarray(C, jnp.float32), env_lo, env_hi,
                           statics + (float(w00), float(wTT)))


@functools.partial(jax.jit, static_argnums=4)
def _cascade_bounds(Q, C, env_lo, env_hi, statics):
    lo, hi, wmin_rows, lo_t, hi_t, wmin_cols = (np.asarray(v)
                                                for v in statics[:6])
    w00, wTT = statics[6:]
    lb1 = lb_kim_band_cross(Q, C, lo, hi, wmin_rows, w00, wTT)
    lb2 = jnp.maximum(lb1, _keogh_penalty(Q, env_lo, env_hi, wmin_rows))
    q_lo, q_hi = envelopes(Q, lo_t, hi_t)
    # no chunking over the corpus: the compiler fuses the penalty into
    # its reduction, so the (Nc, Nq, T) excess is never materialised
    lb2 = jnp.maximum(lb2, _keogh_penalty(C, q_lo, q_hi, wmin_cols).T)
    return lb1, lb2


# ---------------------------------------------------------------------------
# Log-semiring bounds for the K_rdtw kernel measures (DESIGN.md §14)
# ---------------------------------------------------------------------------

def krdtw_log_slacks(support=None, T: int | None = None) -> Tuple[float,
                                                                  float]:
    """Proven slack terms (log S1, log S2) of the K_rdtw upper bound.

    The K1 recursion of ``core.krdtw`` is a sum over admissible paths p of
    coeff(p) * Π_cells exp(-nu * cost(cell)), with path-shape coefficients
    coeff(p) > 0 that do not depend on the series. Bounding every path's
    product by exp(-nu * B1) — B1 any admissible lower bound on the
    unit-weight masked path cost — gives

        K1(x, y) <= [Σ_p coeff(p)] * exp(-nu * B1) = S1 * exp(-nu * B1),

    and S1 is exactly the K1 recursion evaluated with kappa ≡ 1 over the
    support. Same for K2 with S2 (kappa ≡ dkap ≡ 1). Host-side, once per
    fitted support; pass either the (T, T) bool ``support`` or a bare
    ``T`` for the full grid.
    """
    from .krdtw import _krdtw_rows
    if support is not None:
        mask = jnp.asarray(np.asarray(support, bool))
        T = mask.shape[0]
    else:
        assert T is not None, "need a support or a length"
        mask = None
    ones = jnp.ones((T, T), jnp.float32)
    l1, l2 = _krdtw_rows(ones, jnp.ones((T,), jnp.float32), mask)
    return float(l1), float(l2)


def lb_log_krdtw(b1: jnp.ndarray, b2: jnp.ndarray, nu: float,
                 log_s1: float, log_s2: float) -> jnp.ndarray:
    """Admissible lower bound on -log K_rdtw from min-plus cost bounds.

    K_rdtw = K1 + K2 and each term is upper-bounded by its slack times
    exp(-nu * b): ``b1`` is any admissible lower bound on the unit-weight
    masked min-path cost (the same Kim/Keogh/prefix machinery run on a
    unit-weight index), ``b2`` lower-bounds the aligned endpoint cost
    (x_0 - y_0)^2 + (x_{T-1} - y_{T-1})^2 — every K2 path product carries
    the kappa(x_0, y_0) init factor and a final dkap_{T-1} factor, all
    other factors <= 1. Hence

        -log K_rdtw >= -logaddexp(log_s1 - nu*b1, log_s2 - nu*b2),

    so pruning the kernel dissimilarity -log K on this bound never drops
    the true nearest neighbour. f32-safe: nu * INF stays finite.
    """
    lhs = jnp.float32(log_s1) - jnp.float32(nu) * jnp.minimum(b1, INF)
    rhs = jnp.float32(log_s2) - jnp.float32(nu) * jnp.minimum(b2, INF)
    return jnp.minimum(-jnp.logaddexp(lhs, rhs), INF)
