"""Occupancy-grid learning and sparsification (paper Section III, Fig. 3).

Strategy (Fig. 3 a-f):
  (a) take the training set X = {x_i},
  (b) compute the optimal DTW path mask for every pair i < j,
  (c) sum the boolean masks into a global absolute-frequency grid
      (symmetrized: path(i,j) == path(j,i)^T),
  (d) scale into [0, 1),
  (e) zero every cell whose *absolute* frequency is below theta
      (theta picked by leave-one-out on train, Fig. 4 searches [0, 15]),
  (f) keep a sparse representation.

Two sparse representations are produced:
  * the paper's LOC list (row-major sorted (row, col, weight) triples) used by
    the Algorithm-1/2 faithful evaluators and for reporting visited cells,
  * a TPU-native block-sparse layout (DESIGN.md section 3): the grid is cut in
    ``tile`` x ``tile`` blocks, a block survives iff any of its cells does and
    surviving blocks are stored compressed.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .dtw import INF
from .paths import optimal_path_masks64, path_is_feasible


def pairwise_path_counts(X: jnp.ndarray,
                         batch_cells: int = 1 << 23) -> jnp.ndarray:
    """Absolute occupancy counts over all N(N-1)/2 training pairs.

    X: (N, T) or (N, T, d). Returns float32 (T, T) counts. Each unordered
    pair contributes its *symmetrized* path mask ``m | m.T`` once, so every
    cell count is exactly the number of training pairs whose optimal
    alignment (in either orientation) visits it — at most N(N-1)/2. (The
    earlier ``counts + counts.T`` post-hoc symmetrization double-counted
    cells lying on both a path and its transpose, e.g. the corners.)
    Paths come from float64 costs on the host (``optimal_path_masks64``),
    in chunks of about ``batch_cells`` grid cells to bound memory.
    """
    X = np.asarray(X)
    N, T = X.shape[:2]
    iu, ju = np.triu_indices(N, k=1)
    counts = np.zeros((T, T), np.int64)
    step = max(1, batch_cells // (T + 1) ** 2)
    for s in range(0, len(iu), step):
        m = optimal_path_masks64(X[iu[s:s + step]], X[ju[s:s + step]])
        counts += (m | m.transpose(0, 2, 1)).sum(axis=0)
    return jnp.asarray(counts, jnp.float32)


def normalize_grid(counts: jnp.ndarray) -> jnp.ndarray:
    """Scale the absolute-frequency grid into [0, 1) (Fig. 3-d)."""
    return counts / (jnp.max(counts) + 1.0)


@dataclasses.dataclass(frozen=True)
class SparsePaths:
    """Learned sparsified alignment-path search space.

    weights: (T, T) float32; 0 outside the support, f(p) = p^-gamma inside
             (gamma = 0 -> unit weights, pure support sparsification).
    support: (T, T) bool, cells surviving the theta threshold.
    counts:  raw absolute frequencies (kept for Table VI reporting).
    theta, gamma: the meta-parameters that produced this grid.
    """
    weights: jnp.ndarray
    support: jnp.ndarray
    counts: jnp.ndarray
    theta: float
    gamma: float

    @property
    def n_cells(self) -> int:
        """Visited-cell count (paper Table VI's '# visited cells')."""
        return int(jnp.sum(self.support))

    def loc_list(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Paper's LOC interchange format: row-major (rows, cols, weights)."""
        sup = np.asarray(self.support)
        w = np.asarray(self.weights)
        rows, cols = np.nonzero(sup)
        order = np.lexsort((cols, rows))
        rows, cols = rows[order], cols[order]
        return rows.astype(np.int32), cols.astype(np.int32), w[rows, cols]


def learn_sparse_paths(
    X: jnp.ndarray,
    theta: float = 1.0,
    gamma: float = 0.0,
    counts: Optional[jnp.ndarray] = None,
    repair: bool = True,
) -> SparsePaths:
    """Learn the sparsified path search space from training series X.

    theta thresholds the *absolute* occupancy counts (paper Fig. 4 searches
    theta in [0, 15]). gamma is the weighting exponent of Eq. 9.
    If ``repair`` and thresholding disconnected the corners, the main diagonal
    is re-added so every query keeps at least one admissible path.
    """
    if counts is None:
        counts = pairwise_path_counts(X)
    T = counts.shape[0]
    support = counts > theta
    # the corners are always on every path; keep them regardless of theta
    support = support.at[0, 0].set(True).at[T - 1, T - 1].set(True)
    if repair and not bool(path_is_feasible(support)):
        eye = jnp.eye(T, dtype=bool)
        support = support | eye
    p = normalize_grid(counts)
    # f(p) = p^-gamma on the support (Eq. 9); gamma=0 gives unit weights.
    safe_p = jnp.where(support & (p > 0), p, 1.0)
    weights = jnp.where(support, safe_p ** (-gamma), 0.0)
    weights = jnp.minimum(weights, 1e6).astype(jnp.float32)
    return SparsePaths(weights=weights, support=support, counts=counts,
                       theta=float(theta), gamma=float(gamma))


# ---------------------------------------------------------------------------
# TPU block-sparse layout
# ---------------------------------------------------------------------------

def _tile_plan(active: np.ndarray, slot: np.ndarray) -> np.ndarray:
    """Row-major schedule over active tiles, one int32 row per grid step.

    Columns: (ti, tj, slot, top_active, left_active, diag_active,
    row_first). Row-major order guarantees every producer tile of an edge
    runs before its consumer (DP wavefront order); the neighbour bits let
    kernels read skipped-tile edges as +INF instead of stale data.
    ``row_first`` marks the first tile of each tile row — the step at which
    the previous tile row is complete, i.e. where the early-abandon sweep
    (``kernels.gram_block``) may compare the running row-min against the
    1-NN threshold.
    """
    ii, jj = np.nonzero(active)              # np.nonzero is row-major
    if len(ii) == 0:
        return np.zeros((0, 7), np.int32)
    top = (ii > 0) & active[np.maximum(ii - 1, 0), jj]
    left = (jj > 0) & active[ii, np.maximum(jj - 1, 0)]
    diag = ((ii > 0) & (jj > 0)
            & active[np.maximum(ii - 1, 0), np.maximum(jj - 1, 0)])
    row_first = np.concatenate([[True], ii[1:] != ii[:-1]])
    return np.stack([ii, jj, slot[ii, jj], top, left, diag, row_first],
                    axis=1).astype(np.int32)


def _reverse_tile_plan(active: np.ndarray, meta: np.ndarray,
                       g_out: int) -> np.ndarray:
    """Reverse active-tile schedule for the expected-alignment sweep
    (DESIGN.md §11), one int32 row per reverse grid step.

    Walks the forward plan steps ``g_out .. 0`` in reverse row-major order
    (the E recursion's wavefront: every *successor* tile of an edge runs
    before its consumer). Columns: (ti, tj, slot, below_active,
    right_active, diagbr_active, fwd_step). The neighbour bits are taken
    against the *walked* prefix ``meta[:g_out+1]`` — tiles past the result
    tile carry no alignment mass, so their halo edges must read as
    E = 0 / L = NEG, never as computed data. ``fwd_step`` is the forward
    plan index of the tile: the stash-lookup key for the per-tile L blocks
    saved by the forward engines (``kernels.soft_block``).
    """
    sub = meta[:g_out + 1]
    ii, jj = sub[:, 0], sub[:, 1]
    Ti, Tj = active.shape
    walked = np.zeros_like(active, dtype=bool)
    walked[ii, jj] = True
    below = (ii + 1 < Ti) & walked[np.minimum(ii + 1, Ti - 1), jj]
    right = (jj + 1 < Tj) & walked[ii, np.minimum(jj + 1, Tj - 1)]
    diagbr = ((ii + 1 < Ti) & (jj + 1 < Tj)
              & walked[np.minimum(ii + 1, Ti - 1),
                       np.minimum(jj + 1, Tj - 1)])
    fwd = np.arange(g_out + 1)
    rp = np.stack([ii, jj, sub[:, 2], below, right, diagbr, fwd], axis=1)
    return np.ascontiguousarray(rp[::-1]).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class BlockSparsePaths:
    """Compressed block-sparse view of a SparsePaths grid.

    tile:        block edge (lanes-aligned, typically 128 on TPU).
    active:      (Ti, Tj) bool block bitmap.
    slot:        (Ti, Tj) int32 index into ``blocks`` (0 for inactive blocks,
                 which point at a shared all-masked dummy slot).
    blocks:      (n_slots, tile, tile) float32 compressed weights; slot 0 is
                 the all-zero dummy.
    T:           original (padded) grid edge; grids are padded to tile mult.
    meta:        cached (n_active, 7) int32 host-side tile plan (see
                 ``_tile_plan``); filled by ``block_sparsify`` and computed
                 lazily via ``plan()`` for hand-built instances.
    rmeta:       lazily-filled cache of reverse plans keyed by the result
                 tile step (see ``reverse_plan``).
    """
    tile: int
    active: np.ndarray
    slot: np.ndarray
    blocks: np.ndarray
    T: int
    meta: Optional[np.ndarray] = None
    rmeta: Optional[dict] = None

    @property
    def n_active(self) -> int:
        """Number of surviving (scheduled) tiles."""
        return int(self.active.sum())

    @property
    def tile_sparsity(self) -> float:
        """Fraction of blocks *skipped* (the TPU kernel's speed-up lever)."""
        return 1.0 - self.n_active / self.active.size

    def plan(self) -> np.ndarray:
        """The cached active-tile schedule (computed at most once)."""
        if self.meta is None:
            object.__setattr__(self, "meta",
                               _tile_plan(self.active, self.slot))
        return self.meta

    def reverse_plan(self, g_out: int) -> np.ndarray:
        """The cached reverse schedule through forward step ``g_out``
        (the result-tile step for the query length at hand; see
        ``kernels.spdtw_block.result_tile_step``). One cache entry per
        distinct g_out — ragged corpora reuse the few lengths they have.
        """
        if self.rmeta is None:
            object.__setattr__(self, "rmeta", {})
        if g_out not in self.rmeta:
            self.rmeta[g_out] = _reverse_tile_plan(self.active, self.plan(),
                                                   g_out)
        return self.rmeta[g_out]


def default_tile(T: int) -> int:
    """Pick a tile edge for series length T: power of two in [8, 128] such
    that the padded grid is at least ~8 tiles per side (enough granularity
    for the occupancy prior to actually skip blocks)."""
    t = 8
    while t * 8 < T and t < 128:
        t *= 2
    return t


def block_sparsify(sp, tile: int = 128) -> BlockSparsePaths:
    """Re-blockify a learned sparse grid for the TPU kernel (DESIGN.md §3).

    ``sp`` is a SparsePaths or a raw (T, T) weight array (0 = outside the
    support). The active-tile schedule consumed by the Pallas kernels is
    precomputed here (vectorized) and cached on the result.
    """
    w = sp.weights if isinstance(sp, SparsePaths) else sp
    w = np.asarray(w, np.float32)
    T = w.shape[0]
    Tp = ((T + tile - 1) // tile) * tile
    wp = np.zeros((Tp, Tp), np.float32)
    wp[:T, :T] = w
    Ti = Tp // tile
    wt = wp.reshape(Ti, tile, Ti, tile).transpose(0, 2, 1, 3)
    active = (wt > 0).any(axis=(2, 3))
    ii, jj = np.nonzero(active)              # row-major, defines slot order
    n_active = len(ii)
    blocks = np.zeros((n_active + 1, tile, tile), np.float32)  # slot 0 dummy
    blocks[1:] = wt[ii, jj]
    slot = np.zeros((Ti, Ti), np.int32)
    slot[ii, jj] = np.arange(1, n_active + 1)
    return BlockSparsePaths(tile=tile, active=active, slot=slot,
                            blocks=blocks, T=Tp,
                            meta=_tile_plan(active, slot))
