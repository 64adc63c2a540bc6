"""Block-sparse SP-DTW — the paper's sparsified search space, TPU-native.

The paper iterates a cell-level LOC list (Algorithm 1) — pointer-chasing that
is hostile to TPU vector tiles. We keep the insight (prune the DP domain with
the learned occupancy prior) and re-blockify the mechanism (DESIGN.md §3):

  * the T×T grid is cut into S×S tiles; a tile is *active* iff any of its
    cells survives the theta threshold;
  * only active tiles are ever scheduled: the Pallas grid is
    (batch_tiles, n_active) and scalar-prefetched index vectors (ti, tj,
    slot) route each grid step to its tile coordinates and its compressed
    weight block — work scales with active tiles, exactly the paper's
    "complexity linear in surviving cells" claim at tile granularity;
  * DP state flows between tiles through VMEM scratch: ``row_edge`` carries
    bottom edges of the previous tile row, ``col_edge`` the right edge of the
    left tile, ``corner_next`` the top-left corner; per-tile neighbour
    validity bits (top/left/diag active) are prefetched so edges of skipped
    tiles read as +INF, never as stale data;
  * inside a tile, rows are swept sequentially and the in-row dependency is a
    Hillis-Steele min-plus scan over lanes (log2 S steps).

Active tiles are emitted in row-major order, which guarantees the producer
tiles of every edge ran before their consumer (DP wavefront order). The
schedule (ti, tj, slot, neighbour bits, row_first) is computed once,
vectorized, by ``occupancy._tile_plan`` and cached on the BlockSparsePaths —
this kernel and the fused all-pairs Gram engines (``gram_block.py``)
prefetch the same plan instead of re-flattening the bitmap per call (the
``row_first`` column feeds the Gram engines' early-abandon sweep; it is
unused here).

The per-tile DP (``tile_sweep``: row loop + Hillis-Steele min-plus lane
scan, edge injection from the neighbouring tiles) is pure jnp on values and
shared verbatim with ``gram_block.py``'s jnp scan engine. ``gram_block``'s
Pallas kernel holds a pair block as one (ba, bb) value per cell and shares
the arithmetic, not the code: the same operations in the same order, held
bit-identical by the parity tests.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.occupancy import BlockSparsePaths

INF = 1.0e30  # python float: weak-typed, safe to close over in pallas kernels


def _minplus_scan_lanes(u, c, width):
    m, s = u, c
    d = 1
    while d < width:
        bt = m.shape[0]
        m_sh = jnp.concatenate(
            [jnp.full((bt, d), INF, jnp.float32), m[:, :-d]], axis=1)
        s_sh = jnp.concatenate(
            [jnp.zeros((bt, d), jnp.float32), s[:, :-d]], axis=1)
        m = jnp.minimum(m, m_sh + s)
        s = jnp.minimum(s_sh + s, INF)
        d *= 2
    return m


def _pick(v, idx, iota, axis):
    """Slice ``idx`` of ``v`` along ``axis`` (kept as a size-1 axis) for a
    traced ``idx``: a select against ``iota`` and a min-reduce, which is
    exact (every other entry reads +inf) and, unlike ``dynamic_slice`` on a
    value, lowers in Mosaic as well as in XLA."""
    return jnp.min(jnp.where(iota == idx, v, jnp.inf), axis=axis,
                   keepdims=True)


def tile_cost_row(x, y, w, t, *, S: int, d: int = 1):
    """Weighted local-cost row ``t`` of one tile for a pair batch.

    x, y: (bt, d*S) tile-major / channel-inner series tiles (channel k in
    lanes [k*S, (k+1)*S); see ``backends.to_tile_major`` — d = 1 is the
    historical (bt, S) layout unchanged). The squared distance sums over
    channels before the weight multiply, so the multivariate DP is the
    *dependent* DTW of the summed local cost under one shared path —
    exactly what the dense core DPs (``core.dtw.local_cost``) compute.
    Masked cells (w == 0) read +INF. ``t`` may be traced (the row loop
    index). Shared by the hard sweeps here / in ``gram_block``'s scan
    engines; the soft twin lives in ``soft_block``.
    """
    wt = _pick(w, t, jax.lax.broadcasted_iota(jnp.int32, w.shape, 0), 0)
    xlane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    acc = None
    for k in range(d):
        xt = _pick(x, k * S + t, xlane, 1)                       # (bt,1)
        yk = y[:, k * S:(k + 1) * S]
        dk = (xt - yk) ** 2
        acc = dk if acc is None else acc + dk
    return jnp.where(wt > 0, acc * wt, INF)


def tile_sweep(x, y, w, top_vec, left_vec, c_first, *, S: int, ri: int,
               d: int = 1, thr=None):
    """Sweep one S x S tile of the SP-DTW DP for a batch of pairs.

    Pure jnp on values (no refs), so it is shared verbatim by the single-pair
    Pallas kernel here and the jnp scan engines in ``gram_block.py``; the
    fused Gram kernel there repeats its arithmetic in a pair-block layout
    (``gram_block._sweep_rows``), held bit-identical by tests. Row- and
    column-indexed reads and writes at the loop index go through lane
    selects (``_pick`` / ``jnp.where``), never a value-level dynamic slice,
    so the one body lowers both in XLA and in Mosaic.

    x, y:      (bt, d*S) per-pair series tiles, tile-major / channel-inner
               (rows of x, cols of y; d = 1 is the historical (bt, S)).
    w:         (S, S) weight block (0 = masked cell).
    top_vec:   (bt, S) bottom edge of the tile above (+INF if inactive).
    left_vec:  (bt, S) right edge of the tile to the left (+INF if inactive).
    c_first:   (bt, 1) D value diagonally above-left of this tile's corner.
    thr:       optional (bt, 1) per-pair PrunedDTW bound: after each row,
               cells with D > thr are snapped to +INF. Cell costs are
               non-negative, so D is non-decreasing along any path — a
               cell above the bound can never feed a final value <= thr,
               and pruning it leaves every value <= thr bit-identical
               (Herrmann & Webb). Pruned cells stop propagating, so the
               live [lo, hi) span of each DP row shrinks as descendants of
               pruned cells die; thr=None (or +INF) is the exact sweep.
    Returns (d_last, rightcol, dri): the tile's bottom row, right column,
    and the row at in-tile index ``ri`` (global result-row capture).
    """
    bt = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, S), 1)

    def cost_row(t):
        return tile_cost_row(x, y, w, t, S=S, d=d)

    def row_update(t, d_prev, topleft0, left_t):
        c = cost_row(t)
        topleft = jnp.concatenate([topleft0, d_prev[:, :-1]], axis=1)
        u = c + jnp.minimum(d_prev, topleft)
        # inject the left-tile boundary as a virtual D_{-1}
        u0 = jnp.minimum(u[:, 0:1], left_t + c[:, 0:1])
        u = jnp.concatenate([u0, u[:, 1:]], axis=1)
        out = jnp.minimum(_minplus_scan_lanes(u, c, S), INF)
        if thr is not None:
            out = jnp.where(out <= thr, out, INF)
        return out

    d0 = row_update(0, top_vec, c_first, left_vec[:, 0:1])

    def body(t, carry):
        d_prev, rightcol, dri = carry
        tl0 = _pick(left_vec, t - 1, lane, 1)
        lt = _pick(left_vec, t, lane, 1)
        d_row = row_update(t, d_prev, tl0, lt)
        rightcol = jnp.where(lane == t, d_row[:, S - 1:S], rightcol)
        dri = jnp.where(t == ri, d_row, dri)
        return d_row, rightcol, dri

    rightcol0 = jnp.where(lane == 0, d0[:, S - 1:S], INF)
    dri0 = jnp.where(ri == 0, d0, jnp.full((bt, S), INF, jnp.float32))
    return jax.lax.fori_loop(1, S, body, (d0, rightcol0, dri0))


def _spdtw_block_kernel(meta_ref, x_ref, y_ref, w_ref, out_ref,
                        row_edge, col_edge, corner_next, d_ri,
                        *, S: int, g_out: int, ri: int, rj: int, d: int):
    """One grid step = one active tile (meta columns: ti,tj,slot,top,left,diag).

    ``row_edge`` is (Ti, bt, S): tile column tj's bottom edge is indexed on
    the leading axis, so no access ever needs a dynamic lane offset (Mosaic
    only proves 128-aligned ones)."""
    g = pl.program_id(1)
    bt = x_ref.shape[1]
    tj = meta_ref[g, 1]
    top_ok = meta_ref[g, 3] > 0
    left_ok = meta_ref[g, 4] > 0
    diag_ok = meta_ref[g, 5] > 0

    x = x_ref[0]                    # (bt, d*S) rows of this tile
    y = y_ref[0]                    # (bt, d*S) cols of this tile
    w = w_ref[0]                    # (S, S) weight block

    # --- gather incoming edges (guarded against inactive neighbours) ---
    inf_row = jnp.full((bt, S), INF, jnp.float32)
    top_vec = jnp.where(top_ok, row_edge[tj], inf_row)
    left_vec = jnp.where(left_ok, col_edge[...], inf_row)
    c_first = jnp.where(
        g == 0, jnp.zeros((bt, 1), jnp.float32),
        jnp.where(diag_ok,
                  jnp.where(left_ok, corner_next[...],
                            # guarded: only read when diag_ok (=> tj > 0);
                            # clamp keeps the untaken branch in-bounds
                            row_edge[jnp.maximum(tj - 1, 0)][:, S - 1:S]),
                  jnp.full((bt, 1), INF, jnp.float32)))

    # corner for the *next* tile (i, j+1) = last element of this tile's top row
    new_corner = top_vec[:, S - 1:S]

    d_last, rightcol, dri = tile_sweep(x, y, w, top_vec, left_vec, c_first,
                                       S=S, ri=ri, d=d)

    # --- publish edges for downstream tiles ---
    corner_next[...] = new_corner
    row_edge[tj] = d_last
    col_edge[...] = rightcol
    d_ri[...] = dri

    # capture at the tile holding the global result cell (NOT the last
    # active tile: the support may have active tiles past the corner, or —
    # for raw user weights — none at the corner at all)
    @pl.when(g == g_out)
    def _():
        out_ref[...] = d_ri[:, rj:rj + 1]


def _host_plan(bsp: BlockSparsePaths) -> Tuple[np.ndarray, int]:
    """Active-tile schedule (cached on the BlockSparsePaths; see
    ``occupancy._tile_plan`` for the layout)."""
    meta = bsp.plan()
    return meta, meta.shape[0]


def result_tile_step(meta: np.ndarray, S: int, T_orig: int) -> int:
    """Grid-step index of the tile holding the result cell (T_orig-1,
    T_orig-1), or -1 if that tile is inactive (=> SP-DTW is +INF: the
    corner cell itself is outside the support, so no path ends there)."""
    ci = (T_orig - 1) // S
    hit = np.nonzero((meta[:, 0] == ci) & (meta[:, 1] == ci))[0]
    return int(hit[0]) if len(hit) else -1


@functools.partial(jax.jit,
                   static_argnames=("S", "n_active", "T_orig", "g_out",
                                    "block_b", "d", "interpret"))
def _spdtw_block_call(meta, x, y, blocks, *, S, n_active, T_orig, g_out,
                      block_b, d, interpret):
    Ti, Bp, _ = x.shape             # tile-stacked: (Ti, Bp, d*S)
    last = T_orig - 1
    ri, rj = last % S, last % S
    grid = (Bp // block_b, n_active)
    kernel = functools.partial(_spdtw_block_kernel, S=S, g_out=g_out,
                               ri=ri, rj=rj, d=d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # tile ti of every series is one leading-axis slab, so the
            # block's lane extent is the whole d*S (always lane-legal)
            pl.BlockSpec((1, block_b, d * S), lambda b, g, m: (m[g, 0], b, 0)),
            pl.BlockSpec((1, block_b, d * S), lambda b, g, m: (m[g, 1], b, 0)),
            pl.BlockSpec((1, S, S), lambda b, g, m: (m[g, 2], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda b, g, m: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((Ti, block_b, S), jnp.float32),  # row_edge
            pltpu.VMEM((block_b, S), jnp.float32),      # col_edge
            pltpu.VMEM((block_b, 1), jnp.float32),      # corner_next
            pltpu.VMEM((block_b, S), jnp.float32),      # d_ri capture
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        interpret=interpret,
    )(meta, x, y, blocks)


def spdtw_block(x: jnp.ndarray, y: jnp.ndarray, bsp: BlockSparsePaths,
                T_orig: int | None = None, block_b: int = 8,
                interpret: bool = False) -> jnp.ndarray:
    """Batched SP-DTW over a block-sparse learned search space.

    x, y: (B, T_orig) or (B, T_orig, d) f32. Returns (B,) SP-DTW values
    (INF-like where the support admits no path).
    """
    from .backends import series_dim, to_tile_stack
    B, T = x.shape[0], x.shape[1]
    d = series_dim(x)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    meta, n_active = _host_plan(bsp)
    g_out = result_tile_step(meta, bsp.tile, T_orig)
    if g_out < 0:   # corner cell outside the support: no admissible path
        return jnp.full((B,), INF, jnp.float32)
    Bp = ((B + block_b - 1) // block_b) * block_b
    out = _spdtw_block_call(
        jnp.asarray(meta), to_tile_stack(x, bsp.tile, bsp.T, n_to=Bp),
        to_tile_stack(y, bsp.tile, bsp.T, n_to=Bp), jnp.asarray(bsp.blocks),
        S=bsp.tile, n_active=n_active, T_orig=T_orig, g_out=g_out,
        block_b=block_b, d=d, interpret=interpret)
    return out[:B, 0]
