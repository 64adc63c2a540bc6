"""Fused block-sparse all-pairs Gram engine for SP-DTW / SP-K_rdtw.

The paper's production workload (1-NN and SVM classification) is an all-pairs
Gram matrix over two series sets A (Na, T) and B (Nb, T). The historical path
materialized ``jnp.repeat``/``jnp.tile`` Na*Nb-expanded inputs in HBM and ran
the *dense* T x T DP per pair — the learned sparsification never reached the
workload. This module fuses the pair expansion into the kernels instead:

SP-DTW Gram kernel (``gram_spdtw_block``)
  * grid = (A-tile, B-tile, active-path-tile); the innermost axis sweeps the
    row-major schedule of active S x S weight tiles (scalar-prefetched meta:
    ti, tj, slot, top/left/diag-active bits);
  * each (Ti, ba, d*S) A-stripe (queries on sublanes) / (Ti, d*S, bb)
    B-stripe (corpus series on lanes; both tile-stacked from
    ``backends.to_tile_stack``, ba x bb = 8 x 128 keeps the blocks
    lane-legal) is block-specced with an index map constant in the inner
    axes, so Pallas's pipeline loads it into VMEM **once** per (A-tile,
    B-tile) step and revisits it for the whole active sweep — no HBM pair
    expansion ever exists;
  * the ba x bb pair block is one (ba, bb) value, pair (ia, ib) at
    (sublane ia, lane ib): x enters as a lane broadcast, y as a sublane
    broadcast, the weight as an SMEM scalar, and a tile row is S such
    values, one per column, so the DP is elementwise over the block with
    no lane scan. Its arithmetic is ``spdtw_block.tile_sweep``'s, step
    for step, and the parity tests hold it bit-identical to the scan
    engine below;
  * DP state flows between active tiles through VMEM scratch, stacks of
    (ba, bb) blocks on leading axes: ``row_edge`` (bottom edges per tile
    column), ``col_edge`` (right edge of the left tile), ``corner_next``
    (top-left corner), ``d_ri`` (result-cell capture). All cross-tile
    reads are guarded by the prefetched neighbour bits so skipped tiles
    contribute +INF, and every value consumed in a (A-tile, B-tile) step
    was produced in the same step's sweep — scratch never leaks between
    pair blocks;
  * work is Na*Nb*n_active*S^2 instead of Na*Nb*T^2: the paper's
    "complexity linear in surviving cells" claim, at tile granularity, on
    the workload that matters.

SP-K_rdtw Gram kernel (``gram_log_krdtw_block``)
  * grid = (A-tile, B-tile); the (ba*bb) pair batch is formed in VMEM
    (sublane repeat / concat, ``_pair_batch``) and swept with the shared
    anti-diagonal ``krdtw_sweep`` (log-rescaled K1+K2 recursion) under
    the diagonal-major learned support mask.

``gram_spdtw_scan`` is the same active-tile schedule as a jnp ``lax.scan``
over ``tile_sweep`` on the (ba*bb, S) pair batch: the CPU/GPU production
path and the oracle the Pallas kernels are tested against. Backend
selection lives in ``repro.kernels.ops`` / ``repro.core.measures.pairwise``.

Early-abandon sweep (DESIGN.md §4). Both SP-DTW engines optionally carry a
per-pair *alive* flag and a per-query threshold through the active-tile
schedule. Cell costs are non-negative, so once a tile row of the DP is
complete, ``min_j D(r, j)`` is an admissible lower bound on the final
value: at the first tile of each new tile row (the ``row_first`` plan bit)
the running row-min is compared against the threshold and pairs that
provably cannot beat it are abandoned — their elements keep streaming
through the vector engine, but the Pallas kernel skips the whole tile
sweep once *every* pair of its (A-tile, B-tile) block is dead, and
abandoned pairs report +INF. With default (+INF) thresholds the engines
are bit-identical to the unabandoned path. ``alive0`` lets the 1-NN
cascade (``ops.knn_cascade``) pre-kill pairs already pruned by the
lower-bound stages, so the DP only ever runs on the survivors.

In-DP PrunedDTW (DESIGN.md §14). When per-query thresholds are supplied,
both SP-DTW engines further prune *inside* the DP: after every row of a
tile sweep, cells above the pair's threshold are snapped to +INF (cell
costs are non-negative, so such cells can never feed a final value within
the bound — Herrmann & Webb's PrunedDTW, at lane granularity), and a tile
whose every incoming edge exceeds the bound is skipped before its cost
rows are ever formed (``lax.cond`` on the scan path, a ``pl.when`` gate +
explicit +INF edge publish on the Pallas path). Per-pair live-tile
counters (``gram_spdtw_scan(..., return_tiles=True)``) expose the work
actually done — the BENCH_prune artifact tracks it shrinking below the
static support as cascade thresholds tighten.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.occupancy import BlockSparsePaths
from .spdtw_block import INF, result_tile_step, tile_sweep
from .krdtw_wavefront import krdtw_sweep, mask_to_diagonal_major


def _pair_batch(xa: jnp.ndarray, yb: jnp.ndarray, ba: int, bb: int):
    """Expand (ba, S) x (bb, S) tiles to the (ba*bb, S) pair batch in VMEM.

    Pair p = ia*bb + ib maps to (A row ia, B row ib): x rows are sublane-
    repeated, y rows block-tiled — the only place pair expansion happens,
    and it never touches HBM.
    """
    x = jnp.repeat(xa, bb, axis=0)                    # row p -> xa[p // bb]
    y = jnp.concatenate([yb] * ba, axis=0)            # row p -> yb[p % bb]
    return x, y


# ---------------------------------------------------------------------------
# SP-DTW: (A-tile, B-tile, active-tile) fused Pallas kernel
# ---------------------------------------------------------------------------

PAIR_BLOCK = (8, 128)    # the Gram kernel's (A rows, B rows) pair block
_CNT_TILE = (8, 128)     # the smallest lane-legal float32 output block


def _sweep_rows(xa, yb, w_ref, row_edge, left_in, col_edge, d_ri, xs, ys,
                tj, thr, *, S: int, ri: int, rj: int, d: int):
    """Sweep the S rows of one tile for a (ba, bb) pair block.

    Each cell of the DP is one (ba, bb) value, pair (ia, ib) at (sublane
    ia, lane ib), and a tile row is S such values, one per column, so
    every operation is elementwise over the whole block. The arithmetic
    is ``spdtw_block.tile_sweep``'s, operation for operation and in the
    same order, which keeps the values bit-identical to the scan engine
    (the parity tests assert it): the Hillis-Steele shift by k picks a
    column statically, and columns below k, which a step leaves
    unchanged, are not touched. ``row_edge[tj]`` holds the row above on
    entry and the tile's bottom row on exit; row t reads its diagonal
    and left edge at ``left_in[t]`` / ``left_in[t + 1]`` and publishes
    its last cell to ``col_edge[t]``, its cell ``rj`` to ``d_ri`` when
    t == ri. The weight of cell (t, j) is a scalar from SMEM.
    """
    blk = xs.shape[1:]
    for r in range(d * S):
        # x's element r of each query along lanes, y's of each corpus
        # series along sublanes
        xs[r] = jnp.broadcast_to(xa[:, r:r + 1], blk)
        ys[r] = jnp.broadcast_to(yb[r:r + 1, :], blk)

    def row(t, carry):
        xt = [xs[k * S + t] for k in range(d)]
        c = []
        for j in range(S):
            acc = None
            for k in range(d):
                dk = (xt[k] - ys[k * S + j]) ** 2
                acc = dk if acc is None else acc + dk
            w = w_ref[0, t, j]
            c.append(jnp.where(w > 0, acc * w, INF))
        prev = [row_edge[tj, j] for j in range(S)]
        topleft = [left_in[t]] + prev[:-1]
        m = [c[j] + jnp.minimum(prev[j], topleft[j]) for j in range(S)]
        # inject the left-tile boundary as a virtual D_{-1}
        m[0] = jnp.minimum(m[0], left_in[t + 1] + c[0])
        s, k = c, 1
        while k < S:
            m = m[:k] + [jnp.minimum(m[j], m[j - k] + s[j])
                         for j in range(k, S)]
            s = s[:k] + [jnp.minimum(s[j - k] + s[j], INF)
                         for j in range(k, S)]
            k *= 2
        for j in range(S):
            v = jnp.minimum(m[j], INF)
            if thr is not None:
                v = jnp.where(v <= thr, v, INF)
            row_edge[tj, j] = v
            m[j] = v
        col_edge[t] = m[S - 1]

        @pl.when(t == ri)
        def _():
            d_ri[...] = m[rj]
        return carry

    jax.lax.fori_loop(0, S, row, 0)


def _gram_spdtw_kernel(meta_ref, a_ref, b_ref, w_ref, thr_ref, alive0_ref,
                       out_ref, cnt_ref, row_edge, col_edge, left_in, xs, ys,
                       corner_next, d_ri, alive, *, S: int, g_out: int,
                       ri: int, rj: int, d: int, prune: bool):
    """One grid step = one active tile for one (A-stripe, B-stripe) block.

    Per-pair state is (ba, bb) blocks, pair (ia, ib) at (sublane ia,
    lane ib); edges are stacks of them on leading axes, so every dynamic
    index (tile column, row) is a leading-axis ref index. ``cnt_ref`` is
    the block's (8, 128) counter tile: [0, 0] counts the tile sweeps run,
    [0, 1] sums the live pairs over those sweeps."""
    g = pl.program_id(2)
    blk = alive.shape

    @pl.when(g == 0)
    def _():
        # row_edge must start at +INF for the early-abandon row-min to be
        # meaningful (entries of never-written columns would otherwise be
        # stale cross-block data); alive starts from the cascade's
        # bound-stage survivors (all-ones when no cascade is running)
        row_edge[...] = jnp.full(row_edge.shape, INF, jnp.float32)
        alive[...] = alive0_ref[...]
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.float32)

    # early-abandon check at the first tile of each new tile row: the
    # previous tile row is complete, so the running row-min is an
    # admissible lower bound on every pair's final value (rows past the
    # result tile row are excluded via g <= g_out)
    row_first = meta_ref[g, 6] > 0
    thr = jnp.broadcast_to(thr_ref[...], blk)           # per query (sublane)

    @pl.when(row_first & (g > 0) & (g <= g_out))
    def _():
        bound = jnp.min(row_edge[...], axis=(0, 1))            # (ba, bb)
        alive[...] = alive[...] * (bound <= thr).astype(jnp.float32)

    tj = meta_ref[g, 1]
    top_ok = meta_ref[g, 3] > 0
    left_ok = meta_ref[g, 4] > 0
    diag_ok = meta_ref[g, 5] > 0

    # --- gather incoming edges (guarded against inactive neighbours) ---
    top = jnp.where(top_ok, row_edge[tj], INF)             # (S, ba, bb)
    left = jnp.where(left_ok, col_edge[...], INF)
    c_first = jnp.where(
        g == 0, jnp.zeros(blk, jnp.float32),
        jnp.where(diag_ok,
                  jnp.where(left_ok, corner_next[...],
                            # guarded: only read when diag_ok (=> tj > 0);
                            # clamp keeps the untaken branch in-bounds
                            row_edge[jnp.maximum(tj - 1, 0), S - 1]),
                  INF))
    new_corner = top[S - 1]

    if prune:
        # in-DP PrunedDTW tile skip: a tile whose every incoming edge
        # exceeds the pair's bound cannot hold any cell <= bound (costs
        # are non-negative), so its exact pruned sweep is all-+INF rows
        # — skip the sweep whenever no pair in the block is both alive
        # and edge-live, and publish those +INF rows below
        edge_live = ((jnp.min(top, axis=0) <= thr)
                     | (jnp.min(left, axis=0) <= thr)
                     | (c_first <= thr))
        live = alive[...] * edge_live.astype(jnp.float32)          # (ba, bb)
    else:
        # the whole tile sweep is skipped once every pair is dead
        live = alive[...]
    do_sweep = jnp.any(live > 0)

    # counters: a sweep runs iff some pair is live, so the block's live
    # count is its contribution to the swept pairs, and 0 when skipped
    n_live = jnp.sum(jnp.sum(live, axis=1, keepdims=True), axis=0,
                     keepdims=True)                                # (1, 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, cnt_ref.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, cnt_ref.shape, 1)
    cnt_ref[...] += jnp.where(
        sub == 0, jnp.where(lane == 0, jnp.minimum(n_live, 1.0),
                            jnp.where(lane == 1, n_live, 0.0)), 0.0)

    @pl.when(do_sweep)
    def _():
        # tile-stacked layouts: tile ti's d channel planes are one slab,
        # queries on sublanes for A, corpus series on lanes for B
        ti = meta_ref[g, 0]
        corner_next[...] = new_corner
        row_edge[tj] = top
        left_in[0] = c_first
        left_in[pl.ds(1, S)] = left
        _sweep_rows(a_ref[ti], b_ref[tj], w_ref, row_edge, left_in,
                    col_edge, d_ri, xs, ys, tj, thr if prune else None,
                    S=S, ri=ri, rj=rj, d=d)

    if prune:
        @pl.when(~do_sweep)
        def _():
            # publish exactly what the pruned sweep would have: all-+INF
            # rows (never stale state — downstream tiles of this pair
            # block consume these edges)
            corner_next[...] = new_corner
            row_edge[tj] = jnp.full(top.shape, INF, jnp.float32)
            col_edge[...] = jnp.full(top.shape, INF, jnp.float32)
            d_ri[...] = jnp.full(blk, INF, jnp.float32)

    # capture at the tile holding the global result cell (NOT the last
    # active tile — the support may be active past the corner, or raw user
    # weights may not reach it at all; see ``result_tile_step``); abandoned
    # pairs report +INF (their cells may hold garbage from skipped sweeps)
    @pl.when(g == g_out)
    def _():
        out_ref[...] = jnp.where(alive[...] > 0, d_ri[...], INF)


@functools.partial(jax.jit,
                   static_argnames=("S", "n_active", "T_orig", "g_out",
                                    "ba", "bb", "d", "prune", "interpret"))
def _gram_spdtw_call(meta, A, B, blocks, thr, alive0, *, S, n_active, T_orig,
                     g_out, ba, bb, d, prune, interpret):
    """(Nap, Nbp) Gram values, and two int32 counts summed over the pair
    blocks: the tile sweeps run, and the live pairs over those sweeps.

    A is tile-stacked with queries on sublanes, (Ti, Nap, d*S); B with
    time on sublanes and corpus series on lanes, (Ti, d*S, Nbp)."""
    Ti, Nap, _ = A.shape
    Nbp = B.shape[2]
    last = T_orig - 1
    ri, rj = last % S, last % S
    grid = (Nap // ba, Nbp // bb, n_active)
    kernel = functools.partial(_gram_spdtw_kernel, S=S, g_out=g_out,
                               ri=ri, rj=rj, d=d, prune=prune)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # index maps constant in the inner axes: each stripe is copied to
            # VMEM once per (A-tile, B-tile) and revisited for every g
            pl.BlockSpec((Ti, ba, d * S), lambda i, j, g, m: (0, i, 0)),
            pl.BlockSpec((Ti, d * S, bb), lambda i, j, g, m: (0, 0, j)),
            # the tile's weights, read one scalar a cell
            pl.BlockSpec((1, S, S), lambda i, j, g, m: (m[g, 2], 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((ba, 1), lambda i, j, g, m: (i, 0)),    # thresholds
            pl.BlockSpec((ba, bb), lambda i, j, g, m: (i, j)),   # alive0
        ],
        out_specs=[
            pl.BlockSpec((ba, bb), lambda i, j, g, m: (i, j)),
            # one (8, 128) counter tile per pair block, resident across g
            pl.BlockSpec(_CNT_TILE, lambda i, j, g, m: (i, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Ti, S, ba, bb), jnp.float32),   # row_edge
            pltpu.VMEM((S, ba, bb), jnp.float32),       # col_edge
            pltpu.VMEM((S + 1, ba, bb), jnp.float32),   # corner, left edge
            pltpu.VMEM((d * S, ba, bb), jnp.float32),   # x, lane-broadcast
            pltpu.VMEM((d * S, ba, bb), jnp.float32),   # y, sublane-broadcast
            pltpu.VMEM((ba, bb), jnp.float32),          # corner_next
            pltpu.VMEM((ba, bb), jnp.float32),          # d_ri: result cell
            pltpu.VMEM((ba, bb), jnp.float32),          # alive flags
        ],
    )
    ni, nj = grid[0], grid[1]
    out, cnt = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Nap, Nbp), jnp.float32),
                   jax.ShapeDtypeStruct((ni * _CNT_TILE[0],
                                         nj * _CNT_TILE[1]), jnp.float32)],
        interpret=interpret,
    )(meta, A, B, blocks, thr, alive0)
    # per-block counts stay below 2**24, so the float32 tiles are exact
    cnt = cnt.reshape(ni, _CNT_TILE[0], nj, _CNT_TILE[1])[:, 0, :, :2]
    cnt = cnt.astype(jnp.int32)
    return out, jnp.sum(cnt[..., 0]), jnp.sum(cnt[..., 1])


def _pad_rows_cols(X: jnp.ndarray, n_to: int, t_to: int) -> jnp.ndarray:
    N, T = X.shape
    return jnp.pad(X.astype(jnp.float32), ((0, n_to - N), (0, t_to - T)))


def _pad_abandon_state(thresholds, alive0, Na, Nb, Nap, Nbp):
    """Pad the early-abandon operands to the tile batch.

    Defaults (no cascade): +INF thresholds / all-alive — bit-identical to
    the unabandoned engines. When a cascade mask is supplied, padding
    pairs start dead, so ragged fills cost nothing.
    """
    if thresholds is None:
        thr = jnp.full((Nap, 1), INF, jnp.float32)
    else:
        thr = jnp.pad(jnp.asarray(thresholds, jnp.float32).reshape(Na, 1),
                      ((0, Nap - Na), (0, 0)), constant_values=INF)
    if alive0 is None:
        alive = jnp.ones((Nap, Nbp), jnp.float32) if thresholds is None \
            else jnp.pad(jnp.ones((Na, Nb), jnp.float32),
                         ((0, Nap - Na), (0, Nbp - Nb)))
    else:
        alive = jnp.pad(jnp.asarray(alive0).astype(jnp.float32),
                        ((0, Nap - Na), (0, Nbp - Nb)))
    return thr, alive


def gram_spdtw_block(A: jnp.ndarray, B: jnp.ndarray, bsp: BlockSparsePaths,
                     T_orig: int | None = None, ba: int = PAIR_BLOCK[0],
                     bb: int = PAIR_BLOCK[1],
                     thresholds: jnp.ndarray | None = None,
                     alive0: jnp.ndarray | None = None,
                     interpret: bool = False, return_counts: bool = False):
    """All-pairs SP-DTW Gram matrix via the fused block-sparse Pallas kernel.

    A: (Na, T) or (Na, T, d); B likewise. Returns (Na, Nb) SP-DTW values
    (>= 1e29 where the support admits no path). Ragged Na/Nb are padded to
    the tile batch and sliced back. ``thresholds`` ((Na,), per-A-row) and
    ``alive0`` ((Na, Nb) bool) switch on the early-abandon sweep: pairs
    that start dead or whose running row-min exceeds the threshold report
    +INF. Giving thresholds also engages the in-DP PrunedDTW path (live
    pruned row boundaries + boundary-dead tile skips): entries whose true
    value exceeds the threshold may report +INF, entries at or below it
    are bit-identical to the exact sweep.

    ``return_counts=True`` also returns the work done, as two int32
    device scalars summed over the ba x bb pair blocks: the tile sweeps
    the kernel ran, and the live pairs (alive, and with thresholds also
    edge-live) over those sweeps — the pairs whose lanes did useful work.
    """
    from .backends import series_dim, to_tile_stack
    Na, T = A.shape[0], A.shape[1]
    Nb = B.shape[0]
    d = series_dim(A)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    meta = bsp.plan()
    n_active = meta.shape[0]
    g_out = result_tile_step(meta, bsp.tile, T_orig)
    if g_out < 0:   # corner cell outside the support: no admissible path
        G = jnp.full((Na, Nb), INF, jnp.float32)
        return (G, (jnp.int32(0), jnp.int32(0))) if return_counts else G
    Nap = ((Na + ba - 1) // ba) * ba
    Nbp = ((Nb + bb - 1) // bb) * bb
    thr, alive = _pad_abandon_state(thresholds, alive0, Na, Nb, Nap, Nbp)
    out, sweeps, live = _gram_spdtw_call(
        jnp.asarray(meta), to_tile_stack(A, bsp.tile, bsp.T, n_to=Nap),
        to_tile_stack(B, bsp.tile, bsp.T, n_to=Nbp).transpose(0, 2, 1),
        jnp.asarray(bsp.blocks),
        thr, alive, S=bsp.tile, n_active=n_active, T_orig=T_orig,
        g_out=g_out, ba=ba, bb=bb, d=d, prune=thresholds is not None,
        interpret=interpret)
    return (out[:Na, :Nb], (sweeps, live)) if return_counts \
        else out[:Na, :Nb]


# ---------------------------------------------------------------------------
# SP-DTW: jnp scan engines (CPU/GPU production path + oracle)
# ---------------------------------------------------------------------------

def _tile_scan(meta, blocks, get_xy, P, Tp, thr_p, alive_p, *, S, g_out, ri,
               sweep=tile_sweep, neutral: float = INF, stash: bool = False,
               d: int = 1, prune: bool = False, count: bool = False):
    """Shared lax.scan over the active-tile schedule (DP wavefront order).

    ``get_xy(ti, tj) -> ((P, d*S), (P, d*S))`` supplies the per-pair series
    tiles (tile-major / channel-inner; d = 1 is the historical (P, S)) —
    the cross-product Gram engine expands (A-stripe x B-stripe)
    batches, the paired engine slices aligned rows. Returns
    (row_edge, dri, alive) after the sweep: the final bottom-edge state
    (its row-min is an admissible lower bound — the prefix-bound stage),
    the captured result row of step ``g_out`` (pass g_out=-2 to skip
    capture) and the per-pair alive flags after early abandoning.

    ``sweep``/``neutral`` parameterize the per-tile DP and its
    "unreachable" sentinel: (``tile_sweep``, +INF) is the min-plus hard
    SP-DTW; the soft engines in ``soft_block`` pass the log-semiring
    sweep with neutral = NEG (edges then carry L = -R/gamma). The
    early-abandon row-min check only makes sense in min-plus space —
    soft callers pass +INF thresholds, which keep every pair alive.

    ``stash=True`` expects a sweep returning a fourth value — the full
    (P, S*S) tile block — and stacks it as the scan's ys (the soft
    backward's L-block residual, DESIGN.md §11): the return grows a
    fourth element, Lstash (n_active, P, S*S). DP state dtype follows
    ``blocks`` (f64 for the oracle-grade parity checks).

    ``prune=True`` engages the in-DP PrunedDTW path (DESIGN.md §14): the
    per-row clamp inside ``tile_sweep`` snaps cells above the per-pair
    threshold to +INF, and a tile whose every incoming edge exceeds the
    bound is *skipped entirely* via ``lax.cond`` — its cost rows are
    never formed, and the all-+INF rows its pruned sweep would have
    produced are published instead. Min-plus hard sweeps only (asserted
    off for soft callers). ``count=True`` additionally carries a (P, 1)
    int32 per-pair live-tile counter (tiles where the pair was alive
    with at least one live edge — the DP work actually attributable to
    it) and an int32 count of the tile sweeps run (with ``prune``, those
    where some pair was live), and returns them as a fourth and fifth
    element; incompatible with ``stash``.
    """
    assert not (stash and (prune or count)), \
        "prune/count are hard-sweep features; the stash path is soft-only"
    n_active = meta.shape[0]
    dtype = blocks.dtype
    inf_row = jnp.full((P, S), neutral, dtype)

    def step(carry, inp):
        if count:
            row_edge, col_edge, corner, dri_out, alive, tiles, sweeps = carry
        else:
            row_edge, col_edge, corner, dri_out, alive = carry
        k, m = inp
        ti, tj, slot = m[0], m[1], m[2]
        # early-abandon check at the first tile of each new tile row (the
        # previous row is complete => min_j row_edge lower-bounds every
        # pair's final value; rows past the result tile are excluded)
        check = (m[6] > 0) & (k > 0) & (k <= g_out)
        bound = jnp.min(row_edge, axis=1, keepdims=True)       # (P, 1)
        alive = alive & jnp.where(check, bound <= thr_p, True)
        w = blocks[slot]
        top_raw = jax.lax.dynamic_slice_in_dim(row_edge, tj * S, S, axis=1)
        top_vec = jnp.where(m[3] > 0, top_raw, inf_row)
        left_vec = jnp.where(m[4] > 0, col_edge, inf_row)
        corner_row = jax.lax.dynamic_slice_in_dim(
            row_edge, jnp.maximum(tj * S - 1, 0), 1, axis=1)
        c_first = jnp.where(
            k == 0, jnp.zeros((P, 1), dtype),
            jnp.where(m[5] > 0,
                      jnp.where(m[4] > 0, corner, corner_row),
                      jnp.full((P, 1), neutral, dtype)))
        if prune:
            # a tile is live for a pair iff some incoming edge is within
            # the bound — costs are non-negative, so a boundary-dead
            # tile's pruned sweep is all-+INF rows; skip cost-row
            # formation entirely when no pair needs it
            edge_live = (
                (jnp.min(top_vec, axis=1, keepdims=True) <= thr_p)
                | (jnp.min(left_vec, axis=1, keepdims=True) <= thr_p)
                | (c_first <= thr_p))
            live = alive & edge_live

            def run_tile(_):
                x, y = get_xy(ti, tj)
                return sweep(x, y, w, top_vec, left_vec, c_first,
                             S=S, ri=ri, d=d, thr=thr_p)[:3]

            d_last, rightcol, dri = jax.lax.cond(
                jnp.any(live), run_tile,
                lambda _: (inf_row, inf_row, inf_row), None)
            rest = ()
        else:
            live = alive
            x, y = get_xy(ti, tj)
            out = sweep(x, y, w, top_vec, left_vec, c_first, S=S, ri=ri, d=d)
            (d_last, rightcol, dri), rest = out[:3], out[3:]
        row_edge = jax.lax.dynamic_update_slice_in_dim(row_edge, d_last,
                                                       tj * S, axis=1)
        # keep the dri of the tile holding the global result cell (see
        # ``result_tile_step``), not whatever tile happens to run last
        dri_out = jnp.where(k == g_out, dri, dri_out)
        if count:
            tiles = tiles + live.astype(jnp.int32)
            ran = jnp.any(live) if prune else jnp.array(True)
            sweeps = sweeps + ran.astype(jnp.int32)
            carry = (row_edge, rightcol, top_vec[:, S - 1:S], dri_out,
                     alive, tiles, sweeps)
        else:
            carry = (row_edge, rightcol, top_vec[:, S - 1:S], dri_out, alive)
        return carry, (rest[0] if stash else None)

    init = (jnp.full((P, Tp), neutral, dtype), inf_row,
            jnp.full((P, 1), neutral, dtype), inf_row, alive_p)
    if count:
        init = init + (jnp.zeros((P, 1), jnp.int32), jnp.int32(0))
        (row_edge, _, _, dri, alive, tiles, sweeps), _ = jax.lax.scan(
            step, init, (jnp.arange(n_active), meta))
        return row_edge, dri, alive, tiles, sweeps
    (row_edge, _, _, dri, alive), Lstash = jax.lax.scan(
        step, init, (jnp.arange(n_active), meta))
    if stash:
        return row_edge, dri, alive, Lstash
    return row_edge, dri, alive


@functools.partial(jax.jit, static_argnames=("S", "T_orig", "g_out", "d",
                                             "prune", "count"))
def _gram_spdtw_scan_call(meta, A, B, blocks, thr, alive0, *, S, T_orig,
                          g_out, d, prune=False, count=False):
    Na = A.shape[0]
    Tp = A.shape[1] // d
    Nb = B.shape[0]
    P = Na * Nb
    last = T_orig - 1
    ri, rj = last % S, last % S
    thr_p = jnp.repeat(thr.reshape(Na, 1), Nb, axis=0)         # (P, 1)

    def get_xy(ti, tj):
        xa = jax.lax.dynamic_slice(A, (0, ti * d * S), (Na, d * S))
        yb = jax.lax.dynamic_slice(B, (0, tj * d * S), (Nb, d * S))
        return _pair_batch(xa, yb, Na, Nb)

    res = _tile_scan(meta, blocks, get_xy, P, Tp, thr_p,
                     alive0.reshape(P, 1) > 0,
                     S=S, g_out=g_out, ri=ri, d=d, prune=prune, count=count)
    if count:
        _, dri, alive, tiles, _ = res
    else:
        (_, dri, alive), tiles = res, None
    val = jax.lax.dynamic_slice_in_dim(dri, rj, 1, axis=1)
    G = jnp.where(alive, val, INF).reshape(Na, Nb)
    return (G, tiles.reshape(Na, Nb)) if count else G


def gram_spdtw_scan(A: jnp.ndarray, B: jnp.ndarray, bsp: BlockSparsePaths,
                    T_orig: int | None = None, block_a: int = 64,
                    thresholds: jnp.ndarray | None = None,
                    alive0: jnp.ndarray | None = None,
                    return_tiles: bool = False) -> jnp.ndarray:
    """All-pairs SP-DTW Gram matrix: lax.scan over the active-tile schedule.

    A: (Na, T) or (Na, T, d); B likewise. Same schedule, edge dataflow and
    ``tile_sweep`` math as the Pallas kernel, expressed as a scan — work
    is Na*Nb*n_active*S^2 on any backend and the pair batch is broadcast
    per tile, never materialized in HBM at (Na*Nb, T). A rows are chunked
    (``block_a``) to bound the carried edge-state footprint.
    ``thresholds`` / ``alive0`` drive the same early-abandon + in-DP
    PrunedDTW sweep as the Pallas kernel: entries at or below the
    threshold are bit-identical to the exact Gram, entries above it may
    report +INF, and boundary-dead tiles skip cost-row formation outright
    (``lax.cond``), so per-pair work shrinks below the static support as
    thresholds tighten. ``return_tiles=True`` additionally returns the
    (Na, Nb) int32 per-pair live-tile counts (the DP work actually done;
    n_active everywhere when no thresholds are given).
    """
    from .backends import series_dim, to_tile_major
    Na, T = A.shape[0], A.shape[1]
    Nb = B.shape[0]
    d = series_dim(A)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    if g_out < 0:   # corner cell outside the support: no admissible path
        G = jnp.full((Na, Nb), INF, jnp.float32)
        return (G, jnp.zeros((Na, Nb), jnp.int32)) if return_tiles else G
    meta = jnp.asarray(bsp.plan())
    blocks = jnp.asarray(bsp.blocks)
    Ap = to_tile_major(A, bsp.tile, bsp.T)
    Bp = to_tile_major(B, bsp.tile, bsp.T)
    thr, alive = _pad_abandon_state(thresholds, alive0, Na, Nb, Na, Nb)
    prune = thresholds is not None
    rows, tile_rows = [], []
    for s in range(0, Na, block_a):
        out = _gram_spdtw_scan_call(
            meta, Ap[s:s + block_a], Bp, blocks, thr[s:s + block_a],
            alive[s:s + block_a], S=bsp.tile, T_orig=T_orig, g_out=g_out,
            d=d, prune=prune, count=return_tiles)
        if return_tiles:
            rows.append(out[0])
            tile_rows.append(out[1])
        else:
            rows.append(out)
    G = jnp.concatenate(rows, axis=0)
    if return_tiles:
        return G, jnp.concatenate(tile_rows, axis=0)
    return G


@functools.partial(jax.jit, static_argnames=("S", "T_orig", "g_out", "d",
                                             "prune"))
def _spdtw_paired_scan_call(meta, X, Y, blocks, thr, *, S, T_orig, g_out, d,
                            prune=False):
    """(P,) values, the tile sweeps run and the live pairs over them."""
    P = X.shape[0]
    Tp = X.shape[1] // d
    last = T_orig - 1
    ri, rj = last % S, last % S

    def get_xy(ti, tj):
        return (jax.lax.dynamic_slice(X, (0, ti * d * S), (P, d * S)),
                jax.lax.dynamic_slice(Y, (0, tj * d * S), (P, d * S)))

    _, dri, alive, tiles, sweeps = _tile_scan(
        meta, blocks, get_xy, P, Tp, thr.reshape(P, 1),
        jnp.ones((P, 1), bool), S=S, g_out=g_out, ri=ri, d=d, prune=prune,
        count=True)
    val = jax.lax.dynamic_slice_in_dim(dri, rj, 1, axis=1)
    return jnp.where(alive, val, INF).reshape(P), sweeps, jnp.sum(tiles)


def spdtw_paired_scan(x: jnp.ndarray, y: jnp.ndarray, bsp: BlockSparsePaths,
                      T_orig: int | None = None,
                      thresholds: jnp.ndarray | None = None,
                      block_p: int = 4096, return_counts: bool = False):
    """Batched *aligned-pair* SP-DTW over the active-tile schedule.

    x, y: (B, T) or (B, T, d) — pair p is (x[p], y[p]), no cross product.
    Same schedule and ``tile_sweep`` math as the Gram engines, so work is
    B*n_active*S^2: unlike ``ref.wdtw_batch`` this exploits the learned
    sparsity on CPU/GPU too. The cascade's survivor stage runs here after
    gathering the pairs that outlived the bounds. Optional per-pair
    ``thresholds`` engage the early-abandon + in-DP PrunedDTW sweep
    (values <= threshold exact, above it possibly +INF, boundary-dead
    tiles skipped outright). ``return_counts=True`` also returns the
    counts of ``gram_spdtw_block``'s, summed over the ``block_p`` chunks:
    the tile sweeps run (a chunk is one sweep unit) and the live pairs
    over them.
    """
    from .backends import series_dim, to_tile_major
    B, T = x.shape[0], x.shape[1]
    d = series_dim(x)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    if g_out < 0:   # corner cell outside the support: no admissible path
        out = jnp.full((B,), INF, jnp.float32)
        return (out, (jnp.int32(0), jnp.int32(0))) if return_counts else out
    meta = jnp.asarray(bsp.plan())
    blocks = jnp.asarray(bsp.blocks)
    xp = to_tile_major(x, bsp.tile, bsp.T)
    yp = to_tile_major(y, bsp.tile, bsp.T)
    thr = jnp.full((B,), INF, jnp.float32) if thresholds is None \
        else jnp.asarray(thresholds, jnp.float32)
    outs, sweeps, live = [], [], []
    for s in range(0, B, block_p):
        o, sw, lv = _spdtw_paired_scan_call(
            meta, xp[s:s + block_p], yp[s:s + block_p], blocks,
            thr[s:s + block_p], S=bsp.tile, T_orig=T_orig, g_out=g_out,
            d=d, prune=thresholds is not None)
        outs.append(o)
        sweeps.append(sw)
        live.append(lv)
    out = jnp.concatenate(outs, axis=0)
    if not return_counts:
        return out
    return out, (sum(sweeps[1:], sweeps[0]), sum(live[1:], live[0]))


# ---------------------------------------------------------------------------
# SP-DTW: truncated prefix-DP lower bound (the cascade's stage 3)
# ---------------------------------------------------------------------------

def prefix_tile_count(bsp: BlockSparsePaths, frac: float,
                      T_orig: int) -> int:
    """Number of leading plan steps covering the first ``frac`` of the tile
    rows (clamped so every bounded row is a real DP row < T_orig)."""
    if frac <= 0:
        return 0
    kt = min(int(round(frac * (bsp.T // bsp.tile))), T_orig // bsp.tile)
    if kt <= 0:
        return 0
    meta = bsp.plan()
    return int((meta[:, 0] < kt).sum())


@functools.partial(jax.jit, static_argnames=("S", "d"))
def _gram_prefix_bound_call(meta_p, A, B, blocks, *, S, d):
    Na = A.shape[0]
    Tp = A.shape[1] // d
    Nb = B.shape[0]
    P = Na * Nb

    def get_xy(ti, tj):
        xa = jax.lax.dynamic_slice(A, (0, ti * d * S), (Na, d * S))
        yb = jax.lax.dynamic_slice(B, (0, tj * d * S), (Nb, d * S))
        return _pair_batch(xa, yb, Na, Nb)

    row_edge, _, _ = _tile_scan(
        meta_p, blocks, get_xy, P, Tp, jnp.full((P, 1), INF, jnp.float32),
        jnp.ones((P, 1), bool), S=S, g_out=-2, ri=0, d=d)
    # min over the final bottom-edge state: every entry is a true D value
    # of some prefix row (or +INF init), so the min lower-bounds the final
    # DP value of each pair — the sDTW/PrunedDTW prefix bound at tile
    # granularity
    return jnp.min(row_edge, axis=1).reshape(Na, Nb)


def gram_prefix_bound(A: jnp.ndarray, B: jnp.ndarray, bsp: BlockSparsePaths,
                      n_prefix: int, T_orig: int | None = None,
                      block_a: int = 64) -> jnp.ndarray:
    """(Na, Nb) admissible lower bound from the first ``n_prefix`` steps of
    the active-tile schedule (see ``prefix_tile_count``). Costs
    n_prefix / n_active of the full Gram sweep; used by the cascade to
    prune candidates the cheap envelope bounds cannot."""
    from .backends import series_dim, to_tile_major
    Na, T = A.shape[0], A.shape[1]
    d = series_dim(A)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    meta = bsp.plan()
    n_prefix = min(n_prefix, meta.shape[0])
    if n_prefix <= 0:
        return jnp.zeros((Na, B.shape[0]), jnp.float32)
    meta_p = jnp.asarray(meta[:n_prefix])
    blocks = jnp.asarray(bsp.blocks)
    Ap = to_tile_major(A, bsp.tile, bsp.T)
    Bp = to_tile_major(B, bsp.tile, bsp.T)
    rows = []
    for s in range(0, Na, block_a):
        rows.append(_gram_prefix_bound_call(meta_p, Ap[s:s + block_a], Bp,
                                            blocks, S=bsp.tile, d=d))
    return jnp.concatenate(rows, axis=0)


# ---------------------------------------------------------------------------
# SP-K_rdtw: (A-tile, B-tile) fused wavefront kernel
# ---------------------------------------------------------------------------

def _gram_krdtw_kernel(a_ref, b_ref, mask_ref, out_ref,
                       *, T: int, nu: float, radius: int | None,
                       use_mask: bool, ba: int, bb: int):
    x, y = _pair_batch(a_ref[...], b_ref[...], ba, bb)   # (ba*bb, T)
    yr = y[:, ::-1]
    dxr = jnp.exp(-nu * (x[:, ::-1] - yr) ** 2)
    logk = krdtw_sweep(x, yr, dxr, mask_ref[...], T=T, nu=nu,
                       radius=radius, use_mask=use_mask)
    out_ref[...] = logk.reshape(ba, bb)


@functools.partial(jax.jit, static_argnames=("nu", "radius", "use_mask",
                                             "ba", "bb", "interpret"))
def _gram_krdtw_call(A, B, mask_diag, *, nu, radius, use_mask,
                     ba, bb, interpret):
    Nap, T = A.shape
    Nbp = B.shape[0]
    mrows = mask_diag.shape[0]
    kernel = functools.partial(_gram_krdtw_kernel, T=T, nu=nu, radius=radius,
                               use_mask=use_mask, ba=ba, bb=bb)
    return pl.pallas_call(
        kernel,
        grid=(Nap // ba, Nbp // bb),
        in_specs=[
            pl.BlockSpec((ba, T), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, T), lambda i, j: (j, 0)),
            pl.BlockSpec((mrows, T), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((ba, bb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Nap, Nbp), jnp.float32),
        interpret=interpret,
    )(A, B, mask_diag)


def gram_log_krdtw_block(A: jnp.ndarray, B: jnp.ndarray, nu: float,
                         support: np.ndarray | None = None,
                         radius: int | None = None,
                         ba: int = 8, bb: int = 8,
                         interpret: bool = False) -> jnp.ndarray:
    """All-pairs log K_rdtw / SP-K_rdtw Gram matrix, fused pair expansion.

    A: (Na, T), B: (Nb, T). ``support`` is the learned (T, T) sparse support
    (None = full grid); ``radius`` an optional Sakoe-Chiba corridor.
    Returns (Na, Nb) log-kernel values.
    """
    Na, T = A.shape
    Nb = B.shape[0]
    use_mask = support is not None
    if use_mask:
        mask_diag = jnp.asarray(mask_to_diagonal_major(np.asarray(support)))
    else:
        mask_diag = jnp.ones((1, T), jnp.float32)
    Nap = ((Na + ba - 1) // ba) * ba
    Nbp = ((Nb + bb - 1) // bb) * bb
    out = _gram_krdtw_call(
        _pad_rows_cols(A, Nap, T), _pad_rows_cols(B, Nbp, T),
        mask_diag.astype(jnp.float32), nu=nu, radius=radius,
        use_mask=use_mask, ba=ba, bb=bb, interpret=interpret)
    return out[:Na, :Nb]
