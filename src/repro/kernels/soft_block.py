"""Block-sparse soft-SP-DTW engines over the active-tile schedule
(DESIGN.md §10, §11).

The differentiable measure layer (``repro.core.softdtw``) smooths the
masked min-plus DP into the (logaddexp, +) semiring; these engines run
that recursion on the *same* block-sparse plan as the hard kernels —
``gram_block._tile_scan`` is shared verbatim, parameterized by
``soft_tile_sweep`` (the log-semiring twin of ``spdtw_block.tile_sweep``,
identical edge dataflow) with neutral NEG instead of +INF. All inter-tile
edges carry ``L = -R/gamma``; forward work is Na*Nb*n_active*S^2, exactly
the hard Gram engine's accounting.

Forward engines:
  * ``gram_soft_spdtw_scan``   — all-pairs soft Gram, jnp lax.scan
                                 (CPU/GPU production path + oracle);
  * ``soft_spdtw_paired_scan`` — batched aligned-pair forward;
  * ``gram_soft_spdtw_block``  — fused Pallas kernel, same grid /
                                 BlockSpec / VMEM-scratch layout as
                                 ``gram_block.gram_spdtw_block`` (tested
                                 under the ``tpu`` marker);
  * ``soft_spdtw_fwd_stash`` / ``gram_soft_fwd_stash`` — the same
                                 forwards, additionally *stashing* the
                                 per-tile L blocks (the soft-DTW "keep R"
                                 residual, restricted to active tiles).

Backward engines (DESIGN.md §11 — the reverse active-tile sweep):
  * ``soft_reverse_tile_sweep`` — one tile of the expected-alignment
                                  recursion, pure jnp on values, shared
                                  verbatim by the reverse scan engines and
                                  the fused Pallas backward kernel (the
                                  reverse twin of ``soft_tile_sweep``);
  * ``soft_spdtw_bwd_block`` / ``gram_soft_bwd_scan`` — jnp lax.scan
                                  reverse walks of the cached tile plan
                                  (E-edge halo scratch between tiles);
  * ``gram_soft_bwd_pallas``    — fused Pallas Gram-backward kernel
                                  (``tpu``-marked when compiled);
  * ``soft_alignment_pairs``    — assembled (B, T, T) E matrices for
                                  parity testing against the dense
                                  ``core.softdtw.soft_alignment`` oracle.

Differentiable entries (custom VJPs):
  * ``soft_spdtw_batch``      — batched aligned pairs: block-sparse
                                stash forward, reverse-sweep backward;
  * ``soft_spdtw_gram_batch`` — all-pairs Gram: same; the backend is
                                resolved by capability (DIFFERENTIABLE),
                                which today is scan on every platform.

Gradients are the expected-alignment matrix E contracted with the local
cost derivatives; E is identically zero outside the learned support, so
gradients never leave the sparsified search space. The masked-dense
recursion in ``core.softdtw._expected_alignment`` stays as the oracle
(and the fallback for traced weight grids).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.occupancy import BlockSparsePaths
from repro.core.softdtw import NEG, _coeff, _soft_forward, _soft_grads
from .spdtw_block import INF, result_tile_step
from .gram_block import _pad_rows_cols, _pair_batch, _tile_scan


def _logaddexp_scan_lanes(m, s, width):
    """Hillis-Steele solve of L_j = logaddexp(m_j, L_{j-1} + s_j) over
    lanes — ``spdtw_block._minplus_scan_lanes`` in the log semiring.
    Dtype-preserving: the scan engines run it in f64 for oracle-grade
    parity checks; the Pallas kernels feed f32."""
    d = 1
    while d < width:
        bt = m.shape[0]
        m_sh = jnp.concatenate(
            [jnp.full((bt, d), NEG, m.dtype), m[:, :-d]], axis=1)
        s_sh = jnp.concatenate(
            [jnp.zeros((bt, d), s.dtype), s[:, :-d]], axis=1)
        m = jnp.logaddexp(m, m_sh + s)
        s = jnp.maximum(s_sh + s, jnp.asarray(-1e35, s.dtype))  # floor inf
        d *= 2
    return m


def _linrec_scan_lanes(a, b, width):
    """Hillis-Steele solve of x_j = a_j * x_{j-1} + b_j (x_{-1} = 0) over
    lanes — ``krdtw.linrec_scan`` without ``associative_scan`` so it
    lowers inside Pallas kernels. Combine ((a1,b1),(a2,b2)) ->
    (a1*a2, b1*a2 + b2); identity (1, 0) pads the shifted operands."""
    m, s = b, a
    d = 1
    while d < width:
        bt = m.shape[0]
        m_sh = jnp.concatenate(
            [jnp.zeros((bt, d), m.dtype), m[:, :-d]], axis=1)
        s_sh = jnp.concatenate(
            [jnp.ones((bt, d), s.dtype), s[:, :-d]], axis=1)
        m = m + m_sh * s
        s = s * s_sh
        d *= 2
    return m


def _tile_logit_row(x, y, w, t, *, S: int, gamma: float, d: int = 1):
    """Masked logit row ``t`` of one tile: t(i, j) = -w*phi/gamma, NEG
    outside the support. The soft twin of ``spdtw_block.tile_cost_row``
    — x, y are (bt, d*S) tile-major / channel-inner and the squared
    distance sums over channels before the weight multiply."""
    wt = jax.lax.dynamic_slice_in_dim(w, t, 1, axis=0)          # (1,S)
    acc = None
    for k in range(d):
        xt = jax.lax.dynamic_slice_in_dim(x, k * S + t, 1, axis=1)
        yk = jax.lax.dynamic_slice_in_dim(y, k * S, S, axis=1)
        dk = (xt - yk) ** 2
        acc = dk if acc is None else acc + dk
    c = acc * wt
    return jnp.where(wt > 0, -c / gamma, NEG)


def _soft_sweep_core(x, y, w, top_vec, left_vec, c_first, *, S: int, ri: int,
                     gamma: float, stash: bool, d: int = 1):
    """Row loop shared by ``soft_tile_sweep`` (forward-only) and
    ``soft_tile_sweep_stash`` (forward + full L-block capture)."""
    bt = x.shape[0]

    def logit_row(t):
        return _tile_logit_row(x, y, w, t, S=S, gamma=gamma, d=d)

    def row_update(t, L_prev, topleft0, left_t):
        tr = logit_row(t)
        topleft = jnp.concatenate([topleft0, L_prev[:, :-1]], axis=1)
        g = tr + jnp.logaddexp(L_prev, topleft)
        # inject the left-tile boundary as a virtual L_{-1}
        g0 = jnp.logaddexp(g[:, 0:1], left_t + tr[:, 0:1])
        g = jnp.concatenate([g0, g[:, 1:]], axis=1)
        return _logaddexp_scan_lanes(g, tr, S)

    d0 = row_update(0, top_vec, c_first, left_vec[:, 0:1])

    def body(t, carry):
        if stash:
            L_prev, rightcol, dri, Lblk = carry
        else:
            L_prev, rightcol, dri = carry
        tl0 = jax.lax.dynamic_slice_in_dim(left_vec, t - 1, 1, axis=1)
        lt = jax.lax.dynamic_slice_in_dim(left_vec, t, 1, axis=1)
        L_row = row_update(t, L_prev, tl0, lt)
        rightcol = jax.lax.dynamic_update_slice(
            rightcol, L_row[:, S - 1:S], (0, t))
        dri = jnp.where(t == ri, L_row, dri)
        if stash:
            Lblk = jax.lax.dynamic_update_slice(Lblk, L_row, (0, t * S))
            return L_row, rightcol, dri, Lblk
        return L_row, rightcol, dri

    rightcol0 = jnp.full((bt, S), NEG, x.dtype)
    rightcol0 = jax.lax.dynamic_update_slice(rightcol0, d0[:, S - 1:S], (0, 0))
    dri0 = jnp.where(ri == 0, d0, jnp.full((bt, S), NEG, x.dtype))
    if stash:
        Lblk0 = jnp.full((bt, S * S), NEG, x.dtype)
        Lblk0 = jax.lax.dynamic_update_slice(Lblk0, d0, (0, 0))
        return jax.lax.fori_loop(1, S, body, (d0, rightcol0, dri0, Lblk0))
    return jax.lax.fori_loop(1, S, body, (d0, rightcol0, dri0))


def soft_tile_sweep(x, y, w, top_vec, left_vec, c_first, *, S: int, ri: int,
                    gamma: float, d: int = 1):
    """Sweep one S x S tile of the *soft* SP-DTW DP for a batch of pairs.

    Same signature, edge dataflow and in-tile structure as
    ``spdtw_block.tile_sweep`` (x, y tile-major (bt, d*S); d = 1 is the
    historical layout), with every value in L = -R/gamma space
    (NEG = unreachable). Shared by the jnp scan engines and the fused
    Pallas kernels. Returns (d_last, rightcol, dri): the tile's bottom
    row, right column and the row at in-tile index ``ri``.
    """
    return _soft_sweep_core(x, y, w, top_vec, left_vec, c_first,
                            S=S, ri=ri, gamma=gamma, stash=False, d=d)


def soft_tile_sweep_stash(x, y, w, top_vec, left_vec, c_first, *, S: int,
                          ri: int, gamma: float, d: int = 1):
    """``soft_tile_sweep`` that additionally captures the full tile L
    block (DESIGN.md §11): returns (d_last, rightcol, dri, Lblk) with
    Lblk (bt, S*S) row-major — the per-tile residual the reverse
    expected-alignment sweep replays."""
    return _soft_sweep_core(x, y, w, top_vec, left_vec, c_first,
                            S=S, ri=ri, gamma=gamma, stash=True, d=d)


def soft_reverse_tile_sweep(x, y, w, Lblk, bot, corner, right, inj,
                            *, S: int, gamma: float, d: int = 1):
    """Sweep one S x S tile of the *reverse* expected-alignment recursion
    for a batch of pairs (DESIGN.md §11).

    Pure jnp on values — shared verbatim by the reverse scan engines and
    the fused Pallas Gram-backward kernel, exactly as ``soft_tile_sweep``
    is shared on the forward side. Rows are processed bottom-up; the
    in-row dependency ``E_j = b_j E_{j+1} + f_j`` is a lane-flipped
    Hillis-Steele linear recurrence (``_linrec_scan_lanes``).

    x, y:    (bt, d*S) per-pair series tiles, tile-major / channel-inner
             (rows of x, cols of y; d = 1 is the historical (bt, S)).
    w:       (S, S) weight block (0 = masked cell).
    Lblk:    (bt, S*S) stashed forward L of this tile (row-major).
    bot:     (E, L, t) triples, each (bt, S): the tile below's top-row
             halo (E = 0 / L = t = NEG when that tile is skipped).
    corner:  (E, L, t) triples, each (bt, 1): the below-right tile's
             top-left cell.
    right:   (E, L, t) triples, each (bt, S): the right tile's left
             column, one entry per row.
    inj:     (1, S*S) source injection — one-hot at the global result
             cell for the result tile, zeros elsewhere.
    Returns Eblk (bt, S*S): the expected-alignment block.
    """
    bt = x.shape[0]
    bE, bL, bt_ = bot
    cE, cL, ct = corner
    rE, rL, rt = right

    def logit_row(t):
        return _tile_logit_row(x, y, w, t, S=S, gamma=gamma, d=d)

    def body(u, carry):
        E_next, L_next, t_next, Eblk = carry
        r = S - 1 - u
        L_row = jax.lax.dynamic_slice_in_dim(Lblk, r * S, S, axis=1)
        t_row = logit_row(r)
        # boundary cell (r+1, S): the below-right corner at the bottom
        # row, the right tile's left column at row r+1 elsewhere
        rn = jnp.minimum(r + 1, S - 1)
        last = r == S - 1
        eE = jnp.where(last, cE,
                       jax.lax.dynamic_slice_in_dim(rE, rn, 1, axis=1))
        eL = jnp.where(last, cL,
                       jax.lax.dynamic_slice_in_dim(rL, rn, 1, axis=1))
        et = jnp.where(last, ct,
                       jax.lax.dynamic_slice_in_dim(rt, rn, 1, axis=1))
        a = _coeff(L_row, t_next, L_next)                     # (r+1, j)
        t_ns = jnp.concatenate([t_next[:, 1:], et], axis=1)
        L_ns = jnp.concatenate([L_next[:, 1:], eL], axis=1)
        E_ns = jnp.concatenate([E_next[:, 1:], eE], axis=1)
        c = _coeff(L_row, t_ns, L_ns)                         # (r+1, j+1)
        f = a * E_next + c * E_ns
        f = f + jax.lax.dynamic_slice_in_dim(inj, r * S, S, axis=1)
        # within-row successor (r, j+1); column S lives in the right tile
        rrE = jax.lax.dynamic_slice_in_dim(rE, r, 1, axis=1)
        rrL = jax.lax.dynamic_slice_in_dim(rL, r, 1, axis=1)
        rrt = jax.lax.dynamic_slice_in_dim(rt, r, 1, axis=1)
        t_rs = jnp.concatenate([t_row[:, 1:], rrt], axis=1)
        L_rs = jnp.concatenate([L_row[:, 1:], rrL], axis=1)
        b = _coeff(L_row, t_rs, L_rs)                         # (r, j+1)
        # fold the cross-tile b-transition into f, then solve the in-row
        # recurrence right-to-left on flipped lanes
        f = jnp.concatenate(
            [f[:, :S - 1], f[:, S - 1:] + b[:, S - 1:] * rrE], axis=1)
        E_row = _linrec_scan_lanes(b[:, ::-1], f[:, ::-1], S)[:, ::-1]
        Eblk = jax.lax.dynamic_update_slice(Eblk, E_row, (0, r * S))
        return E_row, L_row, t_row, Eblk

    init = (bE, bL, bt_, jnp.zeros((bt, S * S), x.dtype))
    _, _, _, Eblk = jax.lax.fori_loop(0, S, body, init)
    return Eblk


def _from_L(L_val, gamma):
    """Map captured L back to the soft distance (+INF when unreachable)."""
    return jnp.where(L_val > 0.5 * NEG, -gamma * L_val,
                     jnp.asarray(INF, L_val.dtype))


def _row0_logits(x, y, w, gamma, d: int = 1):
    """t of a tile's top row: t(0, j) = -w[0,j] ||x_0 - y_j||^2 / gamma
    (x, y tile-major (bt, d*S); channel distances sum)."""
    S = w.shape[0]
    acc = None
    for k in range(d):
        dk = (x[:, k * S:k * S + 1] - y[:, k * S:(k + 1) * S]) ** 2
        acc = dk if acc is None else acc + dk
    c = acc * w[0][None, :]
    return jnp.where(w[0][None, :] > 0, -c / gamma, NEG)


def _col0_logits(x, y, w, gamma, d: int = 1):
    """t of a tile's left column: t(r, 0) = -w[r,0] ||x_r - y_0||^2 /
    gamma (x, y tile-major (bt, d*S); channel distances sum)."""
    S = w.shape[0]
    acc = None
    for k in range(d):
        dk = (x[:, k * S:(k + 1) * S] - y[:, k * S:k * S + 1]) ** 2
        acc = dk if acc is None else acc + dk
    c = acc * w[:, 0][None, :]
    return jnp.where(w[:, 0][None, :] > 0, -c / gamma, NEG)


# ---------------------------------------------------------------------------
# jnp scan engines (tier-1 production path + oracle for the Pallas kernel)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("S", "T_orig", "g_out", "gamma",
                                             "d"))
def _gram_soft_scan_call(meta, A, B, blocks, *, S, T_orig, g_out, gamma,
                         d=1):
    Na = A.shape[0]
    Tp = A.shape[1] // d
    Nb = B.shape[0]
    P = Na * Nb
    last = T_orig - 1
    ri, rj = last % S, last % S

    def get_xy(ti, tj):
        xa = jax.lax.dynamic_slice_in_dim(A, ti * d * S, d * S, axis=1)
        yb = jax.lax.dynamic_slice_in_dim(B, tj * d * S, d * S, axis=1)
        return _pair_batch(xa, yb, Na, Nb)

    sweep = functools.partial(soft_tile_sweep, gamma=gamma)
    _, dri, _ = _tile_scan(meta, blocks, get_xy, P, Tp,
                           jnp.full((P, 1), INF, jnp.float32),
                           jnp.ones((P, 1), bool),
                           S=S, g_out=g_out, ri=ri, sweep=sweep, neutral=NEG,
                           d=d)
    L_val = jax.lax.dynamic_slice_in_dim(dri, rj, 1, axis=1)
    return _from_L(L_val, gamma).reshape(Na, Nb)


def gram_soft_spdtw_scan(A: jnp.ndarray, B: jnp.ndarray,
                         bsp: BlockSparsePaths, gamma: float,
                         T_orig: int | None = None,
                         block_a: int = 64) -> jnp.ndarray:
    """All-pairs soft-SP-DTW Gram matrix over the active-tile schedule.

    A: (Na, T) or (Na, T, d); B likewise -> (Na, Nb) soft distances
    (+INF where the support admits no path). Forward-only; the
    differentiable Gram entry is ``soft_spdtw_gram_batch``.
    """
    from .backends import series_dim, to_tile_major
    Na, T = A.shape[0], A.shape[1]
    Nb = B.shape[0]
    d = series_dim(A)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    if g_out < 0:   # corner cell outside the support: no admissible path
        return jnp.full((Na, Nb), INF, jnp.float32)
    meta = jnp.asarray(bsp.plan())
    blocks = jnp.asarray(bsp.blocks)
    Ap = to_tile_major(A, bsp.tile, bsp.T)
    Bp = to_tile_major(B, bsp.tile, bsp.T)
    rows = []
    for s in range(0, Na, block_a):
        rows.append(_gram_soft_scan_call(
            meta, Ap[s:s + block_a], Bp, blocks,
            S=bsp.tile, T_orig=T_orig, g_out=g_out, gamma=float(gamma),
            d=d))
    return jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("S", "T_orig", "g_out", "gamma",
                                             "d"))
def _soft_paired_scan_call(meta, X, Y, blocks, *, S, T_orig, g_out, gamma,
                           d=1):
    P = X.shape[0]
    Tp = X.shape[1] // d
    last = T_orig - 1
    ri, rj = last % S, last % S

    def get_xy(ti, tj):
        return (jax.lax.dynamic_slice_in_dim(X, ti * d * S, d * S, axis=1),
                jax.lax.dynamic_slice_in_dim(Y, tj * d * S, d * S, axis=1))

    sweep = functools.partial(soft_tile_sweep, gamma=gamma)
    _, dri, _ = _tile_scan(meta, blocks, get_xy, P, Tp,
                           jnp.full((P, 1), INF, jnp.float32),
                           jnp.ones((P, 1), bool),
                           S=S, g_out=g_out, ri=ri, sweep=sweep, neutral=NEG,
                           d=d)
    L_val = jax.lax.dynamic_slice_in_dim(dri, rj, 1, axis=1)
    return _from_L(L_val, gamma).reshape(P)


def soft_spdtw_paired_scan(x: jnp.ndarray, y: jnp.ndarray,
                           bsp: BlockSparsePaths, gamma: float,
                           T_orig: int | None = None,
                           block_p: int = 4096) -> jnp.ndarray:
    """Batched *aligned-pair* soft-SP-DTW forward: (B, T) x (B, T) -> (B,).

    x, y: (B, T) or (B, T, d). Same schedule and work accounting as
    ``gram_block.spdtw_paired_scan``; the forward half of
    ``soft_spdtw_batch``.
    """
    from .backends import series_dim, to_tile_major
    B, T = x.shape[0], x.shape[1]
    d = series_dim(x)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    if g_out < 0:
        return jnp.full((B,), INF, jnp.float32)
    meta = jnp.asarray(bsp.plan())
    blocks = jnp.asarray(bsp.blocks)
    xp = to_tile_major(x, bsp.tile, bsp.T)
    yp = to_tile_major(y, bsp.tile, bsp.T)
    outs = []
    for s in range(0, B, block_p):
        outs.append(_soft_paired_scan_call(
            meta, xp[s:s + block_p], yp[s:s + block_p], blocks,
            S=bsp.tile, T_orig=T_orig, g_out=g_out, gamma=float(gamma),
            d=d))
    return jnp.concatenate(outs, axis=0)


# ---------------------------------------------------------------------------
# Forward with L-block stashing + reverse sweep (jnp scan engines)
# ---------------------------------------------------------------------------

def _stash_tile_scan(meta, blocks, get_xy, P, Tp, *, S, g_out, ri, gamma,
                     d=1):
    """Forward active-tile scan that stashes each tile's full L block:
    ``gram_block._tile_scan(stash=True)`` with the stashing soft sweep.

    ``meta`` must already be sliced to the walked prefix (steps
    0..g_out); the stacked (P, S*S) L blocks in plan order are the
    residual the reverse sweep consumes. The +INF thresholds keep the
    (min-plus-only) early-abandon check inert.
    """
    dtype = blocks.dtype
    sweep = functools.partial(soft_tile_sweep_stash, gamma=gamma)
    _, dri, _, Lstash = _tile_scan(
        meta, blocks, get_xy, P, Tp,
        jnp.full((P, 1), INF, dtype), jnp.ones((P, 1), bool),
        S=S, g_out=g_out, ri=ri, sweep=sweep, neutral=NEG, stash=True, d=d)
    return dri, Lstash


def _reverse_tile_scan(rmeta, blocks, get_xy, Lstash_rev, gbar, P, Tp,
                       *, S, ri, rj, gamma, with_eblocks=False, d=1):
    """lax.scan over the reverse active-tile schedule (DESIGN.md §11).

    The reverse twin of ``gram_block._tile_scan``: E/L/t halos flow
    between tiles through carried scratch — ``top*`` rows hold the most
    recent tile's top-row halo per tile column (the below-tile edge of
    the next consumer), ``col*`` the left column of the previously swept
    tile (the right-tile edge), ``cor*`` the saved below-right corner.
    Neighbour bits in ``rmeta`` guard every read so skipped tiles
    contribute E = 0 / L = t = NEG. Accumulates the series and weight
    cotangents in-scan; per-tile E blocks ride along as scan ys when
    ``with_eblocks`` (parity tests / ``soft_alignment_pairs``).

    Returns (gx (P, d*Tp), gy (P, d*Tp), gw (Tp, Tp), E-blocks or None);
    the series cotangents are tile-major like the inputs (d = 1 is the
    historical (P, Tp)).
    """
    K = rmeta.shape[0]
    dtype = blocks.dtype
    inj = jnp.zeros((1, S * S), dtype).at[0, ri * S + rj].set(1.0)

    def step(carry, inp):
        (topE, topL, topt, colE, colL, colt, corE, corL, cort,
         gx, gy, gw) = carry
        k, m, Lblk = inp
        ti, tj = m[0], m[1]
        below_ok, right_ok, diag_ok = m[3] > 0, m[4] > 0, m[5] > 0
        x, y = get_xy(ti, tj)
        w = blocks[m[2]]
        bE = jnp.where(below_ok,
                       jax.lax.dynamic_slice_in_dim(topE, tj * S, S, axis=1),
                       0.0)
        bL = jnp.where(below_ok,
                       jax.lax.dynamic_slice_in_dim(topL, tj * S, S, axis=1),
                       NEG)
        bt_ = jnp.where(below_ok,
                        jax.lax.dynamic_slice_in_dim(topt, tj * S, S, axis=1),
                        NEG)
        rE = jnp.where(right_ok, colE, 0.0)
        rL = jnp.where(right_ok, colL, NEG)
        rt = jnp.where(right_ok, colt, NEG)
        # below-right corner: scratch when the right tile just published
        # it, else a direct (un-clobbered) top-halo read
        dcol = jnp.minimum((tj + 1) * S, Tp - 1)
        dEr = jax.lax.dynamic_slice_in_dim(topE, dcol, 1, axis=1)
        dLr = jax.lax.dynamic_slice_in_dim(topL, dcol, 1, axis=1)
        dtr = jax.lax.dynamic_slice_in_dim(topt, dcol, 1, axis=1)
        cE = jnp.where(diag_ok, jnp.where(right_ok, corE, dEr), 0.0)
        cL = jnp.where(diag_ok, jnp.where(right_ok, corL, dLr), NEG)
        ct = jnp.where(diag_ok, jnp.where(right_ok, cort, dtr), NEG)
        inj_k = jnp.where(k == 0, inj, 0.0)
        Eblk = soft_reverse_tile_sweep(x, y, w, Lblk, (bE, bL, bt_),
                                       (cE, cL, ct), (rE, rL, rt), inj_k,
                                       S=S, gamma=gamma, d=d)
        E3 = Eblk.reshape(P, S, S)
        L3 = Lblk.reshape(P, S, S)
        # publish halos for the upstream (reverse-order) tiles
        topE = jax.lax.dynamic_update_slice_in_dim(topE, E3[:, 0, :],
                                                   tj * S, axis=1)
        topL = jax.lax.dynamic_update_slice_in_dim(topL, L3[:, 0, :],
                                                   tj * S, axis=1)
        topt = jax.lax.dynamic_update_slice_in_dim(
            topt, _row0_logits(x, y, w, gamma, d=d), tj * S, axis=1)
        colE, colL = E3[:, :, 0], L3[:, :, 0]
        colt = _col0_logits(x, y, w, gamma, d=d)
        corE, corL, cort = bE[:, 0:1], bL[:, 0:1], bt_[:, 0:1]
        # cotangent contributions of this tile, channel by channel
        Ew = E3 * w[None]
        gx_parts, gy_parts, phi3 = [], [], None
        for c in range(d):
            xk = x[:, c * S:(c + 1) * S]
            yk = y[:, c * S:(c + 1) * S]
            gx_parts.append(
                2.0 * (xk * Ew.sum(2) - (Ew * yk[:, None, :]).sum(2))
                * gbar[:, None])
            gy_parts.append(
                -2.0 * ((Ew * xk[:, :, None]).sum(1) - yk * Ew.sum(1))
                * gbar[:, None])
            pk = (xk[:, :, None] - yk[:, None, :]) ** 2
            phi3 = pk if phi3 is None else phi3 + pk
        gx_t = jnp.concatenate(gx_parts, axis=1)               # (P, d*S)
        gy_t = jnp.concatenate(gy_parts, axis=1)
        gw_t = (E3 * phi3 * gbar[:, None, None]).sum(0)
        gx_cur = jax.lax.dynamic_slice_in_dim(gx, ti * d * S, d * S, axis=1)
        gx = jax.lax.dynamic_update_slice_in_dim(gx, gx_cur + gx_t,
                                                 ti * d * S, axis=1)
        gy_cur = jax.lax.dynamic_slice_in_dim(gy, tj * d * S, d * S, axis=1)
        gy = jax.lax.dynamic_update_slice_in_dim(gy, gy_cur + gy_t,
                                                 tj * d * S, axis=1)
        gw_cur = jax.lax.dynamic_slice(gw, (ti * S, tj * S), (S, S))
        gw = jax.lax.dynamic_update_slice(gw, gw_cur + gw_t,
                                          (ti * S, tj * S))
        carry = (topE, topL, topt, colE, colL, colt, corE, corL, cort,
                 gx, gy, gw)
        return carry, (E3 if with_eblocks else None)

    zeros_w = jnp.zeros((P, Tp), dtype)
    neg_w = jnp.full((P, Tp), NEG, dtype)
    init = (zeros_w, neg_w, neg_w,
            jnp.zeros((P, S), dtype),
            jnp.full((P, S), NEG, dtype),
            jnp.full((P, S), NEG, dtype),
            jnp.zeros((P, 1), dtype),
            jnp.full((P, 1), NEG, dtype),
            jnp.full((P, 1), NEG, dtype),
            jnp.zeros((P, d * Tp), dtype), jnp.zeros((P, d * Tp), dtype),
            jnp.zeros((Tp, Tp), dtype))
    carry, Es = jax.lax.scan(step, init, (jnp.arange(K), rmeta, Lstash_rev))
    gx, gy, gw = carry[9], carry[10], carry[11]
    return gx, gy, gw, Es


@functools.partial(jax.jit, static_argnames=("S", "g_out", "ri", "gamma",
                                             "d"))
def _soft_paired_stash_call(meta_f, X, Y, blocks, *, S, g_out, ri, gamma,
                            d=1):
    P = X.shape[0]
    Tp = X.shape[1] // d

    def get_xy(ti, tj):
        return (jax.lax.dynamic_slice_in_dim(X, ti * d * S, d * S, axis=1),
                jax.lax.dynamic_slice_in_dim(Y, tj * d * S, d * S, axis=1))

    dri, Lstash = _stash_tile_scan(meta_f, blocks, get_xy, P, Tp,
                                   S=S, g_out=g_out, ri=ri, gamma=gamma, d=d)
    L_val = jax.lax.dynamic_slice_in_dim(dri, ri, 1, axis=1)
    return _from_L(L_val, gamma).reshape(P), Lstash


@functools.partial(jax.jit,
                   static_argnames=("S", "ri", "gamma", "with_eblocks", "d"))
def _soft_paired_bwd_call(rmeta, X, Y, blocks, Lstash, gbar, *, S, ri,
                          gamma, with_eblocks, d=1):
    P = X.shape[0]
    Tp = X.shape[1] // d

    def get_xy(ti, tj):
        return (jax.lax.dynamic_slice_in_dim(X, ti * d * S, d * S, axis=1),
                jax.lax.dynamic_slice_in_dim(Y, tj * d * S, d * S, axis=1))

    return _reverse_tile_scan(rmeta, blocks, get_xy, Lstash[::-1], gbar,
                              P, Tp, S=S, ri=ri, rj=ri, gamma=gamma,
                              with_eblocks=with_eblocks, d=d)


def _pad_series(x, bsp, dtype=jnp.float32):
    from .backends import to_tile_major
    return to_tile_major(x, bsp.tile, bsp.T, dtype=dtype)


def soft_spdtw_fwd_stash(x: jnp.ndarray, y: jnp.ndarray,
                         bsp: BlockSparsePaths, gamma: float,
                         T_orig: int | None = None, dtype=jnp.float32):
    """Aligned-pair soft forward that stashes per-tile L blocks.

    x, y: (B, T) or (B, T, d). Returns (values (B,), Lstash
    (g_out+1, B, S*S)) — Lstash is the residual ``soft_spdtw_bwd_block``
    replays; None when the corner tile is inactive (values +INF,
    gradients identically 0). Values are bit-identical to
    ``soft_spdtw_paired_scan``. ``dtype`` sets the compute precision of
    the scan engine (f64 for oracle-grade parity checks; the VJPs use
    f32).
    """
    from .backends import series_dim
    B, T = x.shape[0], x.shape[1]
    d = series_dim(x)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    if g_out < 0:
        return jnp.full((B,), INF, dtype), None
    meta_f = jnp.asarray(bsp.plan()[:g_out + 1])
    val, Lstash = _soft_paired_stash_call(
        meta_f, _pad_series(x, bsp, dtype), _pad_series(y, bsp, dtype),
        jnp.asarray(bsp.blocks, dtype), S=bsp.tile, g_out=g_out,
        ri=(T_orig - 1) % bsp.tile, gamma=float(gamma), d=d)
    return val, Lstash


def soft_spdtw_bwd_block(x: jnp.ndarray, y: jnp.ndarray,
                         bsp: BlockSparsePaths, gamma: float,
                         Lstash: jnp.ndarray, gbar: jnp.ndarray,
                         T_orig: int | None = None, dtype=jnp.float32):
    """Reverse active-tile sweep: aligned-pair cotangents (DESIGN.md §11).

    Walks the cached tile plan backwards over the stashed L blocks,
    computing the expected-alignment matrix restricted to the learned
    support and contracting it with the local-cost derivatives in-scan.
    ``gbar`` (B,) is the per-pair output cotangent (callers fold the
    feasibility mask into it). Returns (gx, gy, gw (Tp, Tp) summed over
    pairs; slice to the weight-grid size) — gx/gy shaped like the
    series ((B, T_orig) univariate, (B, T_orig, d) multivariate).
    """
    from .backends import from_tile_major, series_dim
    B, T = x.shape[0], x.shape[1]
    d = series_dim(x)
    T_orig = T if T_orig is None else T_orig
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    assert g_out >= 0, "no admissible path: backward has no mass to move"
    rmeta = jnp.asarray(bsp.reverse_plan(g_out))
    gx, gy, gw, _ = _soft_paired_bwd_call(
        rmeta, _pad_series(x, bsp, dtype), _pad_series(y, bsp, dtype),
        jnp.asarray(bsp.blocks, dtype), Lstash,
        jnp.asarray(gbar, dtype), S=bsp.tile,
        ri=(T_orig - 1) % bsp.tile, gamma=float(gamma), with_eblocks=False,
        d=d)
    squeeze = x.ndim == 2
    return (from_tile_major(gx, bsp.tile, d, T_orig, squeeze=squeeze),
            from_tile_major(gy, bsp.tile, d, T_orig, squeeze=squeeze), gw)


def soft_alignment_pairs(x: jnp.ndarray, y: jnp.ndarray,
                         bsp: BlockSparsePaths, gamma: float,
                         T_orig: int | None = None,
                         dtype=jnp.float32) -> jnp.ndarray:
    """(B, T, T) expected-alignment matrices via the block-sparse reverse
    sweep — the parity handle against ``core.softdtw.soft_alignment``
    (with ``dtype=jnp.float64`` the two agree to ~1e-12; in f32 both
    carry ~1e-5 roundoff of their own). Zero outside the learned support
    and identically zero for pairs whose support admits no path.
    x, y: (B, T) or (B, T, d).
    """
    from .backends import series_dim
    B, T = x.shape[0], x.shape[1]
    d = series_dim(x)
    T_orig = T if T_orig is None else T_orig
    val, Lstash = soft_spdtw_fwd_stash(x, y, bsp, gamma, T_orig=T_orig,
                                       dtype=dtype)
    if Lstash is None:
        return jnp.zeros((B, T_orig, T_orig), dtype)
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    rmeta = bsp.reverse_plan(g_out)
    S = bsp.tile
    _, _, _, Es = _soft_paired_bwd_call(
        jnp.asarray(rmeta), _pad_series(x, bsp, dtype),
        _pad_series(y, bsp, dtype),
        jnp.asarray(bsp.blocks, dtype), Lstash,
        jnp.ones((B,), dtype), S=S,
        ri=(T_orig - 1) % S, gamma=float(gamma), with_eblocks=True, d=d)
    Es = np.asarray(Es)
    E = np.zeros((B, bsp.T, bsp.T), Es.dtype)
    for k in range(rmeta.shape[0]):
        ti, tj = int(rmeta[k, 0]), int(rmeta[k, 1])
        E[:, ti * S:(ti + 1) * S, tj * S:(tj + 1) * S] = Es[k]
    E *= np.asarray(val < 1e29, Es.dtype)[:, None, None]
    return jnp.asarray(E[:, :T_orig, :T_orig])


@functools.partial(jax.jit, static_argnames=("S", "g_out", "ri", "gamma",
                                             "d"))
def _gram_stash_call(meta_f, A, B, blocks, *, S, g_out, ri, gamma, d=1):
    Na = A.shape[0]
    Tp = A.shape[1] // d
    Nb = B.shape[0]
    P = Na * Nb

    def get_xy(ti, tj):
        xa = jax.lax.dynamic_slice_in_dim(A, ti * d * S, d * S, axis=1)
        yb = jax.lax.dynamic_slice_in_dim(B, tj * d * S, d * S, axis=1)
        return _pair_batch(xa, yb, Na, Nb)

    dri, Lstash = _stash_tile_scan(meta_f, blocks, get_xy, P, Tp,
                                   S=S, g_out=g_out, ri=ri, gamma=gamma, d=d)
    L_val = jax.lax.dynamic_slice_in_dim(dri, ri, 1, axis=1)
    return _from_L(L_val, gamma).reshape(Na, Nb), Lstash


@functools.partial(jax.jit, static_argnames=("S", "ri", "gamma", "d"))
def _gram_bwd_scan_call(rmeta, A, B, blocks, Lstash, gbar, *, S, ri, gamma,
                        d=1):
    Na = A.shape[0]
    Tp = A.shape[1] // d
    Nb = B.shape[0]
    P = Na * Nb

    def get_xy(ti, tj):
        xa = jax.lax.dynamic_slice_in_dim(A, ti * d * S, d * S, axis=1)
        yb = jax.lax.dynamic_slice_in_dim(B, tj * d * S, d * S, axis=1)
        return _pair_batch(xa, yb, Na, Nb)

    gx, gy, gw, _ = _reverse_tile_scan(
        rmeta, blocks, get_xy, Lstash[::-1], gbar.reshape(P), P, Tp,
        S=S, ri=ri, rj=ri, gamma=gamma, with_eblocks=False, d=d)
    gA = gx.reshape(Na, Nb, d * Tp).sum(1)
    gB = gy.reshape(Na, Nb, d * Tp).sum(0)
    return gA, gB, gw


def gram_soft_fwd_stash(A: jnp.ndarray, B: jnp.ndarray,
                        bsp: BlockSparsePaths, gamma: float,
                        T_orig: int | None = None, dtype=jnp.float32):
    """All-pairs soft Gram forward with L-block stashing.

    A: (Na, T) or (Na, T, d); B likewise. Returns (values (Na, Nb),
    Lstash (g_out+1, Na*Nb, S*S)); Lstash is None when the corner tile
    is inactive. Memory is the standard soft-DTW "keep R" residual
    restricted to active tiles: Na*Nb*n_walked*S^2 floats.
    """
    from .backends import series_dim
    Na, T = A.shape[0], A.shape[1]
    d = series_dim(A)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    if g_out < 0:
        return jnp.full((Na, B.shape[0]), INF, dtype), None
    meta_f = jnp.asarray(bsp.plan()[:g_out + 1])
    return _gram_stash_call(
        meta_f, _pad_series(A, bsp, dtype), _pad_series(B, bsp, dtype),
        jnp.asarray(bsp.blocks, dtype), S=bsp.tile, g_out=g_out,
        ri=(T_orig - 1) % bsp.tile, gamma=float(gamma), d=d)


def gram_soft_bwd_scan(A: jnp.ndarray, B: jnp.ndarray,
                       bsp: BlockSparsePaths, gamma: float,
                       Lstash: jnp.ndarray, gbar: jnp.ndarray,
                       T_orig: int | None = None, dtype=jnp.float32):
    """Reverse active-tile sweep over the pair cross-product: Gram
    cotangents. ``gbar``: (Na, Nb) output cotangent (feasibility mask
    folded in by the caller). Returns (gA, gB, gw (Tp, Tp)) — gA/gB
    shaped like the series ((N, T_orig) univariate, (N, T_orig, d)
    multivariate)."""
    from .backends import from_tile_major, series_dim
    Na, T = A.shape[0], A.shape[1]
    d = series_dim(A)
    T_orig = T if T_orig is None else T_orig
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    assert g_out >= 0, "no admissible path: backward has no mass to move"
    rmeta = jnp.asarray(bsp.reverse_plan(g_out))
    gA, gB, gw = _gram_bwd_scan_call(
        rmeta, _pad_series(A, bsp, dtype), _pad_series(B, bsp, dtype),
        jnp.asarray(bsp.blocks, dtype), Lstash,
        jnp.asarray(gbar, dtype), S=bsp.tile,
        ri=(T_orig - 1) % bsp.tile, gamma=float(gamma), d=d)
    squeeze = A.ndim == 2
    return (from_tile_major(gA, bsp.tile, d, T_orig, squeeze=squeeze),
            from_tile_major(gB, bsp.tile, d, T_orig, squeeze=squeeze), gw)


# ---------------------------------------------------------------------------
# Fused Pallas kernels (interpret-mode parity; Mosaic does not lower them yet)
# ---------------------------------------------------------------------------

def _gather_soft_edges(meta_ref, g, row_edge, col_edge, corner_next, bt, S):
    """Incoming forward edges for one grid step, guarded against inactive
    neighbours (shared by the plain and stashing Gram kernels)."""
    tj = meta_ref[g, 1]
    top_ok = meta_ref[g, 3] > 0
    left_ok = meta_ref[g, 4] > 0
    diag_ok = meta_ref[g, 5] > 0
    neg_row = jnp.full((bt, S), NEG, jnp.float32)
    top_raw = row_edge[:, pl.ds(tj * S, S)]
    top_vec = jnp.where(top_ok, top_raw, neg_row)
    left_vec = jnp.where(left_ok, col_edge[...], neg_row)
    c_first = jnp.where(
        g == 0, jnp.zeros((bt, 1), jnp.float32),
        jnp.where(diag_ok,
                  jnp.where(left_ok, corner_next[...],
                            # guarded: only read when diag_ok (=> tj > 0);
                            # clamp keeps the untaken branch in-bounds
                            row_edge[:, pl.ds(jnp.maximum(tj * S - 1, 0),
                                              1)]),
                  jnp.full((bt, 1), NEG, jnp.float32)))
    return top_vec, left_vec, c_first


def _gram_soft_kernel(meta_ref, a_ref, b_ref, w_ref, out_ref,
                      row_edge, col_edge, corner_next, d_ri,
                      *, S: int, g_out: int, ri: int, rj: int,
                      ba: int, bb: int, gamma: float, d: int):
    """One grid step = one active tile for one (A-stripe, B-stripe) block —
    ``gram_block._gram_spdtw_kernel`` in the log semiring (no abandon
    sweep: the row-min bound is a min-plus construct)."""
    g = pl.program_id(2)
    bt = ba * bb

    @pl.when(g == 0)
    def _():
        row_edge[...] = jnp.full((bt, row_edge.shape[1]), NEG, jnp.float32)

    ti = meta_ref[g, 0]
    tj = meta_ref[g, 1]
    # tile-major layout: tile ti's d channel planes are contiguous
    xa = a_ref[:, pl.ds(ti * d * S, d * S)]
    yb = b_ref[:, pl.ds(tj * d * S, d * S)]
    x, y = _pair_batch(xa, yb, ba, bb)                         # (bt, d*S)
    w = w_ref[0]                                               # (S, S)

    top_vec, left_vec, c_first = _gather_soft_edges(
        meta_ref, g, row_edge, col_edge, corner_next, bt, S)
    new_corner = top_vec[:, S - 1:S]

    d_last, rightcol, dri = soft_tile_sweep(x, y, w, top_vec, left_vec,
                                            c_first, S=S, ri=ri, gamma=gamma,
                                            d=d)

    corner_next[...] = new_corner
    row_edge[:, pl.ds(tj * S, S)] = d_last
    col_edge[...] = rightcol
    d_ri[...] = dri

    @pl.when(g == g_out)
    def _():
        res = jax.lax.dynamic_slice_in_dim(d_ri[...], rj, 1, axis=1)
        out_ref[...] = _from_L(res, gamma).reshape(ba, bb)


@functools.partial(jax.jit,
                   static_argnames=("S", "n_active", "T_orig", "g_out",
                                    "ba", "bb", "gamma", "d", "interpret"))
def _gram_soft_call(meta, A, B, blocks, *, S, n_active, T_orig, g_out,
                    ba, bb, gamma, d, interpret):
    Nap, Tw = A.shape
    Nbp = B.shape[0]
    Tp = Tw // d                    # DP grid edge (padded)
    last = T_orig - 1
    ri, rj = last % S, last % S
    grid = (Nap // ba, Nbp // bb, n_active)
    kernel = functools.partial(_gram_soft_kernel, S=S, g_out=g_out,
                               ri=ri, rj=rj, ba=ba, bb=bb, gamma=gamma, d=d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ba, Tw), lambda i, j, g, m: (i, 0)),
            pl.BlockSpec((bb, Tw), lambda i, j, g, m: (j, 0)),
            pl.BlockSpec((1, S, S), lambda i, j, g, m: (m[g, 2], 0, 0)),
        ],
        out_specs=pl.BlockSpec((ba, bb), lambda i, j, g, m: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((ba * bb, Tp), jnp.float32),   # row_edge (L space)
            pltpu.VMEM((ba * bb, S), jnp.float32),    # col_edge
            pltpu.VMEM((ba * bb, 1), jnp.float32),    # corner_next
            pltpu.VMEM((ba * bb, S), jnp.float32),    # d_ri capture
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Nap, Nbp), jnp.float32),
        interpret=interpret,
    )(meta, A, B, blocks)


def gram_soft_spdtw_block(A: jnp.ndarray, B: jnp.ndarray,
                          bsp: BlockSparsePaths, gamma: float,
                          T_orig: int | None = None, ba: int = 8, bb: int = 8,
                          interpret: bool = False) -> jnp.ndarray:
    """All-pairs soft-SP-DTW Gram matrix via the fused Pallas kernel.

    A: (Na, T) or (Na, T, d); B likewise -> (Na, Nb) f32 soft distances.
    Forward-only serving path; the backward twin is
    ``gram_soft_bwd_pallas`` (univariate — multivariate gradients take
    the scan backward, see ``kernels.backends``).
    """
    from .backends import series_dim, to_tile_major
    Na, T = A.shape[0], A.shape[1]
    Nb = B.shape[0]
    d = series_dim(A)
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    meta = bsp.plan()
    n_active = meta.shape[0]
    g_out = result_tile_step(meta, bsp.tile, T_orig)
    if g_out < 0:
        return jnp.full((Na, Nb), INF, jnp.float32)
    Nap = ((Na + ba - 1) // ba) * ba
    Nbp = ((Nb + bb - 1) // bb) * bb
    out = _gram_soft_call(
        jnp.asarray(meta), to_tile_major(A, bsp.tile, bsp.T, n_to=Nap),
        to_tile_major(B, bsp.tile, bsp.T, n_to=Nbp), jnp.asarray(bsp.blocks),
        S=bsp.tile, n_active=n_active, T_orig=T_orig, g_out=g_out,
        ba=ba, bb=bb, gamma=float(gamma), d=d, interpret=interpret)
    return out[:Na, :Nb]


def _gram_soft_stash_kernel(meta_ref, a_ref, b_ref, w_ref,
                            out_ref, lstash_ref,
                            row_edge, col_edge, corner_next, d_ri,
                            *, S: int, g_out: int, ri: int, rj: int,
                            ba: int, bb: int, gamma: float):
    """The forward Gram kernel with per-tile L-block stashing: each grid
    step additionally writes its (bt*S, S) L block to HBM — the residual
    the reverse kernel (``_gram_soft_bwd_kernel``) replays."""
    g = pl.program_id(2)
    bt = ba * bb

    @pl.when(g == 0)
    def _():
        row_edge[...] = jnp.full((bt, row_edge.shape[1]), NEG, jnp.float32)

    ti = meta_ref[g, 0]
    tj = meta_ref[g, 1]
    xa = a_ref[:, pl.ds(ti * S, S)]
    yb = b_ref[:, pl.ds(tj * S, S)]
    x, y = _pair_batch(xa, yb, ba, bb)
    w = w_ref[0]

    top_vec, left_vec, c_first = _gather_soft_edges(
        meta_ref, g, row_edge, col_edge, corner_next, bt, S)
    new_corner = top_vec[:, S - 1:S]

    d_last, rightcol, dri, Lblk = soft_tile_sweep_stash(
        x, y, w, top_vec, left_vec, c_first, S=S, ri=ri, gamma=gamma)

    corner_next[...] = new_corner
    row_edge[:, pl.ds(tj * S, S)] = d_last
    col_edge[...] = rightcol
    d_ri[...] = dri
    lstash_ref[0, 0, 0] = Lblk.reshape(bt * S, S)

    @pl.when(g == g_out)
    def _():
        res = jax.lax.dynamic_slice_in_dim(d_ri[...], rj, 1, axis=1)
        out_ref[...] = _from_L(res, gamma).reshape(ba, bb)


@functools.partial(jax.jit,
                   static_argnames=("S", "K", "T_orig", "ba", "bb", "gamma",
                                    "interpret"))
def _gram_soft_stash_pallas_call(meta, A, B, blocks, *, S, K, T_orig,
                                 ba, bb, gamma, interpret):
    Nap, Tp = A.shape
    Nbp = B.shape[0]
    ni, nj = Nap // ba, Nbp // bb
    last = T_orig - 1
    ri, rj = last % S, last % S
    bt = ba * bb
    kernel = functools.partial(_gram_soft_stash_kernel, S=S, g_out=K - 1,
                               ri=ri, rj=rj, ba=ba, bb=bb, gamma=gamma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ni, nj, K),
        in_specs=[
            pl.BlockSpec((ba, Tp), lambda i, j, g, m: (i, 0)),
            pl.BlockSpec((bb, Tp), lambda i, j, g, m: (j, 0)),
            pl.BlockSpec((1, S, S), lambda i, j, g, m: (m[g, 2], 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((ba, bb), lambda i, j, g, m: (i, j)),
            pl.BlockSpec((1, 1, 1, bt * S, S),
                         lambda i, j, g, m: (g, i, j, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bt, Tp), jnp.float32),   # row_edge (L space)
            pltpu.VMEM((bt, S), jnp.float32),    # col_edge
            pltpu.VMEM((bt, 1), jnp.float32),    # corner_next
            pltpu.VMEM((bt, S), jnp.float32),    # d_ri capture
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((Nap, Nbp), jnp.float32),
                   jax.ShapeDtypeStruct((K, ni, nj, bt * S, S),
                                        jnp.float32)),
        interpret=interpret,
    )(meta, A, B, blocks)


def _gram_soft_bwd_kernel(rmeta_ref, a_ref, b_ref, w_ref, lstash_ref,
                          gbar_ref, ga_ref, gb_ref, gw_ref,
                          topE, topL, topt, colE, colL, colt,
                          corE, corL, cort,
                          *, S: int, ri: int, rj: int,
                          ba: int, bb: int, gamma: float):
    """Fused Gram-backward: one grid step = one reverse-plan tile for one
    (A-stripe, B-stripe) block (DESIGN.md §11).

    The E/L/t halos flow through VMEM scratch exactly as the forward
    kernel's D edges do, in the mirrored directions: ``top*`` carries
    top-row halos per tile column (the below-tile edge of upstream
    consumers), ``col*`` the left column of the previously swept tile
    (the right-tile edge), ``cor*`` the saved below-right corner cell.
    Cotangents accumulate in the revisited output blocks: ``ga`` per
    A-stripe, ``gb`` per (A-stripe, B-stripe) partial (summed over i
    outside), ``gw`` per reverse step (scattered onto the grid outside).
    """
    j = pl.program_id(1)
    k = pl.program_id(2)
    bt = ba * bb
    Tp = topE.shape[1]

    @pl.when(k == 0)
    def _():
        topE[...] = jnp.zeros((bt, Tp), jnp.float32)
        topL[...] = jnp.full((bt, Tp), NEG, jnp.float32)
        topt[...] = jnp.full((bt, Tp), NEG, jnp.float32)
        colE[...] = jnp.zeros((bt, S), jnp.float32)
        colL[...] = jnp.full((bt, S), NEG, jnp.float32)
        colt[...] = jnp.full((bt, S), NEG, jnp.float32)
        corE[...] = jnp.zeros((bt, 1), jnp.float32)
        corL[...] = jnp.full((bt, 1), NEG, jnp.float32)
        cort[...] = jnp.full((bt, 1), NEG, jnp.float32)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    @pl.when((j == 0) & (k == 0))
    def _():
        ga_ref[...] = jnp.zeros_like(ga_ref)

    ti = rmeta_ref[k, 0]
    tj = rmeta_ref[k, 1]
    below_ok = rmeta_ref[k, 3] > 0
    right_ok = rmeta_ref[k, 4] > 0
    diag_ok = rmeta_ref[k, 5] > 0

    xa = a_ref[:, pl.ds(ti * S, S)]
    yb = b_ref[:, pl.ds(tj * S, S)]
    x, y = _pair_batch(xa, yb, ba, bb)
    w = w_ref[0]
    Lblk = lstash_ref[0, 0, 0].reshape(bt, S * S)

    zero_row = jnp.zeros((bt, S), jnp.float32)
    neg_row = jnp.full((bt, S), NEG, jnp.float32)
    bE = jnp.where(below_ok, topE[:, pl.ds(tj * S, S)], zero_row)
    bL = jnp.where(below_ok, topL[:, pl.ds(tj * S, S)], neg_row)
    bt_ = jnp.where(below_ok, topt[:, pl.ds(tj * S, S)], neg_row)
    rE = jnp.where(right_ok, colE[...], zero_row)
    rL = jnp.where(right_ok, colL[...], neg_row)
    rt = jnp.where(right_ok, colt[...], neg_row)
    dcol = jnp.minimum((tj + 1) * S, Tp - 1)
    dEr = topE[:, pl.ds(dcol, 1)]
    dLr = topL[:, pl.ds(dcol, 1)]
    dtr = topt[:, pl.ds(dcol, 1)]
    cE = jnp.where(diag_ok, jnp.where(right_ok, corE[...], dEr),
                   jnp.zeros((bt, 1), jnp.float32))
    cL = jnp.where(diag_ok, jnp.where(right_ok, corL[...], dLr),
                   jnp.full((bt, 1), NEG, jnp.float32))
    ct = jnp.where(diag_ok, jnp.where(right_ok, cort[...], dtr),
                   jnp.full((bt, 1), NEG, jnp.float32))
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (1, S * S), 1)
              == ri * S + rj).astype(jnp.float32)
    inj = jnp.where(k == 0, onehot, 0.0)

    Eblk = soft_reverse_tile_sweep(x, y, w, Lblk, (bE, bL, bt_),
                                   (cE, cL, ct), (rE, rL, rt), inj,
                                   S=S, gamma=gamma)
    E3 = Eblk.reshape(bt, S, S)
    L3 = Lblk.reshape(bt, S, S)

    topE[:, pl.ds(tj * S, S)] = E3[:, 0, :]
    topL[:, pl.ds(tj * S, S)] = L3[:, 0, :]
    topt[:, pl.ds(tj * S, S)] = _row0_logits(x, y, w, gamma)
    colE[...] = E3[:, :, 0]
    colL[...] = L3[:, :, 0]
    colt[...] = _col0_logits(x, y, w, gamma)
    corE[...] = bE[:, 0:1]
    corL[...] = bL[:, 0:1]
    cort[...] = bt_[:, 0:1]

    gbar = gbar_ref[...].reshape(bt, 1)
    Ew = E3 * w[None]
    gx_t = 2.0 * (x * Ew.sum(2) - (Ew * y[:, None, :]).sum(2)) * gbar
    gy_t = -2.0 * ((Ew * x[:, :, None]).sum(1) - y * Ew.sum(1)) * gbar
    phi3 = (x[:, :, None] - y[:, None, :]) ** 2
    gw_ref[0, 0, 0] = (E3 * phi3 * gbar[:, :, None]).sum(0)
    ga_cur = ga_ref[:, pl.ds(ti * S, S)]
    ga_ref[:, pl.ds(ti * S, S)] = ga_cur + gx_t.reshape(ba, bb, S).sum(1)
    gb_cur = gb_ref[:, :, pl.ds(tj * S, S)]
    gb_ref[:, :, pl.ds(tj * S, S)] = (gb_cur
                                      + gy_t.reshape(ba, bb, S).sum(0)[None])


@functools.partial(jax.jit,
                   static_argnames=("S", "K", "T_orig", "ba", "bb", "gamma",
                                    "interpret"))
def _gram_soft_bwd_pallas_call(rmeta, A, B, blocks, lstash, gbar, *, S, K,
                               T_orig, ba, bb, gamma, interpret):
    Nap, Tp = A.shape
    Nbp = B.shape[0]
    ni, nj = Nap // ba, Nbp // bb
    last = T_orig - 1
    ri, rj = last % S, last % S
    bt = ba * bb
    kernel = functools.partial(_gram_soft_bwd_kernel, S=S, ri=ri, rj=rj,
                               ba=ba, bb=bb, gamma=gamma)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ni, nj, K),
        in_specs=[
            pl.BlockSpec((ba, Tp), lambda i, j, k, m: (i, 0)),
            pl.BlockSpec((bb, Tp), lambda i, j, k, m: (j, 0)),
            pl.BlockSpec((1, S, S), lambda i, j, k, m: (m[k, 2], 0, 0)),
            pl.BlockSpec((1, 1, 1, bt * S, S),
                         lambda i, j, k, m: (m[k, 6], i, j, 0, 0)),
            pl.BlockSpec((ba, bb), lambda i, j, k, m: (i, j)),
        ],
        out_specs=(
            pl.BlockSpec((ba, Tp), lambda i, j, k, m: (i, 0)),
            pl.BlockSpec((1, bb, Tp), lambda i, j, k, m: (i, j, 0)),
            pl.BlockSpec((1, 1, 1, S, S),
                         lambda i, j, k, m: (i, j, k, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bt, Tp), jnp.float32),   # topE
            pltpu.VMEM((bt, Tp), jnp.float32),   # topL
            pltpu.VMEM((bt, Tp), jnp.float32),   # topt
            pltpu.VMEM((bt, S), jnp.float32),    # colE
            pltpu.VMEM((bt, S), jnp.float32),    # colL
            pltpu.VMEM((bt, S), jnp.float32),    # colt
            pltpu.VMEM((bt, 1), jnp.float32),    # corE
            pltpu.VMEM((bt, 1), jnp.float32),    # corL
            pltpu.VMEM((bt, 1), jnp.float32),    # cort
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((Nap, Tp), jnp.float32),
                   jax.ShapeDtypeStruct((ni, Nbp, Tp), jnp.float32),
                   jax.ShapeDtypeStruct((ni, nj, K, S, S), jnp.float32)),
        interpret=interpret,
    )(rmeta, A, B, blocks, lstash, gbar)


def gram_soft_fwd_stash_pallas(A: jnp.ndarray, B: jnp.ndarray,
                               bsp: BlockSparsePaths, gamma: float,
                               T_orig: int | None = None,
                               ba: int = 8, bb: int = 8,
                               interpret: bool = False):
    """Pallas forward stash: (values (Na, Nb), Lstash) with Lstash laid
    out (K, ni, nj, ba*bb*S, S) — the block layout
    ``gram_soft_bwd_pallas`` consumes. Lstash is None when the corner
    tile is inactive."""
    Na, T = A.shape
    Nb = B.shape[0]
    T_orig = T if T_orig is None else T_orig
    assert T_orig <= bsp.T
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    if g_out < 0:
        return jnp.full((Na, Nb), INF, jnp.float32), None
    Nap = ((Na + ba - 1) // ba) * ba
    Nbp = ((Nb + bb - 1) // bb) * bb
    meta_f = jnp.asarray(bsp.plan()[:g_out + 1])
    val, lstash = _gram_soft_stash_pallas_call(
        meta_f, _pad_rows_cols(A, Nap, bsp.T), _pad_rows_cols(B, Nbp, bsp.T),
        jnp.asarray(bsp.blocks), S=bsp.tile, K=g_out + 1, T_orig=T_orig,
        ba=ba, bb=bb, gamma=float(gamma), interpret=interpret)
    return val[:Na, :Nb], lstash


def gram_soft_bwd_pallas(A: jnp.ndarray, B: jnp.ndarray,
                         bsp: BlockSparsePaths, gamma: float,
                         Lstash: jnp.ndarray, gbar: jnp.ndarray,
                         T_orig: int | None = None,
                         ba: int = 8, bb: int = 8,
                         interpret: bool = False):
    """Fused Pallas Gram-backward over the reverse active-tile schedule.

    ``Lstash`` must come from ``gram_soft_fwd_stash_pallas`` (same ba/bb).
    ``gbar``: (Na, Nb) output cotangent, feasibility mask folded in.
    Returns (gA (Na, T_orig), gB (Nb, T_orig), gw (Tp, Tp)).
    """
    Na, T = A.shape
    Nb = B.shape[0]
    T_orig = T if T_orig is None else T_orig
    g_out = result_tile_step(bsp.plan(), bsp.tile, T_orig)
    assert g_out >= 0, "no admissible path: backward has no mass to move"
    Nap = ((Na + ba - 1) // ba) * ba
    Nbp = ((Nb + bb - 1) // bb) * bb
    rmeta = bsp.reverse_plan(g_out)
    gbar_p = jnp.pad(jnp.asarray(gbar, jnp.float32),
                     ((0, Nap - Na), (0, Nbp - Nb)))
    ga, gb, gw_tiles = _gram_soft_bwd_pallas_call(
        jnp.asarray(rmeta), _pad_rows_cols(A, Nap, bsp.T),
        _pad_rows_cols(B, Nbp, bsp.T), jnp.asarray(bsp.blocks),
        Lstash, gbar_p, S=bsp.tile, K=g_out + 1, T_orig=T_orig,
        ba=ba, bb=bb, gamma=float(gamma), interpret=interpret)
    S = bsp.tile
    gw_k = gw_tiles.sum(axis=(0, 1))                    # (K, S, S)
    # one vectorized scatter of the (disjoint) tiles onto the grid: view
    # gw as (Ti, S, Tj, S) and index tile coordinates from the host plan
    Ti = bsp.T // S
    ti_idx = jnp.asarray(rmeta[:, 0])
    tj_idx = jnp.asarray(rmeta[:, 1])
    gw = jnp.zeros((Ti, S, Ti, S), jnp.float32) \
        .at[ti_idx, :, tj_idx, :].set(gw_k) \
        .reshape(bsp.T, bsp.T)
    return ga[:Na, :T_orig], gb.sum(0)[:Nb, :T_orig], gw


def gram_soft_spdtw_block_grad(A: jnp.ndarray, B: jnp.ndarray,
                               bsp: BlockSparsePaths, gamma: float,
                               gbar: jnp.ndarray,
                               T_orig: int | None = None,
                               ba: int = 8, bb: int = 8,
                               interpret: bool = False):
    """Convenience chain: Pallas forward stash + fused Pallas backward,
    with the per-pair feasibility mask folded in. Returns (values,
    (gA, gB, gw))."""
    val, Lstash = gram_soft_fwd_stash_pallas(A, B, bsp, gamma,
                                             T_orig=T_orig, ba=ba, bb=bb,
                                             interpret=interpret)
    if Lstash is None:
        T_o = A.shape[1] if T_orig is None else T_orig
        return val, (jnp.zeros((A.shape[0], T_o), jnp.float32),
                     jnp.zeros((B.shape[0], T_o), jnp.float32),
                     jnp.zeros((bsp.T, bsp.T), jnp.float32))
    gb_eff = jnp.asarray(gbar, jnp.float32) * (val < 1e29)
    return val, gram_soft_bwd_pallas(A, B, bsp, gamma, Lstash, gb_eff,
                                     T_orig=T_orig, ba=ba, bb=bb,
                                     interpret=interpret)


# ---------------------------------------------------------------------------
# Differentiable batched entries (custom VJPs)
# ---------------------------------------------------------------------------

def _is_traced(v) -> bool:
    from .backends import is_traced
    return is_traced(v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def soft_spdtw_batch(x: jnp.ndarray, y: jnp.ndarray, weights: jnp.ndarray,
                     gamma: float) -> jnp.ndarray:
    """Batched aligned-pair soft-SP-DTW, differentiable in x, y, weights.

    x, y: (B, T) or (B, T, d) — pair p is (x[p], y[p]); weights: (T, T)
    learned grid (0 outside the support). Returns (B,) soft distances,
    +INF where the support admits no path. When ``weights`` is
    host-concrete (the usual case: the learned grid is a frozen
    compile-time artifact closed over by the training step) *both*
    passes run on the block-sparse active-tile schedule: the forward
    stashes per-tile L blocks and the backward walks the cached plan in
    reverse (``soft_spdtw_bwd_block``, DESIGN.md §11) — gradients never
    leave the learned search space and backward work scales with active
    tiles exactly like the forward. A traced weight grid falls back to
    the vmapped core recursion and its dense expected-alignment backward
    (fully traceable; the oracle) — the capability walk in
    ``kernels.backends.resolve``.
    """
    return _soft_batch_value(x, y, weights, gamma)


def _soft_batch_value(x, y, weights, gamma):
    if not _is_traced(weights):
        from .backends import resolve_plan
        bsp = resolve_plan(weights=weights)
        return soft_spdtw_paired_scan(x, y, bsp, gamma, T_orig=x.shape[1])
    return jax.vmap(
        lambda a, b: _soft_forward(a, b, weights, gamma)[0])(x, y)


def _soft_batch_fwd(x, y, weights, gamma):
    if not _is_traced(weights):
        from .backends import resolve_plan
        bsp = resolve_plan(weights=weights)
        val, stash = soft_spdtw_fwd_stash(
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
            bsp, gamma, T_orig=x.shape[1])
        return val, (x, y, weights, val, stash)
    val = jax.vmap(
        lambda a, b: _soft_forward(a, b, weights, gamma)[0])(x, y)
    return val, (x, y, weights, None, None)


def _soft_batch_bwd(gamma, res, gbar):
    x, y, weights, val, stash = res
    if stash is not None:
        from .backends import resolve_plan
        bsp = resolve_plan(weights=weights)
        gb = (jnp.asarray(gbar, jnp.float32) * (val < 1e29))
        gx, gy, gwp = soft_spdtw_bwd_block(
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
            bsp, gamma, stash, gb, T_orig=x.shape[1])
        Tw = weights.shape[0]
        return (gx.astype(x.dtype), gy.astype(y.dtype),
                gwp[:Tw, :Tw].astype(weights.dtype))
    if not _is_traced(weights):
        # concrete grid whose corner tile is inactive: value is +INF for
        # every pair, gradients are identically zero
        return (jnp.zeros_like(x), jnp.zeros_like(y),
                jnp.zeros_like(weights))
    # traced weights: dense vmapped expected-alignment backward (oracle)
    gx, gy, gw = jax.vmap(
        lambda a, b: _soft_grads(a, b, weights, gamma))(x, y)
    gsh = gbar[:, None] if x.ndim == 2 else gbar[:, None, None]
    return (gsh * gx, gsh * gy,
            jnp.einsum("b,bij->ij", gbar, gw).astype(weights.dtype))


soft_spdtw_batch.defvjp(_soft_batch_fwd, _soft_batch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def soft_spdtw_gram_batch(A: jnp.ndarray, B: jnp.ndarray,
                          weights: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """All-pairs soft-SP-DTW Gram matrix, differentiable in A, B, weights.

    A: (Na, T) or (Na, T, d); B likewise; weights: (T, T). Returns
    (Na, Nb). Forward runs the block-sparse Gram engine when
    ``weights`` is host-concrete; the backward is the reverse active-tile
    sweep over the stashed L blocks (DESIGN.md §11). Both passes take
    the backend that ``resolve`` gives for DIFFERENTIABLE: the scan
    engines, since the soft Pallas kernels do not compile for the chip
    and the pallas record omits that capability. Traced weight grids
    fall back to the nested-vmap dense recursion and its dense backward.
    """
    return _soft_gram_value(A, B, weights, gamma)


def _dense_gram(A, B, weights, gamma):
    from repro.core.softdtw import soft_wdtw
    f = jax.vmap(jax.vmap(lambda a, b: soft_wdtw(a, b, weights, gamma),
                          in_axes=(None, 0)), in_axes=(0, None))
    return f(A, B)


def _gram_vjp_backend(A, weights):
    """Backend of the Gram VJP passes: the capability walk in
    ``kernels.backends.resolve`` (the Pallas stash/backward kernels are
    univariate, so multivariate gradients require MULTIVARIATE_GRAD and
    land on scan; traced grids land on dense)."""
    from . import backends as bk
    require = [bk.DIFFERENTIABLE]
    if _is_traced(weights):
        require.append(bk.TRACED_WEIGHTS)
    if bk.series_dim(A) > 1:
        require.append(bk.MULTIVARIATE_GRAD)
    return bk.resolve("auto", require=tuple(require)).name


def _soft_gram_value(A, B, weights, gamma):
    backend = _gram_vjp_backend(A, weights)
    if backend == "dense":
        return _dense_gram(A, B, weights, gamma)
    from .backends import resolve_plan
    bsp = resolve_plan(weights=weights)
    if backend == "pallas":
        return gram_soft_spdtw_block(A, B, bsp, gamma, T_orig=A.shape[1])
    return gram_soft_spdtw_scan(A, B, bsp, gamma, T_orig=A.shape[1])


def _soft_gram_fwd(A, B, weights, gamma):
    backend = _gram_vjp_backend(A, weights)
    if backend != "dense":
        from .backends import resolve_plan
        bsp = resolve_plan(weights=weights)
        Af = jnp.asarray(A, jnp.float32)
        Bf = jnp.asarray(B, jnp.float32)
        if backend == "pallas":
            val, stash = gram_soft_fwd_stash_pallas(Af, Bf, bsp, gamma,
                                                    T_orig=A.shape[1])
        else:
            val, stash = gram_soft_fwd_stash(Af, Bf, bsp, gamma,
                                             T_orig=A.shape[1])
        return val, (A, B, weights, val, stash)
    return _dense_gram(A, B, weights, gamma), (A, B, weights, None, None)


def _soft_gram_bwd(gamma, res, gbar):
    A, B, weights, val, stash = res
    if stash is not None:
        from .backends import resolve_plan
        backend = _gram_vjp_backend(A, weights)
        bsp = resolve_plan(weights=weights)
        gb = (jnp.asarray(gbar, jnp.float32) * (val < 1e29))
        Af = jnp.asarray(A, jnp.float32)
        Bf = jnp.asarray(B, jnp.float32)
        if backend == "pallas":
            gA, gB, gwp = gram_soft_bwd_pallas(Af, Bf, bsp, gamma, stash,
                                               gb, T_orig=A.shape[1])
        else:
            gA, gB, gwp = gram_soft_bwd_scan(Af, Bf, bsp, gamma, stash,
                                             gb, T_orig=A.shape[1])
        Tw = weights.shape[0]
        return (gA.astype(A.dtype), gB.astype(B.dtype),
                gwp[:Tw, :Tw].astype(weights.dtype))
    if not _is_traced(weights):
        return (jnp.zeros_like(A), jnp.zeros_like(B),
                jnp.zeros_like(weights))
    # traced weights: dense per-pair expected-alignment backward
    grads = jax.vmap(jax.vmap(
        lambda a, b: _soft_grads(a, b, weights, gamma),
        in_axes=(None, 0)), in_axes=(0, None))(A, B)
    gxa, gyb, gw = grads
    gA = jnp.einsum("ab,ab...->a...", gbar, gxa)
    gB = jnp.einsum("ab,ab...->b...", gbar, gyb)
    gW = jnp.einsum("ab,abij->ij", gbar, gw).astype(weights.dtype)
    return gA, gB, gW


soft_spdtw_gram_batch.defvjp(_soft_gram_fwd, _soft_gram_bwd)
