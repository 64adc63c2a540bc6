"""Admissible lower bounds + early-abandon engines (DESIGN.md §4).

Every bound must satisfy b(q, c) <= SP-DTW(q, c) on feasible pairs — the
cascade's exactness rests on nothing else. Checked against the dense
masked-DP oracle on learned and random sparse supports, plus the
early-abandon gram engines (scan and interpret-mode Pallas) and the
aligned-pair scan engine.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (SparsePaths, block_sparsify, build_corpus_index,
                        envelopes, learn_sparse_paths, lb_keogh_cross,
                        lb_kim_band_cross, lb_kim_cross, make_measure,
                        row_min_weights, support_extents)
from repro.kernels import (gram_prefix_bound, gram_spdtw_block,
                           gram_spdtw_scan, prefix_tile_count,
                           spdtw_paired_scan)

RNG = np.random.default_rng(11)


def _series(n, T, rng=RNG):
    return jnp.asarray(rng.normal(size=(n, T)).astype(np.float32))


def _learned_sp(T, theta=1.0, gamma=0.0, N=8, seed=3):
    rng = np.random.default_rng(seed)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    X = jnp.asarray((base[None] + 0.3 * rng.normal(size=(N, T))
                     ).astype(np.float32))
    return learn_sparse_paths(X, theta=theta, gamma=gamma)


def _random_sp(T, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    sup = rng.random((T, T)) < density
    sup |= np.eye(T, dtype=bool)
    w = np.where(sup, rng.uniform(0.5, 2.0, (T, T)), 0.0).astype(np.float32)
    return SparsePaths(weights=jnp.asarray(w), support=jnp.asarray(sup),
                       counts=jnp.asarray(w), theta=0.0, gamma=0.0)


def _oracle(A, B, weights):
    from repro.core.dtw import wdtw
    f = jax.vmap(jax.vmap(lambda a, b: wdtw(a, b, weights),
                          in_axes=(None, 0)), in_axes=(0, None))
    return np.asarray(f(A, B))


def _all_bounds(Q, C, idx):
    lb = np.asarray(lb_kim_cross(Q, C, idx.w00, idx.wTT))
    lb = np.maximum(lb, np.asarray(lb_keogh_cross(
        Q, idx.env_lo, idx.env_hi, idx.wmin_rows)))
    qlo, qhi = envelopes(Q, idx.lo_t, idx.hi_t)
    lb = np.maximum(lb, np.asarray(lb_keogh_cross(
        C, qlo, qhi, idx.wmin_cols)).T)
    return lb


# ---------------------------------------------------------------- extents
def test_support_extents_bruteforce():
    sup = np.asarray(_random_sp(17, density=0.25, seed=5).support)
    lo, hi = support_extents(sup)
    for i in range(17):
        cols = np.nonzero(sup[i])[0]
        assert lo[i] == cols.min() and hi[i] == cols.max()


def test_support_extents_empty_rows():
    sup = np.zeros((6, 6), bool)
    sup[0, 0] = sup[5, 5] = True
    lo, hi = support_extents(sup)
    assert lo[2] == 6 and hi[2] == -1            # inverted window
    w = row_min_weights(np.where(sup, 1.0, 0.0))
    assert w[2] >= 1e29                           # empty row -> +INF floor


def test_envelopes_match_bruteforce():
    T = 20
    sp = _learned_sp(T, theta=1.0)
    lo, hi = support_extents(sp.support)
    C = _series(5, T)
    L, U = envelopes(C, lo, hi)
    Cn = np.asarray(C)
    for n in range(5):
        for i in range(T):
            win = Cn[n, lo[i]:hi[i] + 1]
            np.testing.assert_allclose(np.asarray(L)[n, i], win.min())
            np.testing.assert_allclose(np.asarray(U)[n, i], win.max())


# ------------------------------------------------------------ admissibility
@pytest.mark.parametrize("theta,gamma", [(1.0, 0.0), (1.0, 0.5), (2.0, 1.0)])
def test_bounds_admissible_learned_support(theta, gamma):
    T = 28
    sp = _learned_sp(T, theta=theta, gamma=gamma)
    m = make_measure("spdtw", T, sp=sp)
    C = _series(7, T)
    Q = _series(5, T)
    idx = m.build_index(C)
    lb = _all_bounds(Q, C, idx)
    full = _oracle(Q, C, sp.weights)
    feas = full < 1e29
    assert (lb[feas] <= full[feas] * (1 + 1e-5) + 1e-5).all()


@pytest.mark.parametrize("density,seed", [(0.25, 0), (0.6, 1)])
def test_bounds_admissible_random_support(density, seed):
    T = 24
    sp = _random_sp(T, density=density, seed=seed)
    idx = build_corpus_index(_series(6, T), sp.weights)
    Q = _series(4, T)
    lb = _all_bounds(Q, idx.corpus, idx)
    full = _oracle(Q, idx.corpus, sp.weights)
    feas = full < 1e29
    assert (lb[feas] <= full[feas] * (1 + 1e-5) + 1e-5).all()


def test_bounds_admissible_plain_dtw():
    """All-ones support: kim/keogh reduce to the classic unweighted
    bounds against full-range envelopes."""
    T = 16
    m = make_measure("dtw", T)
    C, Q = _series(6, T), _series(4, T)
    idx = m.build_index(C)
    lb = _all_bounds(Q, C, idx)
    from repro.core.dtw import dtw
    full = np.asarray(jax.vmap(jax.vmap(dtw, in_axes=(None, 0)),
                               in_axes=(0, None))(Q, C))
    assert (lb <= full * (1 + 1e-5) + 1e-5).all()


def test_prefix_bound_admissible_and_monotone():
    T = 32
    sp = _learned_sp(T, theta=1.0, gamma=0.5)
    bsp = block_sparsify(sp, tile=8)
    Q, C = _series(4, T), _series(6, T)
    full = _oracle(Q, C, sp.weights)
    prev = np.zeros_like(full)
    for frac in (0.25, 0.5, 0.75):
        n_p = prefix_tile_count(bsp, frac, T)
        assert n_p > 0
        lb = np.asarray(gram_prefix_bound(Q, C, bsp, n_p, T_orig=T))
        feas = full < 1e29
        assert (lb[feas] <= full[feas] * (1 + 1e-5) + 1e-5).all()
        # deeper prefixes only tighten (row-min of later rows >= earlier)
        assert (lb >= prev - 1e-4).all()
        prev = lb


# ------------------------------------------------- compiled bound stage
def _stage_index(support, d, Nc, T=24, seed=4):
    """A corpus index over a random or learned support, (Nc, T[, d])."""
    sp = _random_sp(T, density=0.3, seed=seed) if support == "random" \
        else _learned_sp(T, theta=1.0, gamma=0.5)
    rng = np.random.default_rng(seed)
    shape = (Nc, T) if d is None else (Nc, T, d)
    C = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    return sp, build_corpus_index(C, sp.weights)


def _stage_queries(n, T, d, seed=5):
    shape = (n, T) if d is None else (n, T, d)
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


def _eager_stage(Q, idx):
    """The bound stage as eager calls: banded Kim, then Keogh both ways
    (the corpus orientation chunked over 256 series at a time)."""
    C = idx.corpus
    lb1 = np.asarray(lb_kim_band_cross(Q, C, idx.lo, idx.hi, idx.wmin_rows,
                                       idx.w00, idx.wTT))
    lb2 = np.maximum(lb1, np.asarray(lb_keogh_cross(
        Q, idx.env_lo, idx.env_hi, idx.wmin_rows)))
    qlo, qhi = envelopes(Q, idx.lo_t, idx.hi_t)
    lb2 = np.maximum(lb2, np.asarray(lb_keogh_cross(
        C, qlo, qhi, idx.wmin_cols)).T)
    return lb1, lb2


@pytest.mark.parametrize("Nc", [7, 300])
@pytest.mark.parametrize("d", [None, 3])
@pytest.mark.parametrize("support", ["random", "learned"])
def test_cascade_bounds_equal_the_eager_stage(support, d, Nc):
    """The one-program bound stage equals the eager composition; 300
    corpus series are not a multiple of the eager chunk of 256."""
    _, idx = _stage_index(support, d, Nc)
    Q = _stage_queries(5, 24, d)
    got = idx.cascade_bounds(Q)
    for g, e in zip(got, _eager_stage(Q, idx)):
        g = np.asarray(g)
        assert g.shape == (5, Nc) and g.dtype == np.float32
        np.testing.assert_allclose(g, e, rtol=1e-5, atol=0)


@pytest.mark.parametrize("d", [None, 3])
@pytest.mark.parametrize("support", ["random", "learned"])
def test_cascade_bounds_admissible(support, d):
    """lb1 <= lb2 <= exact SP-DTW on every feasible pair."""
    sp, idx = _stage_index(support, d, 6)
    Q = _stage_queries(4, 24, d)
    lb1, lb2 = (np.asarray(b) for b in idx.cascade_bounds(Q))
    full = _oracle(Q, idx.corpus, sp.weights)
    feas = full < 1e29
    assert feas.any()
    assert (lb1 <= lb2).all()
    assert (lb2[feas] <= full[feas] * (1 + 1e-5) + 1e-5).all()


@pytest.mark.parametrize("row", [1, 12], ids=["band_row", "middle_row"])
def test_cascade_bounds_empty_row_is_inf(row):
    """A support with an empty row admits no path: lb2 is +INF
    everywhere, and lb1 too where the row lies in Kim's band."""
    T = 24
    w = np.asarray(_random_sp(T, density=0.4, seed=6).weights).copy()
    w[row] = 0.0
    idx = build_corpus_index(_series(9, T), w)
    Q = _series(3, T)
    lb1, lb2 = (np.asarray(b) for b in idx.cascade_bounds(Q))
    assert (lb2 >= 1e29).all()
    assert (lb1 >= 1e29).all() == (row == 1)
    for g, e in zip((lb1, lb2), _eager_stage(Q, idx)):
        np.testing.assert_allclose(g, e, rtol=1e-5, atol=0)


# --------------------------------------------------- early-abandon engines
def test_gram_engines_default_thresholds_unchanged():
    """thresholds=None must stay bit-identical to the unabandoned path."""
    T = 24
    sp = _learned_sp(T, theta=1.0)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(5, T), _series(6, T)
    base = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    thr = jnp.full((5,), jnp.float32(1e30))
    withthr = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T,
                                         thresholds=thr))
    assert np.array_equal(base, withthr)


@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_gram_early_abandon_exact_or_inf(engine):
    """Abandoned pairs report +INF and are provably above the threshold;
    survivors are untouched."""
    T = 24
    sp = _learned_sp(T, theta=1.0)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(6, T), _series(9, T)
    base = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    thr = jnp.asarray(np.partition(base, 2, axis=1)[:, 2])
    if engine == "scan":
        got = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T,
                                         thresholds=thr))
    else:
        got = np.asarray(gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4,
                                          interpret=True, thresholds=thr))
    ab = got >= 1e29
    assert np.array_equal(got[~ab], base[~ab])
    assert (base[ab] > np.asarray(thr)[:, None].repeat(9, 1)[ab]).all()
    # per-row: the row minimum (the 1-NN answer) is never abandoned
    assert np.array_equal(got.min(axis=1), base.min(axis=1))


def test_gram_alive0_prekill():
    T = 16
    sp = _learned_sp(T, theta=1.0)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(4, T), _series(5, T)
    base = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    alive = RNG.random((4, 5)) < 0.5
    for got in (
            gram_spdtw_scan(A, B, bsp, T_orig=T, alive0=jnp.asarray(alive)),
            gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4,
                             interpret=True, alive0=jnp.asarray(alive))):
        got = np.asarray(got)
        assert np.array_equal(got[alive], base[alive])
        assert (got[~alive] >= 1e29).all()


def test_paired_scan_matches_gram_diagonal():
    """The aligned-pair engine equals the Gram engine's matching entries."""
    T = 24
    sp = _learned_sp(T, theta=1.0, gamma=0.5)
    bsp = block_sparsify(sp, tile=8)
    x, y = _series(7, T), _series(7, T)
    G = np.asarray(gram_spdtw_scan(x, y, bsp, T_orig=T))
    p = np.asarray(spdtw_paired_scan(x, y, bsp, T_orig=T))
    np.testing.assert_allclose(p, np.diag(G), rtol=1e-6)
    # chunking invariance
    p2 = np.asarray(spdtw_paired_scan(x, y, bsp, T_orig=T, block_p=3))
    assert np.array_equal(p, p2)
