"""In-DP PrunedDTW + cascade extensions (DESIGN.md §14).

Property-style checks (hypothesis when available, the deterministic
``hyp_fallback`` sampler otherwise) that every bound added by the
pruning upgrade stays admissible, that the in-DP pruned sweep is
exact-or-+INF with the row minimum (the 1-NN answer) bit-identical, that
live-tile work shrinks monotonically as thresholds tighten, and that
``engine.knn`` runs the cascade — bit-identical to the exact argmin —
for the kernel (krdtw / sp_krdtw) and multivariate engines the cascade
now covers.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from hyp_fallback import given, settings, st

from repro.core import (SparsePaths, block_sparsify, krdtw_log_slacks,
                        lb_keogh_cross, lb_kim_band_cross, lb_kim_cross,
                        lb_log_krdtw, learn_sparse_paths, log_krdtw,
                        row_min_weights, support_extents)
from repro.core import engine as eng_mod
from repro.core.bounds import envelopes
from repro.core.dtw import wdtw
from repro.core.spec import MeasureSpec
from repro.kernels import backends as bk
from repro.kernels import gram_spdtw_block, gram_spdtw_scan, spdtw_paired_scan

INF_CUT = 1e29


def _series(n, T, d=None, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n, T) if d is None else (n, T, d)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


def _random_sp(T, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    sup = rng.random((T, T)) < density
    sup |= np.eye(T, dtype=bool)
    w = np.where(sup, rng.uniform(0.5, 2.0, (T, T)), 0.0).astype(np.float32)
    return SparsePaths(weights=jnp.asarray(w), support=jnp.asarray(sup),
                       counts=jnp.asarray(w), theta=0.0, gamma=0.0)


def _learned_sp(T, theta=1.0, gamma=0.0, N=8, seed=3):
    rng = np.random.default_rng(seed)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    X = jnp.asarray((base[None] + 0.3 * rng.normal(size=(N, T))
                     ).astype(np.float32))
    return learn_sparse_paths(X, theta=theta, gamma=gamma)


def _oracle(A, B, weights):
    f = jax.vmap(jax.vmap(lambda a, b: wdtw(a, b, weights),
                          in_axes=(None, 0)), in_axes=(0, None))
    return np.asarray(f(A, B))


# --------------------------------------------------------- banded LB_Kim
@settings(max_examples=6, deadline=None)
@given(st.floats(0.2, 0.7), st.integers(0, 10 ** 6),
       st.sampled_from([None, 2]))
def test_banded_kim_admissible(density, seed, d):
    """Banded Kim <= the dense masked-DP oracle, univariate and (T, d)."""
    T = 20
    sp = _random_sp(T, density=density, seed=seed)
    w = np.asarray(sp.weights)
    lo, hi = support_extents(sp.support)
    wmin = row_min_weights(w)
    Q, C = _series(3, T, d, seed=seed + 1), _series(4, T, d, seed=seed + 2)
    lb = np.asarray(lb_kim_band_cross(Q, C, lo, hi, wmin,
                                      w[0, 0], w[T - 1, T - 1]))
    full = _oracle(Q, C, sp.weights)
    feas = full < INF_CUT
    assert (lb[feas] <= full[feas] * (1 + 1e-5) + 1e-5).all()


def test_banded_kim_dominates_plain_kim():
    """The band rows only add non-negative terms on top of the plain
    endpoint bound — the new stage-1 is never looser than the old one."""
    T = 24
    sp = _learned_sp(T)
    w = np.asarray(sp.weights)
    lo, hi = support_extents(sp.support)
    wmin = row_min_weights(w)
    Q, C = _series(5, T, seed=1), _series(7, T, seed=2)
    plain = np.asarray(lb_kim_cross(Q, C, w[0, 0], w[T - 1, T - 1]))
    band = np.asarray(lb_kim_band_cross(Q, C, lo, hi, wmin,
                                        w[0, 0], w[T - 1, T - 1]))
    assert (band >= plain - 1e-5).all()
    assert band.mean() > plain.mean()       # and strictly tighter somewhere


def test_multivariate_keogh_admissible():
    """(T, d) envelopes + channel-summed Keogh penalty <= the mv oracle."""
    T, d = 20, 3
    sp = _random_sp(T, density=0.4, seed=9)
    lo, hi = support_extents(sp.support)
    wmin = row_min_weights(np.asarray(sp.weights))
    Q, C = _series(3, T, d, seed=4), _series(5, T, d, seed=5)
    L, U = envelopes(C, lo, hi)
    assert L.shape == (5, T, d) and U.shape == (5, T, d)
    lb = np.asarray(lb_keogh_cross(Q, L, U, wmin))
    full = _oracle(Q, C, sp.weights)
    feas = full < INF_CUT
    assert (lb[feas] <= full[feas] * (1 + 1e-5) + 1e-5).all()


# ---------------------------------------------------- log-semiring bound
@settings(max_examples=6, deadline=None)
@given(st.floats(0.3, 2.0), st.sampled_from(["krdtw", "sp_krdtw"]),
       st.integers(0, 10 ** 6))
def test_krdtw_bound_admissible(nu, kind, seed):
    """lb_log_krdtw <= -log K_rdtw for the full grid and masked supports:
    the slack terms really do upper-bound each semiring sum."""
    T = 16
    if kind == "sp_krdtw":
        sp = _random_sp(T, density=0.5, seed=seed)
        sup = np.asarray(sp.support)
        mask = jnp.asarray(sup)
        log_s1, log_s2 = krdtw_log_slacks(sup)
    else:
        sup = np.ones((T, T), bool)
        mask = None
        log_s1, log_s2 = krdtw_log_slacks(T=T)
    Q, C = _series(3, T, seed=seed + 1), _series(4, T, seed=seed + 2)
    # admissible unit-weight min-path bounds: banded Kim with unit floors
    lo, hi = support_extents(jnp.asarray(sup))
    wmin = row_min_weights(sup.astype(np.float32))
    b1 = np.asarray(lb_kim_band_cross(Q, C, lo, hi, wmin, 1.0, 1.0))
    Qn, Cn = np.asarray(Q), np.asarray(C)
    b2 = ((Qn[:, None, 0] - Cn[None, :, 0]) ** 2 +
          (Qn[:, None, -1] - Cn[None, :, -1]) ** 2)
    lb = np.asarray(lb_log_krdtw(jnp.asarray(b1), jnp.asarray(b2),
                                 nu, log_s1, log_s2))
    exact = np.asarray([[-float(log_krdtw(q, c, nu, mask)) for c in C]
                        for q in Q])
    assert (lb <= exact * (1 + 1e-5) + 1e-4).all()


# ------------------------------------------------------- in-DP PrunedDTW
@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_indp_prune_inf_threshold_bit_identical(engine):
    """+INF thresholds engage the pruned sweep but must change nothing."""
    T = 24
    bsp = block_sparsify(_learned_sp(T), tile=8)
    A, B = _series(5, T, seed=1), _series(6, T, seed=2)
    base = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    thr = jnp.full((5,), jnp.float32(1e30))
    if engine == "scan":
        got = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T,
                                         thresholds=thr))
    else:
        got = np.asarray(gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4,
                                          interpret=True, thresholds=thr))
    assert np.array_equal(base, got)


@pytest.mark.parametrize("engine", ["scan", "pallas"])
@pytest.mark.parametrize("d", [None, 2])
def test_indp_prune_exact_or_inf(engine, d):
    """Tight thresholds: surviving entries bit-identical, pruned entries
    +INF and provably above the threshold, row minima untouched."""
    T = 24
    bsp = block_sparsify(_learned_sp(T), tile=8)
    A, B = _series(6, T, d, seed=3), _series(9, T, d, seed=4)
    base = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    thr = jnp.asarray(np.partition(base, 2, axis=1)[:, 2])
    if engine == "scan":
        got = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T,
                                         thresholds=thr))
    else:
        got = np.asarray(gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4,
                                          interpret=True, thresholds=thr))
    ab = got >= INF_CUT
    assert np.array_equal(got[~ab], base[~ab])
    assert (base[ab] > np.asarray(thr)[:, None].repeat(B.shape[0], 1)[ab]
            ).all()
    assert np.array_equal(got.min(axis=1), base.min(axis=1))


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_indp_live_tiles_monotone(seed):
    """The live-tile counter equals the static support at +INF thresholds
    and shrinks monotonically per pair as thresholds tighten."""
    T = 32
    bsp = block_sparsify(_learned_sp(T, seed=seed % 97), tile=8)
    A, B = _series(4, T, seed=seed + 1), _series(6, T, seed=seed + 2)
    base = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    thr_inf = jnp.full((4,), jnp.float32(1e30))
    _, t_inf = gram_spdtw_scan(A, B, bsp, T_orig=T, thresholds=thr_inf,
                               return_tiles=True)
    assert (np.asarray(t_inf) == bsp.n_active).all()
    nn = base.min(axis=1)
    prev = np.asarray(t_inf)
    for alpha in (4.0, 1.5, 1.0):
        thr = jnp.asarray((alpha * nn).astype(np.float32))
        _, tl = gram_spdtw_scan(A, B, bsp, T_orig=T, thresholds=thr,
                                return_tiles=True)
        tl = np.asarray(tl)
        assert (tl <= prev).all()           # per-pair, not just in the mean
        prev = tl
    assert prev.mean() < bsp.n_active       # the tightest sweep skipped work


# ------------------------------------------- survivor DP work counters
def _counted(A, B, bsp, T, thr, alive0=None, ba=4, bb=8):
    """(tile sweeps, live pair sweeps) of the Pallas Gram kernel."""
    _, (sw, lv) = gram_spdtw_block(
        A, B, bsp, T_orig=T, ba=ba, bb=bb, interpret=True, thresholds=thr,
        alive0=None if alive0 is None else jnp.asarray(alive0),
        return_counts=True)
    return int(sw), int(lv)


@pytest.mark.parametrize("case", ["all_alive", "none_alive", "one_per_block"])
def test_gram_kernel_counts_sweeps(case):
    """+INF thresholds: every alive pair is live in every tile, so a block
    with any alive pair sweeps all n_active tiles; a block with none
    sweeps nothing."""
    T = 24
    bsp = block_sparsify(_learned_sp(T), tile=8)
    A, B = _series(8, T, seed=11), _series(16, T, seed=12)
    thr = jnp.full((8,), jnp.float32(1e30))
    n_blocks = (8 // 4) * (16 // 8)
    if case == "all_alive":
        assert _counted(A, B, bsp, T, thr) == (
            n_blocks * bsp.n_active, 8 * 16 * bsp.n_active)
    elif case == "none_alive":
        assert _counted(A, B, bsp, T, thr, np.zeros((8, 16), bool)) == (0, 0)
    else:
        alive0 = np.zeros((8, 16), bool)
        alive0[::4, ::8] = True                  # one pair in each block
        sw, lv = _counted(A, B, bsp, T, thr, alive0)
        assert sw == lv == n_blocks * bsp.n_active


@pytest.mark.parametrize("shape", [(4, 8), (8, 24)], ids=["one_block",
                                                          "six_blocks"])
def test_gram_kernel_counts_match_the_scan_path(shape):
    """The cascade's two survivor engines count the same work: the Pallas
    Gram kernel over (thresholds, alive0), and the scan path's paired
    sweep over the alive pairs. Live pair sweeps agree always; tile
    sweeps where the pairs form one block in both."""
    T = 24
    na, nb = shape
    bsp = block_sparsify(_learned_sp(T), tile=8)
    A, B = _series(na, T, seed=13), _series(nb, T, seed=14)
    base = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    thr = jnp.asarray(np.partition(base, 2, axis=1)[:, 2] * 1.5)
    alive0 = np.random.default_rng(15).random((na, nb)) < 0.6
    sw, lv = _counted(A, B, bsp, T, thr, alive0)
    qi, ci = np.nonzero(alive0)
    _, (sw2, lv2) = spdtw_paired_scan(A[qi], B[ci], bsp, T_orig=T,
                                      thresholds=thr[qi], return_counts=True)
    assert 0 < lv == int(lv2)
    if shape == (4, 8):
        assert sw == int(sw2)
    assert lv < len(qi) * bsp.n_active         # the thresholds pruned work


def test_paired_scan_prune_exact_below_threshold():
    T = 24
    bsp = block_sparsify(_learned_sp(T, gamma=0.5), tile=8)
    x, y = _series(8, T, seed=5), _series(8, T, seed=6)
    base = np.asarray(spdtw_paired_scan(x, y, bsp, T_orig=T))
    thr = jnp.asarray(np.full((8,), np.median(base), np.float32))
    got = np.asarray(spdtw_paired_scan(x, y, bsp, T_orig=T,
                                       thresholds=thr))
    keep = base <= np.asarray(thr)
    assert np.array_equal(got[keep], base[keep])
    assert ((got == base) | (got >= INF_CUT)).all()


# ------------------------------------------------------ engine coverage
@pytest.mark.parametrize("family", ["krdtw", "sp_krdtw"])
def test_kernel_cascade_nn_bit_identical(family):
    """engine.knn runs the log-semiring cascade for kernel engines and
    matches -gram_log argmin bit for bit, with integral counters."""
    rng = np.random.default_rng(21)
    T, Nc, Nq = 32, 24, 6
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    C = (base[None] + 0.4 * rng.normal(size=(Nc, T))).astype(np.float32)
    Q = (base[None] + 0.4 * rng.normal(size=(Nq, T))).astype(np.float32)
    eng = eng_mod.fit(MeasureSpec(family=family, nu=1.0, tile=8), C)
    assert eng.index is not None and eng.index.kind == family
    nn, nnd, st_ = eng.knn(jnp.asarray(Q), return_stats=True)
    D = np.asarray(-eng.gram_log(jnp.asarray(Q)))
    ref = D.argmin(axis=1)
    assert np.array_equal(np.asarray(nn), ref)
    assert np.array_equal(np.asarray(nnd), D[np.arange(Nq), ref])
    assert isinstance(st_["dp_pairs"], int)
    assert st_["dp_pairs"] <= Nq * Nc + Nq * 2   # cascade, not full Gram


def test_multivariate_cascade_nn_bit_identical():
    """(T, d) corpora get a cascade index at fit time; knn matches the
    exact Gram argmin bit for bit and prunes pairs."""
    rng = np.random.default_rng(22)
    T, d, Nc, Nq = 32, 2, 24, 6
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    mk = lambda n, s: np.stack(
        [base[None] + s * rng.normal(size=(n, T)),
         np.cos(np.linspace(0, 2 * np.pi, T))[None]
         + s * rng.normal(size=(n, T))], axis=-1).astype(np.float32)
    C, Q = mk(Nc, 0.3), mk(Nq, 0.3)
    eng = eng_mod.fit(MeasureSpec(family="spdtw", tile=8), C)
    assert eng.index is not None, "mv fit must build the cascade index"
    nn, nnd, st_ = eng.knn(jnp.asarray(Q), return_stats=True)
    G = np.asarray(eng.gram(jnp.asarray(Q)))
    ref = G.argmin(axis=1)
    assert np.array_equal(np.asarray(nn), ref)
    assert np.array_equal(np.asarray(nnd), G[np.arange(Nq), ref])
    assert isinstance(st_["dp_pairs"], int)


def test_pruned_dp_capability_registered():
    """The in-DP prune is a declared backend capability: DP backends
    carry it, the dense reference does not."""
    assert bk.PRUNED_DP in bk.CAPABILITIES
    assert bk.PRUNED_DP in bk.get_backend("scan").caps
    assert bk.PRUNED_DP in bk.get_backend("pallas").caps
    assert bk.PRUNED_DP not in bk.get_backend("dense").caps
