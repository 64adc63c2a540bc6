"""Fused all-pairs Gram engine vs oracles (interpret-mode Pallas + jnp scan).

Parity targets:
  * ``ref.wdtw_batch`` nested over the pair grid (the dense jnp oracle),
  * ``spdtw_loc`` — the paper's Algorithm 1, evaluated per entry,
on random sparse supports, ragged Na/Nb not divisible by the tile batch,
and the fully-dense edge case. A compiled-TPU smoke test rides behind the
``tpu`` marker (excluded from tier-1 CPU runs via pytest.ini).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (SparsePaths, block_sparsify, learn_sparse_paths,
                        pairwise, spdtw_loc, spdtw_pairwise)
from repro.kernels import (gram_log_krdtw_block, gram_spdtw_block,
                           gram_spdtw_scan, ref)

RNG = np.random.default_rng(7)


def _series(n, T, rng=RNG):
    return jnp.asarray(rng.normal(size=(n, T)).astype(np.float32))


def _learned_sp(T, theta=1.0, gamma=0.0, N=7, seed=3):
    rng = np.random.default_rng(seed)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    X = jnp.asarray((base[None] + 0.3 * rng.normal(size=(N, T))
                     ).astype(np.float32))
    return learn_sparse_paths(X, theta=theta, gamma=gamma)


def _random_sp(T, density=0.3, seed=0):
    """Random sparse support (diagonal forced, so a path always exists)."""
    rng = np.random.default_rng(seed)
    sup = rng.random((T, T)) < density
    sup |= np.eye(T, dtype=bool)
    w = np.where(sup, rng.uniform(0.5, 2.0, (T, T)), 0.0).astype(np.float32)
    return SparsePaths(weights=jnp.asarray(w), support=jnp.asarray(sup),
                       counts=jnp.asarray(w), theta=0.0, gamma=0.0)


def _oracle(A, B, weights):
    # nested wdtw over the pair grid, chunk-free (test sizes are small)
    from repro.core.dtw import wdtw
    f = jax.vmap(jax.vmap(lambda a, b: wdtw(a, b, weights),
                          in_axes=(None, 0)), in_axes=(0, None))
    return np.asarray(f(A, B))


def _assert_parity(got, want, rtol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    feasible = want < 1e29
    np.testing.assert_allclose(got[feasible], want[feasible], rtol=rtol)
    assert (got[~feasible] >= 1e29).all()


# --------------------------------------------------------- SP-DTW gram
@pytest.mark.parametrize("T,tile,theta,gamma,Na,Nb", [
    (16, 8, 1.0, 0.0, 4, 4),
    (24, 8, 1.0, 0.5, 5, 7),      # ragged: Na, Nb not multiples of ba/bb
    (33, 16, 2.0, 0.0, 3, 9),     # T not a tile multiple either
])
def test_gram_pallas_matches_oracle_learned(T, tile, theta, gamma, Na, Nb):
    sp = _learned_sp(T, theta=theta, gamma=gamma)
    bsp = block_sparsify(sp, tile=tile)
    A, B = _series(Na, T), _series(Nb, T)
    got = gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4, interpret=True)
    _assert_parity(got, _oracle(A, B, sp.weights))


@pytest.mark.parametrize("density,seed", [(0.2, 0), (0.5, 1), (0.8, 2)])
def test_gram_pallas_matches_oracle_random_support(density, seed):
    T = 24
    sp = _random_sp(T, density=density, seed=seed)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(5, T), _series(6, T)
    got = gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4, interpret=True)
    _assert_parity(got, _oracle(A, B, sp.weights))


def test_gram_fully_dense_support_is_dtw():
    T = 32
    w = np.ones((T, T), np.float32)
    bsp = block_sparsify(w, tile=8)
    assert bsp.n_active == bsp.active.size   # nothing to skip
    A, B = _series(5, T), _series(5, T)
    got = gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4, interpret=True)
    from repro.core.dtw import dtw
    want = np.asarray(jax.vmap(jax.vmap(
        dtw, in_axes=(None, 0)), in_axes=(0, None))(A, B))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5)


def test_gram_scan_matches_pallas_and_loc():
    """jnp scan engine == interpret-mode kernel == paper's Algorithm 1."""
    T = 24
    sp = _learned_sp(T, theta=1.0, gamma=0.5)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(3, T), _series(4, T)
    scan = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    pall = np.asarray(gram_spdtw_block(A, B, bsp, T_orig=T,
                                       ba=4, bb=4, interpret=True))
    np.testing.assert_allclose(scan, pall, rtol=1e-6)
    rows, cols, w = sp.loc_list()
    for i in (0, 2):
        for j in (1, 3):
            want = spdtw_loc(np.asarray(A[i]), np.asarray(B[j]),
                             rows, cols, w)
            got = float(scan[i, j])
            if want >= 1e29:
                assert got >= 1e29
            else:
                np.testing.assert_allclose(got, want, rtol=2e-5)


def test_gram_scan_chunking_is_invariant():
    T = 16
    sp = _learned_sp(T, theta=1.0)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(7, T), _series(5, T)
    full = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T, block_a=64))
    chunked = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T, block_a=2))
    np.testing.assert_allclose(full, chunked, rtol=1e-6)


# ------------------------------------------------------- SP-K_rdtw gram
@pytest.mark.parametrize("Na,Nb", [(4, 4), (5, 7)])
def test_gram_krdtw_matches_ref(Na, Nb):
    T, nu = 20, 1.0
    sp = _learned_sp(T, theta=1.0)
    A, B = _series(Na, T), _series(Nb, T)
    got = gram_log_krdtw_block(A, B, nu, support=np.asarray(sp.support),
                               ba=4, bb=4, interpret=True)
    want = np.asarray(ref.log_krdtw_masked_batch(
        jnp.repeat(A, Nb, axis=0), jnp.tile(B, (Na, 1)), nu,
        sp.support)).reshape(Na, Nb)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_gram_krdtw_full_grid():
    T, nu = 16, 0.5
    A, B = _series(3, T), _series(6, T)
    got = gram_log_krdtw_block(A, B, nu, ba=4, bb=4, interpret=True)
    want = np.asarray(ref.log_krdtw_batch(
        jnp.repeat(A, 6, axis=0), jnp.tile(B, (3, 1)), nu)).reshape(3, 6)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- dispatch layer
def test_pairwise_dispatch_impl_parity():
    T = 24
    sp = _learned_sp(T, theta=1.0, gamma=0.5)
    A, B = _series(5, T), _series(6, T)
    dense = pairwise(A, B, "spdtw", sp=sp, impl="dense")
    scan = pairwise(A, B, "spdtw", sp=sp, impl="ref")
    pall = pairwise(A, B, "spdtw", sp=sp, impl="pallas")
    _assert_parity(scan, dense)
    _assert_parity(pall, dense)


def test_spdtw_pairwise_routes_through_engine():
    T = 20
    sp = _learned_sp(T, theta=1.0)
    A, B = _series(6, T), _series(4, T)
    got = spdtw_pairwise(A, B, sp.weights)
    _assert_parity(got, _oracle(A, B, sp.weights))


def test_classify_series_entry_points():
    from repro.classify import knn_error_series, svm_gram_series
    T = 20
    sp = _learned_sp(T, theta=1.0)
    rng = np.random.default_rng(5)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    Xtr = (base[None] + 0.3 * rng.normal(size=(10, T))).astype(np.float32)
    Xte = (base[None] + 0.3 * rng.normal(size=(6, T))).astype(np.float32)
    ytr = np.arange(10) % 2
    yte = np.arange(6) % 2
    err = knn_error_series(Xte, Xtr, ytr, yte, kind="spdtw", sp=sp)
    assert 0.0 <= err <= 1.0
    Ktr, Kte = svm_gram_series(Xtr, Xte, kind="sp_krdtw", sp=sp, nu=1.0)
    assert Ktr.shape == (10, 10) and Kte.shape == (6, 10)
    np.testing.assert_allclose(np.asarray(jnp.diag(Ktr)), 1.0, atol=1e-4)


def test_krdtw_gram_radius_consistent_across_impls():
    """The Sakoe-Chiba corridor must bite on the ref path too, not only in
    the fused kernel (cross-backend parity). The kernel is called directly:
    ``impl="pallas"`` resolves K_rdtw to scan (no WAVEFRONT capability)."""
    from repro.kernels.ops import log_krdtw_gram
    T, nu, r = 16, 1.0, 3
    A, B = _series(3, T), _series(4, T)
    banded_ref = log_krdtw_gram(A, B, nu, radius=r, impl="ref")
    banded_pal = gram_log_krdtw_block(A, B, nu, radius=r, interpret=True)
    unbanded = log_krdtw_gram(A, B, nu, impl="ref")
    np.testing.assert_allclose(np.asarray(banded_ref),
                               np.asarray(banded_pal), rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(banded_ref) - np.asarray(unbanded)).max() > 1e-3


def test_spdtw_gram_dense_impl_with_bsp_only():
    """impl='dense' must stay SP-DTW when only the compressed plan is
    passed (weights densified from the blocks, not silently dropped)."""
    T = 24
    sp = _learned_sp(T, theta=1.0, gamma=0.5)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(4, T), _series(3, T)
    got = pairwise(A, B, "spdtw", bsp=bsp, impl="dense")
    _assert_parity(got, _oracle(A, B, sp.weights))


def test_gram_corner_tile_missing_is_inf():
    """Raw weights whose support misses the bottom-right corner: every
    value must be +INF (no admissible path), not a stale mid-grid row."""
    T = 16
    w = np.zeros((T, T), np.float32)
    w[:8, :8] = 1.0                       # support nowhere near (15, 15)
    bsp = block_sparsify(w, tile=8)
    A, B = _series(3, T), _series(4, T)
    for got in (gram_spdtw_scan(A, B, bsp, T_orig=T),
                gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4,
                                 interpret=True)):
        assert (np.asarray(got) >= 1e29).all()
    want = _oracle(A, B, jnp.asarray(w))
    assert (want >= 1e29).all()           # oracle agrees: infeasible


def test_gram_active_tiles_past_result_cell():
    """T_orig smaller than the weight grid: active tiles beyond the result
    tile must not clobber the captured output row."""
    Tgrid, T = 24, 16
    w = np.ones((Tgrid, Tgrid), np.float32)
    bsp = block_sparsify(w, tile=8)
    A, B = _series(3, T), _series(5, T)
    got = gram_spdtw_scan(A, B, bsp, T_orig=T)
    want = _oracle(A, B, jnp.ones((T, T), jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5)
    got_p = gram_spdtw_block(A, B, bsp, T_orig=T, ba=4, bb=4,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got_p), want, rtol=2e-5)


def test_spdtw_pairwise_traceable_under_jit():
    """Traced weights fall back to the dense path instead of crashing on
    the host-side tile plan (pre-engine behaviour preserved)."""
    T = 16
    sp = _learned_sp(T, theta=1.0)
    A, B = _series(4, T), _series(4, T)
    got = jax.jit(spdtw_pairwise)(A, B, sp.weights)
    _assert_parity(got, _oracle(A, B, sp.weights))


@pytest.mark.tpu
def test_gram_pallas_compiled_on_tpu():
    """Compiled (non-interpret) kernel smoke test; runs only with -m tpu."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU")
    T = 128
    sp = _learned_sp(T, theta=1.0)
    bsp = block_sparsify(sp, tile=128)
    A, B = _series(16, T), _series(16, T)
    got = gram_spdtw_block(A, B, bsp, T_orig=T)
    _assert_parity(got, _oracle(A, B, sp.weights), rtol=1e-4)
