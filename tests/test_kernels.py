"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # offline image: deterministic fallback sampler
    from hyp_fallback import given, settings, st

from repro.core import learn_sparse_paths, block_sparsify
from repro.kernels import (banded_dtw, spdtw_block, wavefront_dtw,
                           wavefront_log_krdtw, mask_to_diagonal_major, ref)

RNG = np.random.default_rng(42)


def batch(B, T, dtype=np.float32, rng=RNG):
    return (jnp.asarray(rng.normal(size=(B, T)).astype(dtype)),
            jnp.asarray(rng.normal(size=(B, T)).astype(dtype)))


# ------------------------------------------------------------ wavefront DTW
@pytest.mark.parametrize("B,T", [(1, 4), (3, 17), (8, 32), (11, 64), (2, 128)])
def test_wavefront_dtw_matches_ref(B, T):
    x, y = batch(B, T)
    got = wavefront_dtw(x, y, interpret=True)
    want = ref.dtw_batch(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.float16])
def test_wavefront_dtw_dtypes(dtype):
    x, y = batch(4, 24, dtype=np.float32)
    x, y = x.astype(dtype), y.astype(dtype)
    got = wavefront_dtw(x, y, interpret=True)
    want = ref.dtw_batch(x.astype(jnp.float32), y.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-2)


@pytest.mark.parametrize("B,T,r", [(4, 16, 3), (6, 33, 7), (3, 50, 0)])
def test_wavefront_dtw_banded_matches_ref(B, T, r):
    x, y = batch(B, T)
    got = wavefront_dtw(x, y, radius=r, interpret=True)
    want = ref.dtw_band_batch(x, y, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(3, 40), st.integers(0, 10_000))
def test_property_wavefront_dtw(B, T, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    got = wavefront_dtw(x, y, interpret=True)
    want = ref.dtw_batch(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4)


# --------------------------------------------------------------- banded DTW
@pytest.mark.parametrize("B,T,r", [(2, 16, 2), (5, 40, 5), (8, 64, 11),
                                   (1, 20, 0), (3, 33, 16)])
def test_banded_dtw_matches_ref(B, T, r):
    x, y = batch(B, T)
    got = banded_dtw(x, y, r, interpret=True)
    want = ref.dtw_band_batch(x, y, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


def test_banded_equals_full_when_radius_covers():
    x, y = batch(4, 20)
    got = banded_dtw(x, y, 20, interpret=True)
    want = ref.dtw_batch(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


# --------------------------------------------------------- block-sparse SP
def _learned(T, N=7, theta=1.0, gamma=0.0, seed=3, tile=8):
    rng = np.random.default_rng(seed)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    X = jnp.asarray((base[None] + 0.3 * rng.normal(size=(N, T))
                     ).astype(np.float32))
    sp = learn_sparse_paths(X, theta=theta, gamma=gamma)
    return sp, block_sparsify(sp, tile=tile)


@pytest.mark.parametrize("T,tile,theta,gamma", [
    (16, 8, 1.0, 0.0), (24, 8, 1.0, 0.5), (33, 16, 2.0, 0.0),
    (48, 16, 0.0, 1.0), (40, 8, 3.0, 0.25),
])
def test_spdtw_block_matches_ref(T, tile, theta, gamma):
    sp, bsp = _learned(T, theta=theta, gamma=gamma, tile=tile)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(5, T)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(5, T)).astype(np.float32))
    got = spdtw_block(x, y, bsp, T_orig=T, interpret=True)
    want = ref.wdtw_batch(x, y, sp.weights)
    w = np.asarray(want)
    g = np.asarray(got)
    feasible = w < 1e29
    np.testing.assert_allclose(g[feasible], w[feasible], rtol=2e-5)
    assert (g[~feasible] >= 1e29).all()


def test_spdtw_block_skips_tiles():
    """The kernel only schedules active tiles (work ∝ survivors)."""
    sp, bsp = _learned(64, theta=2.0, tile=8)
    assert bsp.n_active < bsp.active.size  # actually sparse
    assert bsp.tile_sparsity > 0.2


def test_spdtw_block_full_support_is_dtw():
    sp, bsp = _learned(32, theta=-1.0, tile=8)  # keep all cells
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    got = spdtw_block(x, y, bsp, T_orig=32, interpret=True)
    want = ref.dtw_batch(x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


@settings(max_examples=8, deadline=None)
@given(st.integers(10, 40), st.sampled_from([4, 8, 16]),
       st.floats(0.0, 4.0), st.integers(0, 10_000))
def test_property_spdtw_block(T, tile, theta, seed):
    sp, bsp = _learned(T, theta=theta, tile=tile, seed=seed)
    rng = np.random.default_rng(seed + 1)
    x = jnp.asarray(rng.normal(size=(3, T)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(3, T)).astype(np.float32))
    got = np.asarray(spdtw_block(x, y, bsp, T_orig=T, interpret=True))
    want = np.asarray(ref.wdtw_batch(x, y, sp.weights))
    feasible = want < 1e29
    np.testing.assert_allclose(got[feasible], want[feasible], rtol=2e-4)
    assert (got[~feasible] >= 1e29).all()


# ------------------------------------------------------------------- krdtw
@pytest.mark.parametrize("B,T,nu", [(2, 8, 1.0), (4, 21, 0.5), (6, 48, 2.0)])
def test_wavefront_krdtw_matches_ref(B, T, nu):
    x, y = batch(B, T)
    got = wavefront_log_krdtw(x, y, nu, interpret=True)
    want = ref.log_krdtw_batch(x, y, nu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,T,nu,r", [(3, 16, 1.0, 3), (2, 30, 0.7, 6)])
def test_wavefront_krdtw_banded(B, T, nu, r):
    x, y = batch(B, T)
    got = wavefront_log_krdtw(x, y, nu, radius=r, interpret=True)
    want = ref.log_krdtw_band_batch(x, y, nu, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_wavefront_krdtw_sparse_support():
    T, nu = 24, 1.0
    sp, _ = _learned(T, theta=1.0)
    x, y = batch(3, T)
    md = jnp.asarray(mask_to_diagonal_major(np.asarray(sp.support)))
    got = wavefront_log_krdtw(x, y, nu, mask_diag=md, interpret=True)
    want = ref.log_krdtw_masked_batch(x, y, nu, sp.support)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_wavefront_krdtw_long_series_stable():
    x, y = batch(2, 300)
    got = np.asarray(wavefront_log_krdtw(x, y, 1.0, interpret=True))
    assert np.isfinite(got).all()

