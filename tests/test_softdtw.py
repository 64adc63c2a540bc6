"""Differentiable soft-SP-DTW layer (DESIGN.md §10): gamma -> 0
convergence to the hard DP, custom-VJP gradients vs finite differences
(dense and block-sparse supports), expected-alignment structure, and
parity of the block-sparse engines against the core recursion. The
compiled Pallas soft kernel rides behind the ``tpu`` marker (the jnp scan
path is the tier-1 production path)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import SparsePaths, block_sparsify, learn_sparse_paths
from repro.core.dtw import wdtw
from repro.core.softdtw import (NEG, soft_alignment, soft_dtw, soft_spdtw,
                                soft_wdtw)
from repro.kernels import ops
from repro.kernels.soft_block import (gram_soft_spdtw_block,
                                      gram_soft_spdtw_scan,
                                      soft_spdtw_batch,
                                      soft_spdtw_paired_scan)

RNG = np.random.default_rng(11)


def _series(n, T, rng=RNG):
    return jnp.asarray(rng.normal(size=(n, T)).astype(np.float32))


def _learned_sp(T, theta=1.0, N=7, seed=3):
    rng = np.random.default_rng(seed)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    X = jnp.asarray((base[None] + 0.3 * rng.normal(size=(N, T))
                     ).astype(np.float32))
    return learn_sparse_paths(X, theta=theta)


def _random_sp(T, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    sup = rng.random((T, T)) < density
    sup |= np.eye(T, dtype=bool)
    w = np.where(sup, rng.uniform(0.5, 2.0, (T, T)), 0.0).astype(np.float32)
    return SparsePaths(weights=jnp.asarray(w), support=jnp.asarray(sup),
                       counts=jnp.asarray(w), theta=0.0, gamma=0.0)


# ------------------------------------------------------- gamma -> 0 limit
@pytest.mark.parametrize("support", ["dense", "learned", "random"])
def test_gamma_to_zero_recovers_hard_spdtw(support):
    """gamma = 1e-3 soft distance within 1e-2 of the hard DP (the
    acceptance fixture: dense, learned and random sparse supports)."""
    T = 32
    w = {"dense": jnp.ones((T, T), jnp.float32),
         "learned": _learned_sp(T).weights,
         "random": _random_sp(T).weights}[support]
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=T).astype(np.float32))
        y = jnp.asarray(rng.normal(size=T).astype(np.float32))
        hard = float(wdtw(x, y, w))
        soft = float(soft_wdtw(x, y, w, 1e-3))
        assert abs(soft - hard) < 1e-2, (support, seed, soft, hard)


def test_soft_below_hard_and_monotone_in_gamma():
    """softmin <= min propagates: soft value <= hard value, tightening as
    gamma shrinks."""
    T = 24
    sp = _learned_sp(T)
    x, y = _series(2, T)
    hard = float(wdtw(x, y, sp.weights))
    prev_gap = None
    for g in (1.0, 0.3, 0.1, 0.01):
        soft = float(soft_spdtw(x, y, sp, g))
        assert soft <= hard + 1e-5
        gap = hard - soft
        if prev_gap is not None:
            assert gap <= prev_gap + 1e-5
        prev_gap = gap


def test_infeasible_support_is_inf_with_zero_grads():
    T = 8
    w = jnp.zeros((T, T), jnp.float32).at[0, 0].set(1.0)  # corner cut off
    x, y = _series(2, T)
    assert float(soft_wdtw(x, y, w, 0.1)) >= 1e29
    gx = jax.grad(soft_wdtw)(x, y, w, 0.1)
    assert np.allclose(np.asarray(gx), 0.0)


# ------------------------------------------------- VJP vs finite differences
def _fd_check(w, gamma, T, seed, rtol=1e-3):
    """Central finite differences in f64 against the custom VJP."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=T))
        y = jnp.asarray(rng.normal(size=T))
        w = jnp.asarray(np.asarray(w, np.float64))
        gx, gy, gw = jax.grad(soft_wdtw, argnums=(0, 1, 2))(x, y, w, gamma)
        eps = 1e-6

        def val(a, b, ww):
            return float(soft_wdtw(a, b, ww, gamma))

        for i in range(T):
            e = jnp.zeros(T).at[i].set(eps)
            fdx = (val(x + e, y, w) - val(x - e, y, w)) / (2 * eps)
            fdy = (val(x, y + e, w) - val(x, y - e, w)) / (2 * eps)
            np.testing.assert_allclose(float(gx[i]), fdx, rtol=rtol,
                                       atol=1e-6)
            np.testing.assert_allclose(float(gy[i]), fdy, rtol=rtol,
                                       atol=1e-6)
        # weight-grid cotangent: spot-check support cells + one masked cell
        sup = np.argwhere(np.asarray(w) > 0)
        for i, j in sup[:: max(1, len(sup) // 4)]:
            de = jnp.zeros((T, T)).at[i, j].set(eps)
            fdw = (val(x, y, w + de) - val(x, y, w - de)) / (2 * eps)
            np.testing.assert_allclose(float(gw[i, j]), fdw, rtol=rtol,
                                       atol=1e-6)
        off = np.argwhere(np.asarray(w) == 0)
        if len(off):
            i, j = off[0]
            assert float(gw[i, j]) == 0.0


def test_vjp_matches_finite_differences_dense():
    T = 8
    _fd_check(np.ones((T, T)), 0.5, T, seed=5)


def test_vjp_matches_finite_differences_sparse():
    T = 10
    _fd_check(np.asarray(_random_sp(T, density=0.35, seed=2).weights),
              0.5, T, seed=6)


def test_vjp_matches_finite_differences_learned_small_gamma():
    T = 10
    _fd_check(np.asarray(_learned_sp(T).weights), 0.05, T, seed=7)


# ------------------------------------------------------ expected alignment
def test_expected_alignment_structure():
    T = 24
    sp = _learned_sp(T)
    x, y = _series(2, T)
    E = np.asarray(soft_alignment(x, y, sp.weights, 0.1))
    sup = np.asarray(sp.support)
    assert np.abs(E[~sup]).max() == 0.0          # restricted to the support
    assert abs(E[0, 0] - 1.0) < 1e-4             # every path starts there
    assert abs(E[-1, -1] - 1.0) < 1e-4           # ... and ends there
    assert E.min() >= 0.0
    # every admissible path crosses every row at least once
    assert E.sum(axis=1).min() >= 1.0 - 1e-3


def test_expected_alignment_approaches_hard_path():
    """gamma -> 0: E collapses onto the unique optimal path mask."""
    from repro.core.paths import optimal_path_mask
    T = 16
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=T).astype(np.float32))
    y = jnp.asarray(rng.normal(size=T).astype(np.float32))
    w = jnp.ones((T, T), jnp.float32)
    E = np.asarray(soft_alignment(x, y, w, 1e-3))
    mask = np.asarray(optimal_path_mask(x, y))
    np.testing.assert_allclose(E, mask.astype(np.float32), atol=1e-3)


# ------------------------------------------------- block-sparse engine parity
def _soft_oracle(A, B, w, gamma):
    f = jax.vmap(jax.vmap(lambda a, b: soft_wdtw(a, b, w, gamma),
                          in_axes=(None, 0)), in_axes=(0, None))
    return np.asarray(f(A, B))


@pytest.mark.parametrize("maker", [_learned_sp, _random_sp])
def test_gram_soft_scan_parity(maker):
    T = 32
    sp = maker(T)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(5, T), _series(7, T, np.random.default_rng(9))
    for gamma in (0.5, 0.05):
        want = _soft_oracle(A, B, sp.weights, gamma)
        got = np.asarray(gram_soft_spdtw_scan(A, B, bsp, gamma))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_soft_paired_scan_parity_and_ragged():
    T = 24
    sp = _learned_sp(T)
    bsp = block_sparsify(sp, tile=8)
    x, y = _series(5, T), _series(5, T, np.random.default_rng(13))
    want = np.asarray(jax.vmap(
        lambda a, b: soft_wdtw(a, b, sp.weights, 0.2))(x, y))
    got = np.asarray(soft_spdtw_paired_scan(x, y, bsp, 0.2))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_soft_pallas_interpret_parity():
    """Interpret-mode Pallas soft Gram kernel on a tiny shape (the
    compiled run is the tpu-marked test below)."""
    T = 16
    sp = _learned_sp(T)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(3, T), _series(4, T, np.random.default_rng(21))
    want = _soft_oracle(A, B, sp.weights, 0.3)
    got = np.asarray(gram_soft_spdtw_block(A, B, bsp, 0.3, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.tpu
def test_soft_pallas_compiled_on_tpu():
    """Compiled (non-interpret) soft kernel; runs only with -m tpu."""
    if jax.default_backend() != "tpu":
        pytest.skip("requires a real TPU backend")
    T = 256
    sp = _learned_sp(T, theta=2.0)
    bsp = block_sparsify(sp, tile=128)
    A, B = _series(16, T), _series(16, T, np.random.default_rng(3))
    want = np.asarray(gram_soft_spdtw_scan(A, B, bsp, 0.1))
    got = np.asarray(gram_soft_spdtw_block(A, B, bsp, 0.1, interpret=False))
    np.testing.assert_allclose(got, want, rtol=1e-3)


def test_soft_batch_vjp_matches_core():
    """The block-sparse forward + expected-alignment backward of
    ``soft_spdtw_batch`` agrees with differentiating the core recursion."""
    T = 24
    sp = _learned_sp(T)
    x, y = _series(4, T), _series(4, T, np.random.default_rng(17))

    def loss_batch(z):
        zb = jnp.broadcast_to(z, y.shape)
        return jnp.sum(soft_spdtw_batch(zb, y, sp.weights, 0.2))

    def loss_core(z):
        return jnp.sum(jax.vmap(
            lambda b: soft_wdtw(z, b, sp.weights, 0.2))(y))

    g_batch = jax.grad(loss_batch)(x[0])
    g_core = jax.grad(loss_core)(x[0])
    np.testing.assert_allclose(np.asarray(g_batch), np.asarray(g_core),
                               rtol=1e-4, atol=1e-5)
    # jit-compiled path agrees (weights stay concrete under closure)
    g_jit = jax.jit(jax.grad(loss_batch))(x[0])
    np.testing.assert_allclose(np.asarray(g_jit), np.asarray(g_batch),
                               rtol=1e-4, atol=1e-5)


def test_ops_soft_dispatch():
    T = 24
    sp = _learned_sp(T)
    A, B = _series(4, T), _series(6, T, np.random.default_rng(23))
    ref = np.asarray(ops.soft_spdtw_gram(A, B, sp=sp, gamma=0.3, impl="ref"))
    dense = np.asarray(ops.soft_spdtw_gram(A, B, sp=sp, gamma=0.3,
                                           impl="dense"))
    np.testing.assert_allclose(ref, dense, rtol=2e-4, atol=2e-5)
    x, y = A, B[:4]
    pairs = np.asarray(ops.soft_spdtw_pairs(x, y, sp=sp, gamma=0.3))
    want = np.asarray(jax.vmap(
        lambda a, b: soft_wdtw(a, b, sp.weights, 0.3))(x, y))
    np.testing.assert_allclose(pairs, want, rtol=2e-4, atol=2e-5)


def test_soft_dtw_dense_helper():
    T = 12
    x, y = _series(2, T)
    a = float(soft_dtw(x, y, 0.1))
    b = float(soft_wdtw(x, y, jnp.ones((T, T), jnp.float32), 0.1))
    assert a == b
