"""Block-sparse reverse-sweep soft-SP-DTW backward (DESIGN.md §11).

Parity of the reverse active-tile sweep against the dense
expected-alignment oracle (``core.softdtw._expected_alignment``): E
matrices to 1e-6 in f64 (both engines are exact re-orderings of the same
recursion; in f32 each carries ~1e-5 roundoff of its own), gradients of
the rewired custom VJPs against the dense backward, edge cases
(single-tile plans, fully dense support, ragged corpus lengths,
infeasible supports), gamma -> 0 collapse onto the hard path, and
interpret-mode parity of the fused Pallas Gram-backward kernel. The
compiled Pallas kernels ride behind the ``tpu`` marker.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import SparsePaths, block_sparsify, learn_sparse_paths
from repro.core.softdtw import soft_alignment, soft_wdtw
from repro.kernels import ops
from repro.kernels.soft_block import (
    gram_soft_bwd_pallas, gram_soft_bwd_scan, gram_soft_fwd_stash,
    gram_soft_fwd_stash_pallas, soft_alignment_pairs, soft_spdtw_batch,
    soft_spdtw_bwd_block, soft_spdtw_fwd_stash, soft_spdtw_gram_batch,
    soft_spdtw_paired_scan)

RNG = np.random.default_rng(29)


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


def _dense_E(x, y, w, gamma):
    return np.stack([np.asarray(soft_alignment(x[i], y[i], w, gamma))
                     for i in range(x.shape[0])])


# --------------------------------------------------- E-matrix parity (f64)
@pytest.mark.parametrize("maker,tile", [(_learned_sp, 8), (_random_sp, 8),
                                        (_random_sp, 16)])
def test_e_matrix_parity_f64(maker, tile):
    """Reverse-sweep E matches the dense backward to <= 1e-6 (f64: the
    two are exact re-orderings of the same recursion)."""
    T = 32
    sp = maker(T)
    bsp = block_sparsify(sp, tile=tile)
    rng = np.random.default_rng(5)
    xs, ys = rng.normal(size=(4, T)), rng.normal(size=(4, T))
    with jax.enable_x64(True):
        x, y = jnp.asarray(xs), jnp.asarray(ys)
        w = jnp.asarray(np.asarray(sp.weights, np.float64))
        for gamma in (0.5, 0.1):
            Eb = np.asarray(soft_alignment_pairs(x, y, bsp, gamma,
                                                 dtype=jnp.float64))
            Ed = _dense_E(x, y, w, gamma)
            assert np.abs(Eb - Ed).max() <= 1e-6, (gamma, tile)
            # restricted to the support by construction
            assert np.abs(Eb[:, ~np.asarray(sp.support)]).max() == 0.0


def test_e_matrix_parity_f32():
    """The f32 production path stays within f32 roundoff of f64 truth."""
    T = 32
    sp = _random_sp(T, density=0.35, seed=11)
    bsp = block_sparsify(sp, tile=8)
    rng = np.random.default_rng(7)
    xs, ys = rng.normal(size=(3, T)), rng.normal(size=(3, T))
    with jax.enable_x64(True):
        Ed = _dense_E(jnp.asarray(xs), jnp.asarray(ys),
                      jnp.asarray(np.asarray(sp.weights, np.float64)), 0.3)
    Eb = np.asarray(soft_alignment_pairs(
        jnp.asarray(xs.astype(np.float32)),
        jnp.asarray(ys.astype(np.float32)), bsp, 0.3))
    assert np.abs(Eb - Ed).max() <= 1e-3
    assert Eb.min() >= 0.0
    np.testing.assert_allclose(Eb[:, 0, 0], 1.0, atol=1e-4)
    np.testing.assert_allclose(Eb[:, -1, -1], 1.0, atol=1e-4)


# ------------------------------------------------- rewired VJPs vs dense
def test_batch_vjp_matches_dense_backward():
    """soft_spdtw_batch grads (block-sparse reverse sweep) == grads of
    the vmapped core recursion (dense expected-alignment backward)."""
    T = 32
    sp = _learned_sp(T)
    x, y = _series(4, T), _series(4, T, np.random.default_rng(13))
    w = sp.weights
    gbar = jnp.arange(1.0, 5.0)

    def loss_blk(a, b, ww):
        return jnp.sum(gbar * soft_spdtw_batch(a, b, ww, 0.2))

    def loss_dense(a, b, ww):
        d = jax.vmap(lambda u, v: soft_wdtw(u, v, ww, 0.2))(a, b)
        return jnp.sum(gbar * d)

    g1 = jax.grad(loss_blk, argnums=(0, 1, 2))(x, y, w)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(x, y, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
    # jit-compiled path agrees (weights stay concrete under closure)
    g_jit = jax.jit(jax.grad(lambda a: loss_blk(a, y, w)))(x)
    np.testing.assert_allclose(np.asarray(g_jit), np.asarray(g1[0]),
                               rtol=1e-4, atol=1e-5)


def test_gram_vjp_matches_dense_backward():
    T = 24
    sp = _learned_sp(T)
    A, B = _series(3, T), _series(5, T, np.random.default_rng(17))
    w = sp.weights
    gbar = jnp.asarray(RNG.uniform(0.5, 1.5, (3, 5)).astype(np.float32))

    def loss_blk(a, b, ww):
        return jnp.sum(gbar * soft_spdtw_gram_batch(a, b, ww, 0.3))

    def loss_dense(a, b, ww):
        f = jax.vmap(jax.vmap(lambda u, v: soft_wdtw(u, v, ww, 0.3),
                              in_axes=(None, 0)), in_axes=(0, None))
        return jnp.sum(gbar * f(a, b))

    g1 = jax.grad(loss_blk, argnums=(0, 1, 2))(A, B, w)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(A, B, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
    # forward values unchanged by the VJP wrapper
    np.testing.assert_allclose(
        np.asarray(soft_spdtw_gram_batch(A, B, w, 0.3)),
        np.asarray(ops.soft_spdtw_gram(A, B, sp=sp, gamma=0.3, impl="ref")),
        rtol=1e-5, atol=1e-6)


def test_ops_gram_auto_is_differentiable():
    """ops.soft_spdtw_gram on the default path differentiates through
    the reverse sweep (serving + training share one entry)."""
    T = 16
    sp = _learned_sp(T)
    A, B = _series(2, T), _series(3, T, np.random.default_rng(19))

    def loss(a):
        return jnp.sum(ops.soft_spdtw_gram(a, B, sp=sp, gamma=0.3))

    def loss_dense(a):
        return jnp.sum(ops.soft_spdtw_gram(a, B, sp=sp, gamma=0.3,
                                           impl="dense"))

    np.testing.assert_allclose(np.asarray(jax.grad(loss)(A)),
                               np.asarray(jax.grad(loss_dense)(A)),
                               rtol=1e-3, atol=1e-4)


# --------------------------------------------------------------- edge cases
def test_single_tile_plan():
    """T <= tile: the whole grid is one tile; the reverse walk is a
    single step with every halo inactive."""
    T = 8
    sp = _random_sp(T, density=0.5, seed=2)
    bsp = block_sparsify(sp, tile=8)
    assert bsp.plan().shape[0] == 1
    x, y = _series(3, T), _series(3, T, np.random.default_rng(23))
    Eb = np.asarray(soft_alignment_pairs(x, y, bsp, 0.3))
    Ed = _dense_E(x, y, sp.weights, 0.3)
    np.testing.assert_allclose(Eb, Ed, atol=5e-5)


def test_fully_dense_support():
    T = 24
    w = jnp.ones((T, T), jnp.float32)
    bsp = block_sparsify(np.ones((T, T), np.float32), tile=8)
    assert bsp.tile_sparsity == 0.0
    x, y = _series(3, T), _series(3, T, np.random.default_rng(31))
    Eb = np.asarray(soft_alignment_pairs(x, y, bsp, 0.2))
    Ed = _dense_E(x, y, w, 0.2)
    np.testing.assert_allclose(Eb, Ed, atol=5e-5)


def test_ragged_corpus_lengths():
    """T_orig < bsp.T: series shorter than the (padded) plan grid — the
    reverse walk starts at the result tile of the query length and the
    padded region carries no alignment mass."""
    T_grid, T = 24, 20         # tile 8 => padded grid 24, ragged length 20
    sp = _learned_sp(T)
    bsp = block_sparsify(sp, tile=8)
    assert bsp.T == T_grid
    x, y = _series(3, T), _series(3, T, np.random.default_rng(37))
    # forward parity on the ragged length
    np.testing.assert_allclose(
        np.asarray(soft_spdtw_paired_scan(x, y, bsp, 0.3, T_orig=T)),
        np.asarray(jax.vmap(
            lambda a, b: soft_wdtw(a, b, sp.weights, 0.3))(x, y)),
        rtol=2e-4, atol=2e-5)
    Eb = np.asarray(soft_alignment_pairs(x, y, bsp, 0.3, T_orig=T))
    assert Eb.shape == (3, T, T)
    Ed = _dense_E(x, y, sp.weights, 0.3)
    np.testing.assert_allclose(Eb, Ed, atol=5e-5)
    # grads through the batch VJP on the ragged length
    g1 = jax.grad(lambda a: jnp.sum(
        soft_spdtw_batch(a, y, sp.weights, 0.3)))(x)
    g2 = jax.grad(lambda a: jnp.sum(jax.vmap(
        lambda u, v: soft_wdtw(u, v, sp.weights, 0.3))(a, y)))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-4)


def test_infeasible_support_zero_grads():
    """Corner tile inactive => +INF values and identically-zero grads
    through the block-sparse VJP (mirrors the dense feasibility mask)."""
    T = 16
    w = np.zeros((T, T), np.float32)
    w[:8, :8] = 1.0            # corner tile never active
    x, y = _series(2, T), _series(2, T, np.random.default_rng(41))
    val, stash = soft_spdtw_fwd_stash(x, y, block_sparsify(w, tile=8), 0.3)
    assert stash is None and np.all(np.asarray(val) >= 1e29)
    gx = jax.grad(lambda a: jnp.sum(
        soft_spdtw_batch(a, y, jnp.asarray(w), 0.3)))(x)
    assert np.allclose(np.asarray(gx), 0.0)


def test_gamma_to_zero_matches_hard_path():
    """gamma -> 0: the sparse E collapses onto the hard-path indicator
    on the support (unique-optimum dense case: the DTW path mask)."""
    from repro.core.paths import optimal_path_mask
    T = 16
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, T)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(1, T)).astype(np.float32))
    bsp = block_sparsify(np.ones((T, T), np.float32), tile=8)
    E = np.asarray(soft_alignment_pairs(x, y, bsp, 1e-3))[0]
    mask = np.asarray(optimal_path_mask(x[0], y[0]))
    np.testing.assert_allclose(E, mask.astype(np.float32), atol=1e-3)
    # sparse support at tiny gamma: parity with the dense soft oracle
    sp = _learned_sp(T)
    bsp2 = block_sparsify(sp, tile=8)
    E2 = np.asarray(soft_alignment_pairs(x, y, bsp2, 1e-3))[0]
    Ed = np.asarray(soft_alignment(x[0], y[0], sp.weights, 1e-3))
    np.testing.assert_allclose(E2, Ed, atol=1e-3)
    assert np.abs(E2[~np.asarray(sp.support)]).max() == 0.0


# ----------------------------------------------- Pallas backward (interpret)
def test_pallas_gram_backward_interpret_parity():
    """Interpret-mode fused Pallas Gram-backward vs the scan reverse
    engine on a tiny shape (the compiled run is the tpu-marked test)."""
    T = 16
    sp = _learned_sp(T)
    bsp = block_sparsify(sp, tile=8)
    A, B = _series(3, T), _series(5, T, np.random.default_rng(43))
    gbar = jnp.asarray(RNG.uniform(0.5, 1.5, (3, 5)).astype(np.float32))
    val_s, stash_s = gram_soft_fwd_stash(A, B, bsp, 0.3)
    val_p, stash_p = gram_soft_fwd_stash_pallas(A, B, bsp, 0.3, ba=2, bb=4,
                                                interpret=True)
    np.testing.assert_allclose(np.asarray(val_p), np.asarray(val_s),
                               rtol=1e-5, atol=1e-6)
    gb = gbar * (val_s < 1e29)
    g_s = gram_soft_bwd_scan(A, B, bsp, 0.3, stash_s, gb)
    g_p = gram_soft_bwd_pallas(A, B, bsp, 0.3, stash_p, gb, ba=2, bb=4,
                               interpret=True)
    for a, b in zip(g_p, g_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.tpu
def test_pallas_gram_backward_compiled_on_tpu():
    """Compiled (non-interpret) forward-stash + Gram-backward kernels;
    runs only with -m tpu on real hardware."""
    if jax.default_backend() != "tpu":
        pytest.skip("requires a real TPU backend")
    T = 256
    sp = _learned_sp(T, theta=2.0)
    bsp = block_sparsify(sp, tile=128)
    A, B = _series(16, T), _series(16, T, np.random.default_rng(3))
    gbar = jnp.ones((16, 16), jnp.float32)
    val_s, stash_s = gram_soft_fwd_stash(A, B, bsp, 0.1)
    val_p, stash_p = gram_soft_fwd_stash_pallas(A, B, bsp, 0.1,
                                                interpret=False)
    np.testing.assert_allclose(np.asarray(val_p), np.asarray(val_s),
                               rtol=1e-3)
    gb = gbar * (val_s < 1e29)
    g_s = gram_soft_bwd_scan(A, B, bsp, 0.1, stash_s, gb)
    g_p = gram_soft_bwd_pallas(A, B, bsp, 0.1, stash_p, gb,
                               interpret=False)
    for a, b in zip(g_p, g_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-3)


# ------------------------------------------------------- barycenter descent
def test_barycenter_still_descends():
    """End-to-end: the rewired backward drives the barycenter fit (loss
    decreases and the fixed point matches the dense-backward fit)."""
    from repro.cluster import soft_barycenter
    T = 24
    sp = _learned_sp(T)
    rng = np.random.default_rng(47)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    X = jnp.asarray((base[None] + 0.2 * rng.normal(size=(6, T))
                     ).astype(np.float32))
    z, losses = soft_barycenter(X, sp.weights, gamma=0.1, steps=40, lr=0.1)
    assert float(losses[-1]) < float(losses[0])
    assert np.isfinite(np.asarray(z)).all()
