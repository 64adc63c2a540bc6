"""Centroid workload (DESIGN.md §10): the barycenter's Adam, barycenter
fixed point, class centroids, k-means loop, centroid-seeded cascade
exactness, centroid serving mode, and the sharded fitting job."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.classify import centroid_error_series, knn_error_series
from repro.cluster import (CentroidModel, fit_class_centroids,
                           nearest_centroid, soft_barycenter, soft_kmeans)
from repro.cluster.barycenter import adam_init, adam_update
from repro.core import learn_sparse_paths, make_measure
from repro.core.dtw import wdtw
from repro.data import load
from repro.kernels import knn_cascade

T = 32


@pytest.fixture(scope="module")
def cbf():
    ds = load("CBF", n_train=48, n_test=24, T=T)
    Xtr = jnp.asarray(ds.X_train)
    sp = learn_sparse_paths(Xtr[:16], theta=4.0)
    return ds, Xtr, sp


@pytest.fixture(scope="module")
def fitted(cbf):
    """One fitted class-centroid model shared by every test that only
    needs *a* model (fitting dominates the suite's wall-clock)."""
    ds, Xtr, sp = cbf
    return fit_class_centroids(Xtr, ds.y_train, sp.weights, gamma=0.05,
                               steps=25)


# ------------------------------------------------------------- barycenter
def test_barycenter_identical_series_fixed_point(cbf):
    """The barycenter of B copies of one series converges back to (a
    near-zero hard-SP-DTW neighbourhood of) that series from a perturbed
    init, and the loss history decreases."""
    ds, Xtr, sp = cbf
    rng = np.random.default_rng(0)
    x = Xtr[0]
    Xid = jnp.tile(x[None], (6, 1))
    init = x + 0.3 * jnp.asarray(rng.normal(size=T).astype(np.float32))
    z, losses = soft_barycenter(Xid, sp.weights, gamma=0.05, init=init,
                                steps=80, lr=0.05)
    d_init = float(wdtw(init, x, sp.weights))
    d_fit = float(wdtw(z, x, sp.weights))
    assert d_fit < 0.05 * d_init          # collapsed onto the series
    assert float(losses[-1]) < float(losses[0])


def test_barycenter_zero_sample_weights_frozen(cbf):
    """All-zero member weights (a padding centroid in the sharded job)
    must leave the init untouched — zero loss, zero gradient."""
    ds, Xtr, sp = cbf
    init = Xtr[1]
    z, losses = soft_barycenter(Xtr[:5], sp.weights, gamma=0.1, init=init,
                                steps=10, sample_weights=jnp.zeros(5))
    np.testing.assert_allclose(np.asarray(z), np.asarray(init), atol=1e-6)
    assert float(losses[-1]) == 0.0



# ------------------------------------------------------------------ Adam
def test_adamw_minimizes_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adam_init(params)
    for _ in range(200):
        g = jax.tree.map(lambda p: 2 * p, params)   # d/dw ||w||^2
        params, state = adam_update(g, state, params, 0.1)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def _textbook_adam(p, grad, lr, steps, b1=0.9, b2=0.95, eps=1e-8):
    """Adam with bias correction, in float64 NumPy, one leaf."""
    m = np.zeros_like(p)
    v = np.zeros_like(p)
    for t in range(1, steps + 1):
        g = grad(p, t)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        p = p - lr * (m / (1 - b1 ** t)) / (np.sqrt(v / (1 - b2 ** t)) + eps)
    return p


@pytest.mark.parametrize("shapes", [{"s": ()}, {"v": (7,)},
                                    {"m": (3, 4)}, {"a": (5,), "b": (2, 3)}],
                         ids=["scalar", "vector", "matrix", "pytree"])
def test_adam_matches_textbook_update(shapes):
    """Five steps of the barycenter's Adam on a noisy quadratic equal the
    textbook update leaf by leaf, to float32 rounding."""
    rng = np.random.default_rng(0)
    p0 = {k: rng.normal(size=s) for k, s in shapes.items()}
    target = {k: rng.normal(size=s) for k, s in shapes.items()}
    noise = {k: rng.normal(size=(6,) + s) for k, s in shapes.items()}

    def grad(k):
        return lambda p, t: 2 * (p - target[k]) + noise[k][t]

    lr, steps = 0.05, 5
    params = {k: jnp.asarray(v, jnp.float32) for k, v in p0.items()}
    state = adam_init(params)
    for t in range(1, steps + 1):
        g = {k: jnp.asarray(grad(k)(np.asarray(params[k], np.float64), t),
                            jnp.float32) for k in params}
        params, state = adam_update(g, state, params, lr)
    assert int(state.step) == steps
    for k in shapes:
        assert params[k].dtype == jnp.float32 and params[k].shape == shapes[k]
        want = _textbook_adam(p0[k], grad(k), lr, steps)
        np.testing.assert_allclose(np.asarray(params[k]), want, rtol=1e-5,
                                   atol=1e-6)

# -------------------------------------------------------- class centroids
def test_fit_class_centroids_model(cbf, fitted):
    ds, Xtr, sp = cbf
    model = fitted
    assert model.k == ds.n_classes
    assert sorted(model.labels.tolist()) == sorted(
        np.unique(ds.y_train).tolist())
    # medoids index the fitting corpus and carry their centroid's class
    assert model.medoids.shape == (model.k,)
    for c in range(model.k):
        mi = int(model.medoids[c])
        assert 0 <= mi < len(ds.y_train)
        assert int(ds.y_train[mi]) == int(model.labels[c])
    # classification within striking distance of 1-NN on the tiny split
    err_c = centroid_error_series(ds.X_test, ds.y_test, model)
    err_1nn = knn_error_series(ds.X_test, Xtr, ds.y_train, ds.y_test,
                               kind="spdtw", sp=sp)
    assert err_c <= err_1nn + 0.15


def test_fit_class_centroids_multi_per_class(cbf):
    ds, Xtr, sp = cbf
    n = 24
    model = fit_class_centroids(Xtr[:n], ds.y_train[:n], sp.weights,
                                gamma=0.05, n_per_class=2, steps=6,
                                kmeans_iters=1)
    assert model.k == 2 * len(np.unique(ds.y_train[:n]))
    counts = np.bincount(model.labels)
    assert (counts[np.unique(ds.y_train[:n])] == 2).all()


# ------------------------------------------------------------- k-means
def test_soft_kmeans_inertia_and_shapes(cbf):
    ds, Xtr, sp = cbf
    model, info = soft_kmeans(Xtr[:20], 3, sp.weights, gamma=0.05,
                              iters=2, steps=8)
    assert model.centroids.shape == (3, T)
    assert info["assign"].shape == (20,)
    assert info["assign"].max() < 3
    # refitting centroids on their members should not blow up inertia
    assert info["inertia"][-1] <= info["inertia"][0] * 1.5
    assert np.isfinite(info["inertia"]).all()


# ------------------------------------- centroid-seeded cascade exactness
def test_centroid_seeded_cascade_exact(cbf, fitted):
    """The seeded cascade must return bit-identical neighbours to the
    plain cascade and the dense full-Gram argmin (the exactness flag the
    benchmark artifact gates on)."""
    ds, Xtr, sp = cbf
    m = make_measure("spdtw", T, sp=sp)
    index = m.build_index(Xtr)
    model = fitted
    Q = jnp.asarray(ds.X_test)
    nn_plain, d_plain = knn_cascade(Q, index)
    nn_seed, d_seed, st = knn_cascade(Q, index, centroid_model=model,
                                      return_stats=True)
    assert np.array_equal(np.asarray(nn_plain), np.asarray(nn_seed))
    np.testing.assert_allclose(np.asarray(d_plain), np.asarray(d_seed),
                               rtol=1e-6)
    nn_full = np.argmin(np.asarray(m.cross(Q, Xtr)), axis=1)
    assert np.array_equal(np.asarray(nn_seed), nn_full)
    assert int(st["n_centroids"]) == model.k


def test_seeded_cascade_without_medoids_falls_back(cbf):
    """A model with no medoid handles cannot seed; the cascade must just
    run unseeded rather than fail."""
    ds, Xtr, sp = cbf
    m = make_measure("spdtw", T, sp=sp)
    index = m.build_index(Xtr)
    bare = CentroidModel(centroids=Xtr[:3], weights=sp.weights, gamma=0.1)
    Q = jnp.asarray(ds.X_test[:8])
    nn0, _ = knn_cascade(Q, index)
    nn1, _ = knn_cascade(Q, index, centroid_model=bare)
    assert np.array_equal(np.asarray(nn0), np.asarray(nn1))


# ------------------------------------------------------- serving layer
def test_search_engine_centroid_mode(cbf, fitted):
    from repro.launch.search import SearchEngine, stream_search
    ds, Xtr, sp = cbf
    model = fitted
    engine = SearchEngine(Xtr, ds.y_train, sp=sp, centroid_model=model,
                          mode="centroid")
    Q = jnp.asarray(ds.X_test[:10])
    idx, dist = engine.search(Q)
    # brute force over the centroid set
    Dc = np.asarray(model.distances(Q))
    assert np.array_equal(idx, Dc.argmin(axis=1))
    # label mapping rides through the streaming loop untouched
    results = stream_search(engine, list(np.asarray(ds.X_test[:6])),
                            batch=4)
    for r in results:
        assert r.label == int(model.labels[r.nn])
    st = engine.stats()
    assert st["pairs_dp"] < st["pairs_total"]  # k << N per query


def test_search_engine_centroid_mode_unsupervised(cbf):
    """An unsupervised model (labels=None) serves centroid ids with
    label=None instead of crashing the streaming loop, and stats() omits
    the cascade stage keys (no bounds ran)."""
    from repro.launch.search import SearchEngine, stream_search
    ds, Xtr, sp = cbf
    model, _ = soft_kmeans(Xtr[:16], 3, sp.weights, gamma=0.05,
                           iters=1, steps=5)
    assert model.labels is None
    engine = SearchEngine(Xtr, sp=sp, centroid_model=model,
                          mode="centroid")
    results = stream_search(engine, list(np.asarray(ds.X_test[:4])),
                            batch=2)
    assert all(r.label is None for r in results)
    st = engine.stats()
    assert "stage1_prune" not in st and st["queries"] == 4


def test_soft_pairs_bsp_only_keeps_plan(cbf):
    """A bsp-only soft_spdtw_pairs call runs on the caller's own tile
    plan (no densify/re-sparsify round trip) and matches the core."""
    from repro.core import block_sparsify
    from repro.core.softdtw import soft_wdtw
    from repro.kernels import ops
    ds, Xtr, sp = cbf
    bsp = block_sparsify(sp, tile=8)          # non-default tile
    x, y = Xtr[:4], Xtr[4:8]
    got = np.asarray(ops.soft_spdtw_pairs(x, y, bsp=bsp, gamma=0.2))
    want = np.asarray(jax.vmap(
        lambda a, b: soft_wdtw(a, b, sp.weights, 0.2))(x, y))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_search_run_centroid_mode_end_to_end():
    from repro.launch.search import run
    out = run(dataset="CBF", workload="classify", n_queries=8, batch=4,
              n_train=24, n_sp_train=12, theta=4.0, centroids=1,
              fit_steps=8, T=48, check=True)
    assert out["mode"] == "centroid"
    assert out["exact_match"]
    assert 0.0 <= out["accuracy"] <= 1.0
    assert out["n_centroids"] == 3


# ------------------------------------------------------- sharded fitting
def test_cluster_job_host_mesh():
    from repro.launch.cluster import run
    Z, loss = run(k=4, n=16, t=16, steps=8)
    assert Z.shape[1] == 16 and Z.shape[0] >= 4
    assert np.isfinite(Z).all() and np.isfinite(loss).all()


def test_cluster_job_matches_unsharded():
    """The shard_map job fits the same centroids as calling the
    barycenter loop directly (single-device mesh: pure refactor)."""
    from repro.launch import cluster as lc
    from repro.launch.mesh import make_host_mesh
    from repro.core.dtw import band_mask
    t, n, k = 16, 12, 2
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(n, t)).astype(np.float32))
    w = np.asarray(band_mask(t, t, 2), np.float32)
    A = jnp.asarray((np.arange(n) % k == np.arange(k)[:, None])
                    .astype(np.float32))
    Z0 = jnp.asarray(rng.normal(size=(k, t)).astype(np.float32))
    mesh = make_host_mesh(1, 1)
    with jax.set_mesh(mesh):
        job = lc.cluster_job(mesh, w, 0.1, steps=6)
        Zs, _ = job(Z0, X, A)
    Zd = []
    for c in range(k):
        z, _ = soft_barycenter(X, w, 0.1, init=Z0[c], steps=6,
                               sample_weights=A[c])
        Zd.append(z)
    np.testing.assert_allclose(np.asarray(Zs), np.asarray(jnp.stack(Zd)),
                               rtol=1e-5, atol=1e-6)
