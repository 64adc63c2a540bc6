"""The served path end to end: ``SearchEngine(..., impl="pallas",
seed_k=2, prefix_frac=0.5)``, which ``impl="auto"`` resolves to on a TPU
and which the benchmark serves, here in interpret mode.

Two shapes, those of the benchmark's configurations: T = 96 at tile 16,
and T = 500 on a grid padded to 512 at tile 64. Each learns one support
(module fixture) and compiles once; the cases vary only the values of the
corpus and the queries, from a seed. Two kinds of traffic: a full batch
of 16, and 5 queries padded to 16 by repeating the last, as the
benchmark's open loop pads. The reference is the dense full-Gram masked
DP (``impl="dense"``) under ``argmin``'s first-index tie rule.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import learn_sparse_paths
from repro.data.synthetic_ucr import make_cbf
from repro.kernels import gram_prefix_bound
from repro.kernels.gram_block import PAIR_BLOCK, prefix_tile_count
from repro.launch.search import SearchEngine

BATCH, N_OPEN = 16, 5
# shape -> series length, tile, support series, support theta, corpus size
SHAPES = {"t96": (96, 16, 16, 8.0, 200), "t500": (500, 64, 8, 5.0, 32)}
SEEDS = (0, 1, 2, 3)


def _with_rounding(D):
    """A DP value plus its float32 rounding, the slack ``test_bounds``
    allows a bound."""
    return D * (1 + 1e-5) + 1e-5


def _pad(Q, n):
    """The first ``n`` queries padded to a full batch with the last."""
    return np.concatenate([Q[:n], np.repeat(Q[n - 1:n], BATCH - n, axis=0)])


class _Served:
    """One learned support per shape, and per (shape, seed) one engine
    that has served the full batch and the padded batch, memoised so each
    shape compiles once however the cases are split or ordered."""

    def __init__(self):
        self._sp, self._runs, self._ties = {}, {}, {}

    def support(self, shape):
        if shape not in self._sp:
            T, _, n_sup, theta, _ = SHAPES[shape]
            X = make_cbf(n_train=n_sup, n_test=1, T=T, seed=100).X_train
            sp = learn_sparse_paths(jnp.asarray(X), theta=theta)
            # a query equal to a corpus series is at distance 0 only if
            # the whole diagonal is in the support
            assert np.diag(np.asarray(sp.support)).all()
            self._sp[shape] = sp
        return self._sp[shape]

    def engine(self, shape, corpus):
        T, tile = SHAPES[shape][:2]
        se = SearchEngine(corpus, kind="spdtw", sp=self.support(shape),
                          impl="pallas", seed_k=2, prefix_frac=0.5)
        assert se.index.bsp.tile == tile
        assert se.index.bsp.T == -(-T // tile) * tile
        return se

    def dense(self, se, Q, C):
        return np.asarray(se.engine.gram(jnp.asarray(Q), jnp.asarray(C),
                                         impl="dense"))

    def run(self, shape, seed):
        if (shape, seed) not in self._runs:
            T, n_c = SHAPES[shape][0], SHAPES[shape][4]
            ds = make_cbf(n_train=n_c, n_test=BATCH, T=T, seed=seed)
            C, Q = ds.X_train, ds.X_test
            se = self.engine(shape, C)
            full = se.search(Q)
            padded = se.search(_pad(Q, N_OPEN))
            self._runs[shape, seed] = {
                "engine": se, "Q": Q, "C": C, "full": full,
                "padded": padded, "n_batches": 2, "stats": se.stats(),
                "dense": self.dense(se, Q, C)}
        return self._runs[shape, seed]

    def ties(self, shape):
        """A corpus with an exact duplicate (rows 3 and 11) and a batch
        holding a noisy copy of that series (row 0) and a query equal to
        corpus series 7 (row 1)."""
        if shape not in self._ties:
            T, n_c = SHAPES[shape][0], SHAPES[shape][4]
            ds = make_cbf(n_train=n_c, n_test=BATCH, T=T, seed=7)
            C, Q = ds.X_train.copy(), ds.X_test.copy()
            C[11] = C[3]
            rng = np.random.default_rng(7)
            Q[0] = C[3] + 0.05 * rng.normal(size=T).astype(np.float32)
            Q[1] = C[7]
            se = self.engine(shape, C)
            nn, dist = se.search(Q)
            self._ties[shape] = {"nn": nn, "dist": dist,
                                 "dense": self.dense(se, Q[:2], C)}
        return self._ties[shape]


@pytest.fixture(scope="module")
def served():
    return _Served()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("traffic", ["full", "padded"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_served_answers_equal_dense(served, shape, traffic, seed):
    """Served ids equal the dense argmin and distances match it to float32
    rounding; padding rows answer as the last real query does."""
    r = served.run(shape, seed)
    nn, dist = (np.asarray(a) for a in r[traffic])
    n = BATCH if traffic == "full" else N_OPEN
    D = r["dense"][:n]
    assert np.isfinite(D).all()
    assert np.array_equal(nn[:n], D.argmin(axis=1))
    np.testing.assert_allclose(dist[:n], D.min(axis=1), rtol=1e-5)
    if traffic == "padded":
        assert (nn[n:] == nn[n - 1]).all()
        assert (dist[n:] == dist[n - 1]).all()


def _bound(r, which):
    se, Q, C = r["engine"], jnp.asarray(r["Q"]), jnp.asarray(r["C"])
    if which in ("lb1", "lb2"):
        return np.asarray(se.index.cascade_bounds(Q)[which == "lb2"])
    T = Q.shape[1]
    n_prefix = prefix_tile_count(se.index.bsp, se.prefix_frac, T)
    assert 0 < n_prefix < se.index.bsp.n_active
    return np.asarray(gram_prefix_bound(Q, C, se.index.bsp, n_prefix,
                                        T_orig=T))


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("which", ["lb1", "lb2", "prefix"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_served_bounds_admissible(served, shape, which, seed):
    """Each cascade bound, on the plan the engine built, lies at or below
    the dense distance of every (query, corpus) pair."""
    r = served.run(shape, seed)
    lb, D = _bound(r, which), r["dense"]
    assert lb.shape == D.shape
    assert (lb <= _with_rounding(D)).all()
    assert lb.max() > 0                    # the bound is not vacuous


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_served_stats_consistent(served, shape, seed):
    """``stats()`` after the batches: pair totals, the overall prune
    share, stage shares in [0, 1], the kernel's counters within its pair
    blocks, and the plan on every survivor DP span."""
    r = served.run(shape, seed)
    st, bsp = r["stats"], r["engine"].index.bsp
    assert st["queries"] == r["n_batches"] * BATCH
    assert st["pairs_total"] == st["queries"] * len(r["C"])
    assert st["pre_dp_prune_overall"] == pytest.approx(
        1 - st["pairs_dp"] / st["pairs_total"], abs=1e-12)
    for k in ("stage1_prune", "stage2_prune", "stage3_prune"):
        assert 0.0 <= st[k] <= 1.0, k
    block_pairs = PAIR_BLOCK[0] * PAIR_BLOCK[1]
    c = st["trace"]["counters"]
    assert 0 <= c["survivor_dp.alive_pair_sweeps"] \
        <= c["survivor_dp.tile_sweeps"] * block_pairs
    spans = [s for s in st["trace"]["spans"]
             if s["name"] == "cascade.survivor_dp"]
    assert len(spans) == r["n_batches"]
    want = {"tile": bsp.tile, "plan_tiles": bsp.n_active,
            "block_pairs": 1024}
    assert all(s["attrs"] == want for s in spans)


@pytest.mark.parametrize("case", ["duplicate", "self"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_served_ties_and_self_match(served, shape, case):
    """An exact duplicate in the corpus ties, and the lower index wins; a
    query equal to a corpus series is at distance 0 and gets its id."""
    r = served.ties(shape)
    if case == "duplicate":
        D = r["dense"][0]
        assert D[3] == D[11] == D.min()
        assert int(r["nn"][0]) == 3
        np.testing.assert_allclose(r["dist"][0], D[3], rtol=1e-5)
    else:
        assert int(r["nn"][1]) == 7
        assert float(r["dist"][1]) == 0.0
        assert r["dense"][1].min() == 0.0
