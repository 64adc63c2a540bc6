"""The Gram kernel's pair-block layout against the scan engine.

The kernel holds a (ba, bb) pair block as one value, query ia on sublane
ia and corpus series ib on lane ib, and a tile row as S such values. A
non-square block (8 x 16) catches swapped sublane and lane axes; tile 16
and tile 64 on grids padded past T put the result cell inside a tile.
Every case is bit-identical to ``gram_spdtw_scan`` (interpret mode) and
counts its work by the rules of ``test_prune.test_gram_kernel_counts_sweeps``.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import block_sparsify
from repro.kernels import gram_spdtw_block, gram_spdtw_scan

BA, BB = 8, 16
NA, NB = 12, 28          # ragged: padded to 2 x 2 pair blocks


def _banded_plan(T, tile, seed):
    """Weights in [0.5, 2) on |i - j| <= T / 6, zero elsewhere."""
    rng = np.random.default_rng(seed)
    i, j = np.meshgrid(np.arange(T), np.arange(T), indexing="ij")
    w = np.where(np.abs(i - j) <= T // 6, rng.uniform(0.5, 2.0, (T, T)), 0.0)
    return block_sparsify(w.astype(np.float32), tile=tile)


def _series(n, T, d, seed):
    rng = np.random.default_rng(seed)
    shape = (n, T) if d == 1 else (n, T, d)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


@pytest.mark.parametrize("case", ["exact", "thresholds_alive0", "all_dead"])
@pytest.mark.parametrize("d", [1, 2])
@pytest.mark.parametrize("T,tile", [(45, 16), (120, 64)],
                         ids=["tile16", "tile64"])
def test_pair_block_layout_matches_scan(T, tile, d, case):
    bsp = _banded_plan(T, tile, seed=T)
    assert bsp.T > T                     # the result cell is inside a tile
    A, B = _series(NA, T, d, seed=1), _series(NB, T, d, seed=2)
    base = np.asarray(gram_spdtw_scan(A, B, bsp, T_orig=T))
    n_blocks = 2 * 2
    kw = {}
    if case != "exact":
        rng = np.random.default_rng(3)
        kw["thresholds"] = jnp.asarray(np.partition(base, 3, axis=1)[:, 3])
        kw["alive0"] = jnp.asarray(rng.random((NA, NB)) < 0.6) \
            if case == "thresholds_alive0" else jnp.zeros((NA, NB), bool)
    want, tiles = gram_spdtw_scan(A, B, bsp, T_orig=T, return_tiles=True,
                                  **kw)
    got, (sw, lv) = gram_spdtw_block(A, B, bsp, T_orig=T, ba=BA, bb=BB,
                                     interpret=True, return_counts=True,
                                     **kw)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    sw, lv = int(sw), int(lv)
    if case == "exact":
        # every pair of the padded blocks is alive in every tile
        assert np.array_equal(np.asarray(got), base)
        assert (sw, lv) == (n_blocks * bsp.n_active,
                            n_blocks * BA * BB * bsp.n_active)
    elif case == "all_dead":
        assert (np.asarray(got) >= 1e29).all()
        assert (sw, lv) == (0, 0)
    else:
        # live pairs are those the scan engine counts; a block sweeps a
        # tile iff one of its pairs is live there
        assert 0 < lv == int(np.asarray(tiles).sum())
        assert -(-lv // (BA * BB)) <= sw <= n_blocks * bsp.n_active
        assert lv <= int(np.asarray(kw["alive0"]).sum()) * bsp.n_active
