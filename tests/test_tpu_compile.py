"""Compile the main-path Pallas kernels for a TPU v5e that is described, not
attached: what the chip's compiler (Mosaic) refuses fails here, at no chip
time. Nothing runs, so these tests say nothing about values or speed.

Shapes are the serving cells': 16 queries against an 8,926-series corpus
(padded to the 128-wide pair block) at T = 96 (tile 16) and T = 500
(tile 64). The topology is described inside a fixture, never at import:
only one process may load the TPU compiler library, and every test worker
imports this file. Keep every chip compile in this one file.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.occupancy import block_sparsify, default_tile
from repro.kernels.gram_block import _gram_spdtw_call
from repro.kernels.spdtw_block import _spdtw_block_call, result_tile_step

N_QUERY, N_CORPUS_PADDED, N_PAIRS = 16, 8960, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without a chip: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _plan(T):
    """A banded support (|i - j| <= T/6) and its tile plan at the default
    tile for T."""
    i, j = np.meshgrid(np.arange(T), np.arange(T), indexing="ij")
    bsp = block_sparsify((np.abs(i - j) <= T // 6).astype(np.float32),
                         tile=default_tile(T))
    meta = bsp.plan()
    return bsp, meta, result_tile_step(meta, bsp.tile, T)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("T,d,n_corpus", [(96, 1, N_CORPUS_PADDED),
                                          (500, 1, N_CORPUS_PADDED),
                                          (96, 2, 256)],
                         ids=["T96", "T500", "T96_d2"])
@pytest.mark.parametrize("prune", [False, True],
                         ids=["exact", "thresholds_alive0"])
def test_gram_kernel_compiles_for_v5e(one_chip, no_persistent_cache, T, d,
                                      n_corpus, prune):
    """The fused Gram kernel, queries tile-stacked on sublanes and the
    corpus on lanes; d = 2 covers the pallas record's MULTIVARIATE
    claim."""
    bsp, meta, g_out = _plan(T)
    S, Ti = bsp.tile, bsp.T // bsp.tile
    compiled = _gram_spdtw_call.lower(
        _sds(one_chip, meta.shape, jnp.int32),
        _sds(one_chip, (Ti, N_QUERY, d * S)),
        _sds(one_chip, (Ti, d * S, n_corpus)),
        _sds(one_chip, bsp.blocks.shape), _sds(one_chip, (N_QUERY, 1)),
        _sds(one_chip, (N_QUERY, n_corpus)),
        S=S, n_active=meta.shape[0], T_orig=T, g_out=g_out, ba=8, bb=128,
        d=d, prune=prune, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [96, 500])
def test_pair_kernel_compiles_for_v5e(one_chip, no_persistent_cache, T):
    """The seed/survivor pair kernel behind ``ops._pair_dp``."""
    bsp, meta, g_out = _plan(T)
    S, Ti = bsp.tile, bsp.T // bsp.tile
    compiled = _spdtw_block_call.lower(
        _sds(one_chip, meta.shape, jnp.int32),
        _sds(one_chip, (Ti, N_PAIRS, S)), _sds(one_chip, (Ti, N_PAIRS, S)),
        _sds(one_chip, bsp.blocks.shape), S=S, n_active=meta.shape[0],
        T_orig=T, g_out=g_out, block_b=8, d=1, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
