"""The served cascade against the plain reference on long series: at
T = 300 the plan takes 64 x 64 tiles, as at FordA's T = 500, on a grid
padded to 320."""
import numpy as np

from bench import check, run
from bench.tests import tiny
from bench.traffic import Served

LONG = {"n_train": 40, "n_test": 32, "T": 300, "d": 1,
        "support": {"n_series": 8, "theta": 1.0},
        "serving": {"seed_k": 2, "prefix_frac": 0.5}}


def test_served_cascade_agrees_with_reference_on_a_padded_tile_64_grid():
    cell = dict(tiny.cell(tiny.OFFLINE), config=LONG, seconds=1.0)
    seed = 2 ** 40 + 11
    serve, state, ds, support_series, queries = run.build(cell, seed)
    bsp = state["engine"].index.bsp
    assert (bsp.tile, bsp.T) == (64, 320)
    assert bsp.n_active < bsp.active.size    # the plan skips tiles
    nn, dist = zip(*(serve(queries[s:s + 16])
                     for s in range(0, len(queries), 16)))
    served = Served(nn=np.concatenate(nn), dist=np.concatenate(dist),
                    latency_s=None, n_batches=len(nn), elapsed_s=1.0,
                    queries=queries)
    # the check samples every served query here: all of them are held
    # against the reference
    assert len(served.nn) <= check.SAMPLE
    numbers = check.compare(served, ds.X_train, support_series,
                            LONG["support"]["theta"], run.rng(seed, 4))
    assert numbers["answer_err"]["value"] < 1e-5, numbers
