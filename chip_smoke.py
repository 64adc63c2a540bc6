"""Chip smoke test: exact SP-DTW 1-NN serving on a TPU.

    python chip_smoke.py                # one chip: cascade vs dense reference
    python chip_smoke.py --four-chips   # only the sharded mesh path, 4 chips

Run it from the repository root. It drives the main path once through the
entry points a user calls — ``fit`` via ``SearchEngine`` (or
``ShardedSearch``) and ``search`` — at a real corpus size: 8,926 series of
length 96, the train-split size of UCR ElectricDevices, the archive's
largest (Dau et al. 2018, arXiv:1810.07758), generated as synthetic CBF
from ``--seed``. The support is learned on a seeded subset of 32 series.
Queries are warped, renoised corpus entries served in batches of 16.

Checks: the top-1 ids of one batch equal a brute-force argmin over the
whole corpus from the ``dense`` backend (the core DPs: no plan, no
bounds), and the distances agree to 1e-5 relative; the compiled cascade
holds a ``tpu_custom_call`` (the Pallas Gram kernel, compiled). With
``--four-chips`` the mesh path must run with the corpus placed on 4
devices, and its answers must equal the one-device cascade and the dense
reference. Latencies printed here are a smoke figure, not a benchmark.

Exits non-zero, without the result line, when JAX finds no TPU or any
check fails. The last line of stdout is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_CORPUS = 8926     # UCR ElectricDevices train split
T = 96
N_SUPPORT = 32
BATCH = 16
THETA = 8.0
RTOL = 1e-5


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _corpus(seed: int):
    """The synthetic corpus, its learned support and the query batches."""
    import jax.numpy as jnp
    from repro.core import learn_sparse_paths
    from repro.data import load
    from repro.launch.search import _make_workload
    ds = load("CBF", n_train=N_CORPUS, T=T, seed=seed)
    sub = np.random.default_rng(seed).choice(N_CORPUS, N_SUPPORT,
                                             replace=False)
    sp = learn_sparse_paths(jnp.asarray(ds.X_train[sub]), theta=THETA)
    Q = _make_workload(ds, "retrieval", 4 * BATCH, seed)
    return ds, sp, [Q[i:i + BATCH] for i in range(0, len(Q), BATCH)]


def _reference(engine, Q):
    """Brute-force 1-NN over the whole corpus on the dense backend."""
    D = np.asarray(engine.gram(Q, impl="dense"))
    nn = D.argmin(axis=1)
    return nn, D[np.arange(len(nn)), nn]


def _compare(name: str, nn, dist, ref_nn, ref_dist) -> None:
    nn, dist = np.asarray(nn), np.asarray(dist)
    id_miss = int((nn != ref_nn).sum())
    rel = float(np.max(np.abs(dist - ref_dist)
                       / np.maximum(np.abs(ref_dist), 1e-30)))
    print(f"delta[{name}] id_mismatches={id_miss} max_rel_dist={rel!r}",
          flush=True)
    _check(id_miss == 0, f"{name}: {id_miss} top-1 ids differ")
    _check(rel <= RTOL, f"{name}: relative distance error {rel} > {RTOL}")


def _serve(search, batches):
    """Serve every batch; returns the answers and per-batch seconds (the
    first one includes compilation)."""
    out, secs = [], []
    for Q in batches:
        t0 = time.perf_counter()
        nn, dist = search(Q)
        nn, dist = np.asarray(nn), np.asarray(dist)
        secs.append(time.perf_counter() - t0)
        out.append((nn, dist))
    return out, secs


def _report_latency(secs) -> None:
    steady = secs[1:]
    print(f"first_batch_s={secs[0]!r} (compile included)", flush=True)
    print(f"compile_s={secs[0] - float(np.median(steady))!r} "
          f"(first batch minus steady median)", flush=True)
    print("batch_latency_ms (smoke figure, not a benchmark): "
          + " ".join(f"{1e3 * s:.3f}" for s in steady), flush=True)


def one_chip(seed: int) -> None:
    """Exact 1-NN through ``SearchEngine(..., shards=1)`` on one chip."""
    import jax
    from repro.kernels import backends as bk
    from repro.launch.search import SearchEngine
    ds, sp, batches = _corpus(seed)
    t0 = time.perf_counter()
    se = SearchEngine(ds.X_train, ds.y_train, sp=sp, impl="auto", seed=seed,
                      shards=1)
    print(f"fit_s={time.perf_counter() - t0!r} corpus={se.index.size} "
          f"T={T} tile={se.index.bsp.tile} "
          f"active_tiles={se.index.bsp.n_active}", flush=True)
    _check(bk.resolve("auto").name == "pallas",
           "impl='auto' does not resolve to the Pallas backend")
    answers, secs = _serve(se.search, batches)
    _report_latency(secs)
    _compare("cascade_vs_dense", *answers[0], *_reference(se.engine,
                                                          batches[0]))

    cascade = jax.jit(lambda q: se.engine.knn(q, impl="auto"))
    t0 = time.perf_counter()
    compiled = cascade.lower(batches[0]).compile()
    print(f"jit_cascade_compile_s={time.perf_counter() - t0!r}", flush=True)
    _check("tpu_custom_call" in compiled.as_text(),
           "the compiled cascade holds no tpu_custom_call")
    jit_answers, jit_secs = _serve(compiled, batches + batches[:1])
    print("jit_cascade_latency_ms (smoke figure, not a benchmark): "
          + " ".join(f"{1e3 * s:.3f}" for s in jit_secs[1:]), flush=True)
    _compare("jit_cascade_vs_served", *jit_answers[0], *answers[0])


def four_chips(seed: int) -> None:
    """The sharded mesh path alone: ``ShardedSearch(engine, n_shards=4)``."""
    import jax
    from repro.core.engine import MeasureSpec, fit
    from repro.launch.shard_index import ShardedSearch
    _check(len(jax.devices()) >= 4,
           f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    ds, sp, batches = _corpus(seed)
    engine = fit(MeasureSpec("spdtw", seed=seed), ds.X_train,
                 labels=ds.y_train, sp=sp, impl="auto")
    ss = ShardedSearch(engine, n_shards=4)
    devs = {d for a in ss._placed for d in a.sharding.device_set}
    print(f"path={ss.path} placed_devices={len(devs)} "
          f"shard_sizes={ss.balance()['sizes']}", flush=True)
    _check(ss.path == "mesh", f"sharded path is {ss.path!r}, not 'mesh'")
    _check(len(devs) == 4, f"corpus placed on {len(devs)} devices, not 4")
    answers, secs = _serve(ss.knn, batches)
    _report_latency(secs)
    one = engine.knn(batches[0], impl="auto")
    _compare("mesh_vs_one_device", *answers[0], *map(np.asarray, one))
    _compare("mesh_vs_dense", *answers[0], *_reference(engine, batches[0]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded mesh path on four chips")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    dev = jax.devices()[0]
    _check(dev.platform == "tpu", f"JAX found no TPU (platform "
                                  f"{dev.platform!r})")
    print(f"compile_cache={enable_compile_cache()}", flush=True)
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}", flush=True)
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
