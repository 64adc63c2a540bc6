"""MLPerf-style serving scenarios over the sharded cascade
(DESIGN.md §15) — the measurement harness for the serving tier.

Modeled on MaxText's ``offline_inference.py``: one fitted engine, one
sharded index, three load shapes with MLPerf-inference semantics, each
measured with wall-clock latency percentiles rather than a single mean:

  * **offline** — maximum throughput. All queries are available up
    front, sorted by series length so every batch is shape-uniform
    (one compiled cascade per shape; a no-op for fixed-T UCR corpora
    but the batching rule the harness commits to), then drained in
    full batches. Metric: throughput_qps.
  * **server** — seeded Poisson arrivals and continuous batching. The
    arrival process is drawn from ``MeasureSpec.seed`` (reproducible
    traffic), the offered rate defaults to half the calibrated offline
    capacity, and each step drains every query that has arrived by the
    virtual clock (up to ``batch``). Metric: p50/p95/p99 of per-query
    latency = completion − arrival.
  * **single_stream** — one query in flight at a time (batch = 1,
    sequential). Metric: per-query latency percentiles.

A fourth load shape, **server+refresh** (DESIGN.md §16), measures the
learner/actor split: the server scenario runs twice at the same offered
rate — once against a frozen engine, once with a background ``Learner``
concurrently consuming an arrival stream and publishing versioned
snapshots that serving adopts at batch boundaries. The delta between
the two latency distributions is the cost of continuous fitting; the
payload also reports snapshot cadence, staleness (refresh lag), version
monotonicity, and an ``exact_final`` flag asserting the last published
snapshot answers bit-identically to a from-scratch fit on the final
corpus.

A fifth load shape, **anomaly** (DESIGN.md §17), measures the streaming
corpus-analytics tier: seeded outliers are injected into the Poisson
arrival stream and the server scenario runs twice at the same offered
rate — monitor off, then with a fitted ``repro.monitor.Monitor``
scoring every batch. The payload (``BENCH_anomaly.json``) reports the
sketch-score ROC-AUC over the injected outliers, the escalation rate
(the borderline band that paid the exact cascade), the p99 overhead of
monitoring, a ``decisions_exact`` flag (escalated decisions bit-equal
to exact-distance scoring at the calibrated threshold), and the drift
monitor's behaviour on i.i.d. vs shifted streams; the corpus embedding
map rides along as ``BENCH_embed.json``.

Every run emits ``BENCH_serving.json`` (throughput, per-stage latency
percentiles, shard-balance stats, and an ``exact`` flag asserting the
sharded top-1 is bit-identical to the single-host cascade) which
``benchmarks/check_artifacts.py`` schema-gates; the refresh shape emits
``BENCH_refresh.json`` instead, gated the same way. CI runs ``--smoke``
on a forced 4-device CPU mesh and gates both artifacts.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      PYTHONPATH=src python -m repro.launch.scenarios --smoke \\
      --shards 4 --out /tmp/bench-smoke
  PYTHONPATH=src python -m repro.launch.scenarios --dataset CBF \\
      --shards 2 --scenario server --rate 200
  PYTHONPATH=src python -m repro.launch.scenarios --smoke \\
      --scenario server+refresh --out /tmp/bench-refresh
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import learn_sparse_paths
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.search import SearchEngine, _make_workload
from repro.launch.stats import percentiles


def _drain(engine: SearchEngine, queries: np.ndarray,
           batch: int) -> np.ndarray:
    """Serve ``queries`` in back-to-back full batches; returns nn ids."""
    nn_all = []
    for lo in range(0, len(queries), batch):
        nn, _ = engine.search(queries[lo:lo + batch])
        nn_all.append(nn)
    return np.concatenate(nn_all)


def offline_scenario(engine: SearchEngine, queries: np.ndarray,
                     batch: int) -> Dict[str, float]:
    """Max-throughput drain: sorted-length batching, full batches,
    nothing waits on arrivals. The length sort keeps every batch
    shape-uniform (one compiled cascade per shape)."""
    order = np.argsort([q.shape[-1] for q in queries], kind="stable")
    t0 = time.time()
    _drain(engine, queries[order], batch)
    wall = time.time() - t0
    return {"n_queries": len(queries), "batch": batch, "wall_s": wall,
            "throughput_qps": len(queries) / wall,
            "latency_ms": percentiles([wall / max(1, len(queries))] *
                                       len(queries))}


def server_scenario(engine: SearchEngine, queries: np.ndarray,
                    batch: int, *, rate_qps: Optional[float] = None,
                    seed: Optional[int] = None,
                    on_step=None) -> Dict[str, float]:
    """Poisson-arrival continuous batching with per-query latency.

    Arrivals are an exponential inter-arrival process seeded from the
    engine's ``MeasureSpec.seed`` (reproducible traffic; ``seed``
    overrides). ``rate_qps=None`` calibrates the offered load to half
    the measured offline capacity of one warm batch. A virtual clock
    advances by each batch's measured service time; each step drains
    every query that has arrived by then (up to ``batch``), and a
    query's latency is its completion time minus its arrival time —
    queueing delay included, which is what p99 is for.

    ``on_step`` (optional) is called with the step index after each
    served batch — the deterministic-interleaving hook the refresh
    shape uses to step a learner synchronously between batches when it
    is not running one in a background thread.
    """
    n = len(queries)
    if seed is None:
        seed = engine.engine.spec.seed
    rng = np.random.default_rng(seed)
    # warm + calibrate: one measured batch gives the service capacity
    t0 = time.time()
    engine.search(queries[:batch])
    svc = time.time() - t0
    if rate_qps is None:
        rate_qps = 0.5 * batch / max(svc, 1e-9)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_qps, size=n))
    now = 0.0
    served = 0
    lat: List[float] = []
    n_steps = 0
    while served < n:
        ready = int(np.searchsorted(arrivals, now, side="right"))
        if ready == served:            # idle: jump to the next arrival
            now = float(arrivals[served])
            continue
        take = min(batch, ready - served)
        # fixed-slot continuous batching: pad the drain to the full
        # batch shape so every step hits the one compiled cascade
        # (variable shapes would recompile per step and the queueing
        # tail would measure the compiler, not the server)
        Qb = queries[served:served + take]
        if take < batch:
            Qb = np.concatenate(
                [Qb, np.broadcast_to(Qb[-1:], (batch - take,)
                                     + Qb.shape[1:])])
        t0 = time.time()
        engine.search(Qb)
        now += time.time() - t0
        lat.extend(now - arrivals[served:served + take])
        served += take
        n_steps += 1
        if on_step is not None:
            on_step(n_steps)
    return {"n_queries": n, "batch": batch, "rate_qps": float(rate_qps),
            "seed": int(seed), "wall_s": float(now),
            "throughput_qps": n / max(now, 1e-9),
            "mean_batch": n / max(n_steps, 1),
            "latency_ms": percentiles(lat)}


def single_stream_scenario(engine: SearchEngine,
                           queries: np.ndarray) -> Dict[str, float]:
    """One query in flight at a time: sequential batch-1 serving, the
    per-query latency floor."""
    lat: List[float] = []
    t0 = time.time()
    for q in queries:
        t1 = time.time()
        engine.search(q[None])
        lat.append(time.time() - t1)
    wall = time.time() - t0
    return {"n_queries": len(queries), "batch": 1, "wall_s": wall,
            "throughput_qps": len(queries) / wall,
            "latency_ms": percentiles(lat)}


SCENARIOS = ("offline", "server", "single_stream")


def run(dataset: str = "CBF", n_queries: int = 64, batch: int = 16,
        shards: int = 2, scenario: str = "all", theta: float = 8.0,
        n_train: int = 128, T: Optional[int] = None, impl: str = "auto",
        seed: int = 0, rate_qps: Optional[float] = None,
        n_sp_train: int = 32) -> dict:
    """Fit one engine, shard it, drive the requested scenarios, and
    return the ``BENCH_serving.json`` payload. The ``exact`` flag is
    computed first: the sharded top-1 (ids and distances) must be
    bit-identical to the single-host cascade over the full query set."""
    from repro.data import load
    kw = {} if T is None else {"T": T}
    ds = load(dataset, n_train=n_train, **kw)
    Xtr = jnp.asarray(ds.X_train)
    sp = learn_sparse_paths(Xtr[:n_sp_train], theta=theta)
    shards = max(1, min(shards, len(ds.X_train)))
    engine = SearchEngine(Xtr, ds.y_train, sp=sp, impl=impl, seed=seed,
                          shards=shards)
    queries = _make_workload(ds, "retrieval", n_queries, seed)

    # exactness gate: sharded vs single-host cascade, bit-identical
    assert engine.sharded is not None
    g_sh, d_sh = engine.sharded.knn(queries)
    nn_one, d_one = engine.engine.knn(jnp.asarray(queries), impl=impl,
                                      seed_k=engine.seed_k,
                                      prefix_frac=engine.prefix_frac)
    exact = bool(np.array_equal(np.asarray(g_sh), np.asarray(nn_one)) and
                 np.array_equal(np.asarray(d_sh), np.asarray(d_one)))

    wanted = SCENARIOS if scenario == "all" else (scenario,)
    out_sc: Dict[str, dict] = {}
    for name in wanted:
        if name == "offline":
            out_sc[name] = offline_scenario(engine, queries, batch)
        elif name == "server":
            out_sc[name] = server_scenario(engine, queries, batch,
                                           rate_qps=rate_qps)
        elif name == "single_stream":
            out_sc[name] = single_stream_scenario(engine, queries)
        else:
            raise ValueError(f"unknown scenario {name!r}")
    return {
        "bench": "serving", "backend": jax.default_backend(),
        "impl": impl, "dataset": dataset, "corpus": engine.index.size,
        "T": int(ds.T), "n_queries": int(n_queries), "seed": int(seed),
        "n_shards": engine.sharded.n_shards,
        "shard_path": engine.sharded.path,
        "shard_balance": engine.sharded.balance(),
        "exact": exact,
        "scenarios": out_sc,
        "stats": engine.stats(),
    }


def refresh_run(dataset: str = "CBF", n_queries: int = 64,
                batch: int = 16, theta: float = 8.0, n_train: int = 128,
                T: Optional[int] = None, impl: str = "auto", seed: int = 0,
                rate_qps: Optional[float] = None, n_sp_train: int = 32,
                arrival_frac: float = 0.25, learner_batch: int = 8,
                threaded: bool = True) -> dict:
    """The ``server+refresh`` load shape (DESIGN.md §16): serving
    percentiles with and without a concurrent background learner.

    The training pool is split: the first ``1 - arrival_frac`` of it is
    the initially-fitted corpus, the rest becomes the learner's arrival
    stream (labels ride along). The server scenario then runs twice at
    the *same* offered rate — first against the frozen initial engine
    (the baseline the calibration comes from), then with a ``Learner``
    publishing a new snapshot per consumed mini-batch while serving
    adopts each one at the next batch boundary. ``threaded=True`` runs
    the learner in its own thread (real concurrency, the no-pause
    claim); ``threaded=False`` steps it synchronously between serving
    steps via the ``on_step`` hook (deterministic, used by tests).

    Returns the ``BENCH_refresh.json`` payload: both latency
    distributions, snapshot count/cadence, staleness (refresh lag),
    ``versions_monotone``, and ``exact_final`` — the last published
    snapshot must answer the query set bit-identically to a
    from-scratch fit on the final corpus (the invariant that makes the
    whole refresh loop exact rather than approximate)."""
    from repro.core.engine import fit
    from repro.core.snapshot import SnapshotStore
    from repro.data import load
    from repro.launch.learner import Learner
    kw = {} if T is None else {"T": T}
    ds = load(dataset, n_train=n_train, **kw)
    n_arr = max(1, int(len(ds.X_train) * arrival_frac))
    n0 = len(ds.X_train) - n_arr
    assert n0 >= 2, "arrival_frac leaves too small an initial corpus"
    X0, Xarr = ds.X_train[:n0], ds.X_train[n0:]
    y0, yarr = ds.y_train[:n0], ds.y_train[n0:]
    sp = learn_sparse_paths(jnp.asarray(X0[:n_sp_train]), theta=theta)
    queries = _make_workload(ds, "retrieval", n_queries, seed)

    # pass 1: frozen engine — the baseline (also calibrates the rate)
    base_engine = SearchEngine(jnp.asarray(X0), y0, sp=sp, impl=impl,
                               seed=seed)
    base = server_scenario(base_engine, queries, batch, rate_qps=rate_qps,
                           seed=seed)

    # pass 2: same initial engine behind a store, learner refreshing it
    store = SnapshotStore(base_engine.engine, keep_history=True)
    serve_engine = SearchEngine(None, engine=None, refresh=store, impl=impl)
    learner = Learner(store, Xarr, labels=yarr, batch=learner_batch,
                      impl=impl)
    t0 = time.time()
    if threaded:
        learner.start()
        refreshed = server_scenario(serve_engine, queries, batch,
                                    rate_qps=base["rate_qps"], seed=seed)
        learner.join()
    else:
        refreshed = server_scenario(
            serve_engine, queries, batch, rate_qps=base["rate_qps"],
            seed=seed, on_step=lambda i: learner.step())
        learner.drain()
    learner_wall = time.time() - t0
    stats = serve_engine.stats()

    versions = [s.version for s in store.history]
    monotone = all(b == a + 1 for a, b in zip(versions, versions[1:]))

    # exactness of the final snapshot: bit-identical answers to a
    # from-scratch fit on the final corpus (same sp / bsp / T)
    eng_f = store.current().engine
    fresh = fit(eng_f.spec, eng_f.corpus, labels=eng_f.labels,
                sp=eng_f.sp, bsp=eng_f.bsp, T=eng_f.T)
    Q = jnp.asarray(queries)
    nn_a, d_a = eng_f.knn(Q, impl=impl)
    nn_b, d_b = fresh.knn(Q, impl=impl)
    exact_final = bool(np.array_equal(np.asarray(nn_a), np.asarray(nn_b))
                       and np.array_equal(np.asarray(d_a),
                                          np.asarray(d_b)))

    return {
        "bench": "refresh", "backend": jax.default_backend(),
        "impl": impl, "dataset": dataset, "T": int(ds.T),
        "n_queries": int(n_queries), "seed": int(seed),
        "threaded": bool(threaded),
        "corpus_initial": int(n0), "corpus_final": int(eng_f.corpus_size),
        "n_arrivals": int(n_arr), "learner_batch": int(learner_batch),
        "n_snapshots": int(store.n_published),
        "final_version": int(store.version),
        "versions_monotone": bool(monotone),
        "snapshot_cadence_s": learner_wall / max(store.n_published, 1),
        "exact_final": exact_final,
        "server": base, "server_refresh": refreshed,
        "staleness": {
            "published_version": int(store.version),
            "served_version": int(stats.get("version", 0)),
            "n_refreshes": int(stats["refresh"]["n_refreshes"]),
            "mean_lag": float(stats["refresh"]["mean_lag"]),
            "max_lag": int(stats["refresh"]["max_lag"]),
        },
    }


def _inject_outliers(queries: np.ndarray, frac: float,
                     seed: int) -> tuple:
    """Replace a seeded ``frac`` of the query stream with z-normalized
    random walks — off-manifold series no corpus family generates.
    Returns (queries, truth) with truth[i] = 1 on injected rows."""
    rng = np.random.default_rng([int(seed), 0xBAD5])
    q = np.array(queries, np.float32, copy=True)
    n, T = q.shape[0], q.shape[-1]
    n_out = max(1, int(round(frac * n)))
    idx = np.sort(rng.permutation(n)[:n_out])
    walks = np.cumsum(rng.normal(size=(n_out, T)), axis=1)
    walks = (walks - walks.mean(1, keepdims=True)) / \
        (walks.std(1, keepdims=True) + 1e-8)
    q[idx] = walks.astype(np.float32)
    truth = np.zeros(n, np.int32)
    truth[idx] = 1
    return q, truth


def anomaly_run(dataset: str = "CBF", n_queries: int = 96,
                batch: int = 16, theta: float = 8.0, n_train: int = 128,
                T: Optional[int] = None, impl: str = "auto", seed: int = 0,
                rate_qps: Optional[float] = None, n_sp_train: int = 32,
                outlier_frac: float = 0.25, sketch_r: int = 8,
                k: int = 3, quantile: float = 0.95, n_cal: int = 64,
                window: int = 24, alpha: float = 0.01,
                n_perm: int = 200) -> dict:
    """The ``anomaly`` load shape (DESIGN.md §17): the server scenario
    with a fitted ``repro.monitor.Monitor`` scoring every batch, seeded
    outliers injected into the Poisson arrival stream.

    Four measurements make the ``BENCH_anomaly.json`` payload:

      * detection quality — sketch-score ROC-AUC over the injected
        outliers, plus a ``decisions_exact`` flag asserting the
        escalated flag/clean decisions are bit-identical to scoring
        every query with the exact cascade at the calibrated ``tau``;
      * serving cost — the server scenario runs twice at the *same*
        offered rate (monitor off, then on); the p99 delta/ratio is
        the streaming-analytics overhead, and the monitor's own stage
        percentiles ride in ``stats.latency_ms.monitor``;
      * escalation economy — what fraction of the stream actually paid
        the exact cascade (the borderline band around ``tau``);
      * drift behaviour — a fresh ``DriftMonitor`` per stream must stay
        silent on an i.i.d. resample of the corpus and fire on an
        amplitude-shifted copy of the same stream, deterministically
        under the spec seed.
    """
    from repro.core.engine import MeasureSpec, fit
    from repro.data import load
    from repro.monitor import fit_drift_monitor, fit_monitor, roc_auc, \
        sketch_map
    kw = {} if T is None else {"T": T}
    ds = load(dataset, n_train=n_train, **kw)
    Xtr = jnp.asarray(ds.X_train)
    sp = learn_sparse_paths(Xtr[:n_sp_train], theta=theta)
    spec = MeasureSpec("spdtw", theta=theta, seed=seed, sketch_r=sketch_r)
    eng = fit(spec, Xtr, labels=ds.y_train, sp=sp, impl=impl)
    mon = fit_monitor(eng, k=k, quantile=quantile, n_cal=n_cal,
                      window=window, alpha=alpha, n_perm=n_perm, impl=impl)
    clean_q = _make_workload(ds, "retrieval", n_queries, seed)
    queries, truth = _inject_outliers(clean_q, outlier_frac, seed)

    # detection quality, off the serving clock: one batched decision
    # pass over the full stream + the exact-cascade oracle
    flags, scores, dstats = mon.anomaly.decide(queries, impl=impl,
                                               return_stats=True)
    flags_x, _ = mon.anomaly.decide_exact(queries, impl=impl)
    decisions_exact = bool(np.array_equal(flags, flags_x))
    auc = roc_auc(scores, truth)

    # serving cost: same offered rate, monitor off then on
    off_engine = SearchEngine(None, engine=eng, impl=impl, seed=seed)
    base = server_scenario(off_engine, queries, batch, rate_qps=rate_qps,
                           seed=seed)
    mon.reset()
    on_engine = SearchEngine(None, engine=eng, impl=impl, seed=seed,
                             monitor=mon)
    refreshed = server_scenario(on_engine, queries, batch,
                                rate_qps=base["rate_qps"], seed=seed)
    stats = on_engine.stats()
    p99_off = base["latency_ms"]["p99"]
    p99_on = refreshed["latency_ms"]["p99"]

    # drift behaviour: fresh monitors, i.i.d. vs amplitude-shifted
    rng = np.random.default_rng([int(seed), 0xD1FF])
    iid = np.asarray(ds.X_train)[rng.integers(0, len(ds.X_train),
                                              size=n_queries)]
    shifted = 2.0 * iid + 0.5
    dm_iid = fit_drift_monitor(eng, window=window, alpha=alpha,
                               n_perm=n_perm)
    dm_shift = fit_drift_monitor(eng, window=window, alpha=alpha,
                                 n_perm=n_perm)
    for lo in range(0, n_queries, batch):
        dm_iid.update(np.asarray(eng.sketch_embed(iid[lo:lo + batch],
                                                  impl=impl)))
        dm_shift.update(np.asarray(eng.sketch_embed(shifted[lo:lo + batch],
                                                    impl=impl)))

    return {
        "bench": "anomaly", "backend": jax.default_backend(),
        "impl": impl, "dataset": dataset, "T": int(ds.T),
        "corpus": int(eng.index.size), "n_queries": int(n_queries),
        "seed": int(seed), "theta": theta,
        "sketch_r": int(sketch_r), "k": int(k),
        "outlier_frac": float(outlier_frac),
        "n_outliers": int(truth.sum()),
        "quantile": float(quantile), "tau": float(mon.anomaly.tau),
        "roc_auc": float(auc),
        "decisions_exact": decisions_exact,
        "flag_rate": float(np.mean(flags)),
        "escalation_rate": float(dstats["escalation_rate"]),
        "n_escalated": int(dstats["n_escalated"]),
        "server": base, "server_monitor": refreshed,
        "p99_overhead_ms": float(p99_on - p99_off),
        "p99_overhead_ratio": float(p99_on / max(p99_off, 1e-9)),
        "monitor": stats["monitor"],
        "drift": {
            "window": int(window), "alpha": float(alpha),
            "n_perm": int(n_perm),
            "events_iid": len(dm_iid.events),
            "events_shift": len(dm_shift.events),
            "silent_on_iid": len(dm_iid.events) == 0,
            "fires_on_shift": len(dm_shift.events) > 0,
        },
        "embed_map": sketch_map(eng),
    }


def main(argv=None):
    """CLI entry: ``python -m repro.launch.scenarios [--smoke]
    [--scenario all|offline|server|single_stream|server+refresh|anomaly]
    ...`` — writes ``BENCH_serving.json`` (``BENCH_refresh.json`` for
    the refresh shape; ``BENCH_anomaly.json`` + ``BENCH_embed.json``
    for the anomaly shape) under ``--out`` (DESIGN.md §15, §16, §17)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="CBF")
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--scenario", default="all",
                    choices=("all",) + SCENARIOS +
                    ("server+refresh", "anomaly"))
    ap.add_argument("--theta", type=float, default=8.0)
    ap.add_argument("--impl", default="auto")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rate", type=float, default=None, dest="rate_qps",
                    help="server-scenario offered load in qps (default: "
                         "half the calibrated offline capacity)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for the CI gate (and a tempdir "
                         "artifact unless --out is given)")
    ap.add_argument("--out", default=None,
                    help="artifact directory (default: repo root, or a "
                         "fresh tempdir with --smoke)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    refresh = args.scenario == "server+refresh"
    anomaly = args.scenario == "anomaly"
    if anomaly:
        kw = dict(dataset=args.dataset, n_queries=args.queries,
                  batch=args.batch, theta=args.theta, impl=args.impl,
                  seed=args.seed, rate_qps=args.rate_qps)
        if args.smoke:
            kw.update(n_queries=min(args.queries, 24),
                      batch=min(args.batch, 8), n_train=48, T=32,
                      n_sp_train=16, sketch_r=4, n_cal=32, window=8,
                      n_perm=100)
    elif refresh:
        kw = dict(dataset=args.dataset, n_queries=args.queries,
                  batch=args.batch, theta=args.theta, impl=args.impl,
                  seed=args.seed, rate_qps=args.rate_qps)
        if args.smoke:
            kw.update(n_queries=min(args.queries, 24),
                      batch=min(args.batch, 8), n_train=48, T=32,
                      n_sp_train=16, learner_batch=4)
    else:
        kw = dict(dataset=args.dataset, n_queries=args.queries,
                  batch=args.batch, shards=args.shards,
                  scenario=args.scenario, theta=args.theta, impl=args.impl,
                  seed=args.seed, rate_qps=args.rate_qps)
        if args.smoke:
            kw.update(n_queries=min(args.queries, 24),
                      batch=min(args.batch, 8), n_train=48, T=32,
                      n_sp_train=16,
                      shards=max(1, min(args.shards, jax.device_count())))
    out_dir = args.out
    if out_dir is None:
        if args.smoke:
            import tempfile
            out_dir = tempfile.mkdtemp(prefix="bench-serving-")
        else:
            out_dir = "."
    if anomaly:
        res = anomaly_run(**kw)
    elif refresh:
        res = refresh_run(**kw)
    else:
        res = run(**kw)
    res["smoke"] = bool(args.smoke)
    os.makedirs(out_dir, exist_ok=True)
    name = "BENCH_anomaly.json" if anomaly else (
        "BENCH_refresh.json" if refresh else "BENCH_serving.json")
    path = os.path.join(out_dir, name)
    if anomaly:
        # the dataset map is its own schema-gated artifact
        emb = dict(res.pop("embed_map"), smoke=bool(args.smoke))
        epath = os.path.join(out_dir, "BENCH_embed.json")
        with open(epath, "w") as f:
            json.dump(emb, f, indent=1, default=float)
            f.write("\n")
    with open(path, "w") as f:
        json.dump(res, f, indent=1, default=float)
        f.write("\n")
    print(json.dumps(res, indent=1, default=float))
    print(f"wrote {path}")
    if anomaly:
        print(f"wrote {epath}")
        for nm, sc in (("server", res["server"]),
                       ("server+monitor", res["server_monitor"])):
            p = sc["latency_ms"]
            print(f"{nm:15s} {sc['throughput_qps']:9.1f} qps  "
                  f"p50={p['p50']:8.2f}ms p95={p['p95']:8.2f}ms "
                  f"p99={p['p99']:8.2f}ms")
        print(f"roc_auc={res['roc_auc']:.3f} "
              f"escalation_rate={res['escalation_rate']:.3f} "
              f"p99_overhead={res['p99_overhead_ms']:+.2f}ms")
        if not res["decisions_exact"]:
            raise SystemExit("escalated anomaly decisions diverged from "
                             "exact-cascade scoring")
        if not (res["drift"]["silent_on_iid"] and
                res["drift"]["fires_on_shift"]):
            raise SystemExit("drift monitor mis-triggered (fired on iid "
                             "or stayed silent on shift)")
        return
    if refresh:
        for name, sc in (("server", res["server"]),
                         ("server+refresh", res["server_refresh"])):
            p = sc["latency_ms"]
            print(f"{name:15s} {sc['throughput_qps']:9.1f} qps  "
                  f"p50={p['p50']:8.2f}ms p95={p['p95']:8.2f}ms "
                  f"p99={p['p99']:8.2f}ms")
        print(f"snapshots={res['n_snapshots']} "
              f"cadence={res['snapshot_cadence_s']:.3f}s "
              f"max_lag={res['staleness']['max_lag']}")
        if not res["exact_final"]:
            raise SystemExit("final snapshot diverged from a from-scratch "
                             "fit on the final corpus")
        if not res["versions_monotone"]:
            raise SystemExit("published versions were not monotone")
        return
    for name, sc in res["scenarios"].items():
        p = sc["latency_ms"]
        print(f"{name:13s} {sc['throughput_qps']:9.1f} qps  "
              f"p50={p['p50']:8.2f}ms p95={p['p95']:8.2f}ms "
              f"p99={p['p99']:8.2f}ms")
    if not res["exact"]:
        raise SystemExit("sharded top-1 diverged from single-host cascade")


if __name__ == "__main__":
    main()
