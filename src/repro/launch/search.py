"""Streaming SP-DTW similarity-search driver (DESIGN.md §4/§8/§10).

The serving side of the paper plane: a fixed corpus is indexed once
(``Measure.build_index`` — envelopes, support windows, block-sparse tile
plan), then a stream of 1-NN queries is served continuous-batching style:
requests join at the next step boundary, each step runs one cascade
batch, finished slots free up for the next arrivals. Every batch runs bounds -> survivors -> fused
masked DP (``kernels.ops.knn_cascade``) and reports per-stage prune
rates; results are bit-identical to the full-Gram path.

With ``--centroids N`` the engine serves in nearest-centroid mode
(DESIGN.md §10): N soft-SP-DTW barycenters per class are fitted on the
corpus labels at startup and each query pays k = n_classes * N masked
DPs instead of a corpus-sized cascade — approximate classification at a
fraction of the query cost. In cascade mode a fitted model still helps:
it seeds the per-query threshold (centroid-seeded cascade, exactness
untouched).

  PYTHONPATH=src python -m repro.launch.search --dataset CBF --queries 64
  PYTHONPATH=src python -m repro.launch.search --workload retrieval --check
  PYTHONPATH=src python -m repro.launch.search --workload classify \\
      --centroids 1
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import SparsePaths, learn_sparse_paths
from repro.core.engine import MeasureSpec, fit
from repro.launch.stats import percentiles

_STAT_KEYS = ("stage1_prune", "stage2_prune", "stage3_prune",
              "pre_dp_prune", "dp_abandoned")
_SKETCH_STAT_KEYS = ("shortlist_prune", "bound_prune", "pre_dp_prune")
_SKETCH_STAGES = ("embed", "shortlist", "rerank")
# latency_ms stage <- the span whose durations it reports
_LATENCY_SPANS = (("monitor", "search.monitor"), ("total", "search"))
# the cascade's device-scalar work counts -> counter names
_DP_COUNTERS = (("dp_tile_sweeps", "survivor_dp.tile_sweeps"),
                ("dp_alive_pair_sweeps", "survivor_dp.alive_pair_sweeps"))


@dataclasses.dataclass
class QueryResult:
    """One served query: neighbour, distance, and stream bookkeeping."""
    rid: int
    nn: int
    dist: float
    label: Optional[int]
    submitted_step: int
    completed_step: int

    @property
    def wait_steps(self) -> int:
        """Streaming-loop steps between submission and completion."""
        return self.completed_step - self.submitted_step


class SearchEngine:
    """1-NN / nearest-centroid serving shell over a ``SimilarityEngine``.

    Construction runs ``core.engine.fit`` once (the expensive part:
    support resolution, tile plan, corpus index); ``search`` then serves
    arbitrarily many query batches against the fitted engine.
    ``mode="cascade"`` (default) is the exact 1-NN lower-bound cascade —
    a fitted ``centroid_model`` only seeds its thresholds.
    ``mode="centroid"`` serves the nearest *centroid* instead (k DPs per
    query; ``search`` then returns centroid indices, and ``labels`` maps
    them to class labels, so the streaming loop is unchanged).
    ``mode="sketch"`` serves through the Random Warping Series tier
    (DESIGN.md §13): matmul shortlist of ``top_c`` candidates, exact
    cascade re-rank (skipped entirely with ``approx=True``) — sub-linear
    DP cost, exact whenever the shortlist covers the true neighbour.
    Every mode records each batch as spans (``repro.tracing``, on the
    profiler's clock): ``search``, ``search.monitor``, the cascade's
    ``cascade.*`` stages and ``search.readback``, with the programs
    compiled meanwhile as ``compile``. ``stats()`` reports the batch and
    monitor span durations as p50/p95/p99 and the spans and counters
    themselves under ``trace``.

    ``refresh`` accepts a ``core.snapshot.SnapshotStore`` (DESIGN.md
    §16): before each batch the engine adopts the store's current
    snapshot if a background learner published a newer one — one
    wait-free read, swap at the batch boundary, so every query in a
    batch is answered by exactly one fully-built snapshot. ``stats()``
    then reports the serving ``version`` plus refresh lag (how far
    serving trailed publication).

    ``monitor`` accepts a fitted ``repro.monitor.Monitor`` (DESIGN.md
    §17): every served batch is scored before serving — anomaly
    decisions (exact-escalated) and the drift window — timed as its own
    ``monitor`` latency stage, and ``stats()`` gains the cumulative
    anomaly/drift counters. The monitor keeps its own calibration
    engine, so snapshot refreshes never silently move the threshold.
    """

    def __init__(self, corpus, labels=None, *, kind: str = "spdtw",
                 sp: Optional[SparsePaths] = None, impl: str = "auto",
                 seed_k: int = 2, prefix_frac: float = 0.5,
                 centroid_model=None, mode: str = "cascade",
                 engine=None, sketch_r: int = 16, top_c: int = 32,
                 approx: bool = False, seed: int = 0, shards: int = 0,
                 refresh=None, monitor=None):
        assert mode in ("cascade", "centroid", "sketch")
        assert shards <= 1 or mode == "cascade", \
            "sharded serving is the exact cascade tier (DESIGN.md §15)"
        if mode == "centroid":
            assert centroid_model is not None, \
                "centroid mode needs a fitted cluster.CentroidModel"
        if engine is None and refresh is not None:
            engine = refresh.current().engine
        if engine is None:
            spec = MeasureSpec(family=kind, seed=seed,
                               sketch_r=sketch_r if mode == "sketch" else 0)
            engine = fit(spec, corpus, labels=labels, sp=sp, impl=impl)
        if mode == "sketch":
            assert engine.index is not None and \
                engine.index.sketch is not None, \
                "sketch mode needs an engine fit with sketch_r > 0"
        if centroid_model is not None:
            engine = dataclasses.replace(engine,
                                         centroid_model=centroid_model)
        self.mode = mode
        self.impl = impl
        self.seed_k = seed_k
        self.prefix_frac = prefix_frac
        self.top_c = top_c
        self.approx = approx
        self.shards = int(shards)
        self.store = refresh
        if monitor is not None:
            assert monitor.engine.index is not None and \
                monitor.engine.index.sketch is not None, \
                "monitoring reads the sketch tier: fit the monitor's " \
                "engine with sketch_r > 0 (repro.monitor.fit_monitor)"
        self.monitor = monitor
        self._bind_engine(engine)
        self.reset_stats()

    def _bind_engine(self, engine) -> None:
        """(Re)bind serving state to a fitted engine — the refresh seam.

        Everything queries read (index, centroid model, label map,
        sharded fan-out) is derived here from the one engine record, so
        adopting a new snapshot between batches re-derives all of it
        atomically from the serving loop's point of view: no query ever
        sees a new corpus next to an old label map."""
        self.engine = engine
        self.index = engine.index
        self.centroid_model = engine.centroid_model
        if self.mode == "centroid":
            # unsupervised models (soft_kmeans) have labels=None: serve
            # centroid ids with label=None rather than crashing the loop
            self.labels = None if engine.centroid_model.labels is None \
                else np.asarray(engine.centroid_model.labels)
        else:
            self.labels = None if engine.labels is None else \
                np.asarray(engine.labels)
        self.sharded = None
        if self.shards > 1:
            from repro.launch.shard_index import ShardedSearch
            self.sharded = ShardedSearch(engine, self.shards,
                                         impl=self.impl,
                                         seed_k=self.seed_k,
                                         prefix_frac=self.prefix_frac)

    def _maybe_refresh(self) -> None:
        """Adopt the store's current snapshot when a newer one has been
        published (one wait-free ``current()`` read). Refresh lag — how
        many publications serving trailed by when this batch arrived —
        is recorded *before* the swap, so ``stats()`` reports the
        staleness queries actually experienced."""
        if self.store is None:
            return
        snap = self.store.current()
        lag = int(snap.version) - int(self.engine.version)
        self._lag_sum += max(lag, 0)
        self._lag_max = max(self._lag_max, lag)
        self._lag_n += 1
        if lag > 0:
            self._bind_engine(snap.engine)
            self._n_refreshes += 1

    def reset_stats(self) -> None:
        """Zero every serving accumulator: prune counters, the span
        recorder, pair/query totals, refresh-lag bookkeeping. Call
        between streams so each reports independent stats — without
        this, a second ``stream_search`` pass folds the first pass's
        counters into its rates and percentiles."""
        keys = _SKETCH_STAT_KEYS if self.mode == "sketch" else _STAT_KEYS
        self._stats_acc: Dict[str, float] = {k: 0.0 for k in keys}
        self._trace = tracing.Recorder()
        self._sketch_lat: Dict[str, List[float]] = {}
        self._pairs_total = 0
        self._pairs_dp = 0
        self._queries = 0
        self._n_refreshes = 0
        self._lag_sum = 0
        self._lag_max = 0
        self._lag_n = 0

    @property
    def measure(self):
        """Legacy ``Measure`` view of the fitted engine (kept for
        callers that assert against the dense cross-matrix path)."""
        return self.engine.measure

    def search(self, queries) -> Tuple[np.ndarray, np.ndarray]:
        """(Nq, T) -> (nn_idx, nn_dist); prune stats accumulate on self.

        In centroid mode ``nn_idx`` indexes the centroid set (k DPs per
        query, counted as such in the pair stats)."""
        n = len(queries)
        with tracing.recording(self._trace), \
                tracing.span("search", n=n, mode=self.mode):
            Q = jnp.asarray(queries, jnp.float32)
            self._maybe_refresh()
            if self.monitor is not None:
                # corpus analytics tier (DESIGN.md §17): anomaly
                # decisions + drift window on this batch
                with tracing.span("search.monitor"):
                    self.monitor.observe(Q, impl=self.impl)
            return self._serve(Q, n)

    def _serve(self, Q, n: int) -> Tuple[np.ndarray, np.ndarray]:
        self._queries += n
        self._pairs_total += n * self.index.size
        if self.mode == "centroid":
            from repro.cluster import nearest_centroid
            idx, dist = nearest_centroid(Q, self.centroid_model,
                                         impl=self.impl)
            with tracing.span("search.readback"):
                idx, dist = np.asarray(idx), np.asarray(dist)
            self._pairs_dp += n * self.centroid_model.k
            return idx, dist
        if self.sharded is not None:
            # sharded tier: per-shard cascade + global top-k merge
            # (DESIGN.md §15) — per-stage prune counters and stage spans
            # live inside the shard_map trace, so neither is recorded
            nn, dist = self.sharded.knn(Q)
            with tracing.span("search.readback"):
                return np.asarray(nn), np.asarray(dist)
        if self.mode == "sketch":
            nn, dist, st = self.engine.knn(
                Q, impl=self.impl, mode="sketch", top_c=self.top_c,
                approx=self.approx, return_stats=True)
        else:
            nn, dist, st = self.engine.knn(
                Q, impl=self.impl, seed_k=self.seed_k,
                prefix_frac=self.prefix_frac, return_stats=True)
        for key, name in _DP_COUNTERS:
            if key in st:
                tracing.count(name, st[key])
        with tracing.span("search.readback"):
            nn, dist = np.asarray(nn), np.asarray(dist)
            for k in self._stats_acc:
                self._stats_acc[k] += float(st.get(k, 0.0)) * n
            self._pairs_dp += int(st["dp_pairs"])
        for stage in _SKETCH_STAGES:
            if f"t_{stage}_s" in st:
                self._sketch_lat.setdefault(stage, []).append(
                    float(st[f"t_{stage}_s"]))
        return nn, dist

    def stats(self) -> Dict[str, float]:
        """Aggregated per-stage prune rates over everything served (the
        stage keys only exist in cascade / sketch mode — centroid serving
        runs no bounds, and all-zero prune rates would read as a broken
        cascade), plus per-stage p50/p95/p99 batch latency under
        ``latency_ms``: ``total`` and ``monitor`` from the ``search`` and
        ``search.monitor`` spans still in the recorder's ring, and in
        sketch mode embed / shortlist / re-rank as the tier times them.
        ``trace`` holds the recorder's spans and counter totals."""
        if self._queries == 0:
            return {}
        if self.sharded is not None:
            # per-stage prune counters live inside the shard_map trace;
            # reporting the untouched accumulators would read as a
            # broken cascade, so sharded serving reports the shard story
            out: Dict[str, float] = {
                "n_shards": self.sharded.n_shards,
                "shard_balance": self.sharded.balance()}
        else:
            out = {} if self.mode == "centroid" else \
                {k: v / self._queries for k, v in self._stats_acc.items()}
            out["pairs_dp"] = self._pairs_dp
            out["pre_dp_prune_overall"] = 1.0 - self._pairs_dp / max(
                self._pairs_total, 1)
        out["queries"] = self._queries
        out["pairs_total"] = self._pairs_total
        out["version"] = int(self.engine.version)
        if self.store is not None:
            out["refresh"] = {
                "published_version": int(self.store.version),
                "n_refreshes": self._n_refreshes,
                "mean_lag": self._lag_sum / max(self._lag_n, 1),
                "max_lag": int(self._lag_max)}
        if self.monitor is not None:
            out["monitor"] = self.monitor.counters()
        trace = self._trace.snapshot()
        lat: Dict[str, List[float]] = {}
        for stage, name in _LATENCY_SPANS:
            d = [(sp["end_ns"] - sp["start_ns"]) * 1e-9
                 for sp in trace["spans"] if sp["name"] == name]
            if d:
                lat[stage] = d
        lat.update(self._sketch_lat)
        out["latency_ms"] = {stage: percentiles(v)
                             for stage, v in lat.items()}
        out["trace"] = trace
        return out


def stream_search(engine: SearchEngine, queries: Sequence[np.ndarray],
                  batch: int = 16,
                  arrivals_per_step: Optional[int] = None
                  ) -> List[QueryResult]:
    """Serve a query stream with continuous batching.

    Requests arrive ``arrivals_per_step`` at a time (None = all up front)
    and join the pending queue; each step drains up to ``batch`` of them
    into one cascade call. A request admitted while a step is in flight
    waits for the next step boundary.
    """
    if arrivals_per_step is not None and arrivals_per_step <= 0:
        raise ValueError("arrivals_per_step must be positive (or None for "
                         "all-up-front admission)")
    queries = list(queries)
    n = len(queries)
    pending: deque = deque()
    results: List[QueryResult] = []
    arrived = 0
    step = 0
    while arrived < n or pending:
        # admissions for this step boundary
        take = n - arrived if arrivals_per_step is None else min(
            arrivals_per_step, n - arrived)
        for _ in range(take):
            pending.append((arrived, step))
            arrived += 1
        if not pending:
            step += 1
            continue
        slot = [pending.popleft() for _ in range(min(batch, len(pending)))]
        Q = np.stack([queries[rid] for rid, _ in slot])
        nn, dist = engine.search(Q)
        for row, (rid, sub) in enumerate(slot):
            lab = None if engine.labels is None else int(
                engine.labels[nn[row]])
            results.append(QueryResult(rid=rid, nn=int(nn[row]),
                                       dist=float(dist[row]), label=lab,
                                       submitted_step=sub,
                                       completed_step=step))
        step += 1
    return sorted(results, key=lambda r: r.rid)


def _make_workload(ds, kind: str, n_queries: int, seed: int,
                   with_labels: bool = False):
    """Query stream: "classify" takes test-split series; "retrieval" takes
    warped + renoised corpus entries (the similarity-search case where the
    query has a genuinely close neighbour). ``with_labels`` additionally
    returns the per-query ground-truth labels (classify only — built here
    so they can never drift out of step with the query tiling; None for
    retrieval)."""
    rng = np.random.default_rng(seed)
    if kind == "classify":
        reps = -(-n_queries // len(ds.X_test))
        Q = np.tile(ds.X_test, (reps, 1))[:n_queries]
        if with_labels:
            return Q, np.tile(ds.y_test, reps)[:n_queries]
        return Q
    T = ds.X_train.shape[1]
    src = rng.integers(0, len(ds.X_train), n_queries)
    out = np.empty((n_queries, T), np.float32)
    for i, s in enumerate(src):
        idx = np.sort(np.clip(np.arange(T) + rng.integers(-3, 4, T), 0, T - 1))
        q = ds.X_train[s][idx] + 0.1 * rng.normal(size=T)
        out[i] = (q - q.mean()) / (q.std() + 1e-8)
    return (out, None) if with_labels else out


def run(dataset: str = "CBF", workload: str = "retrieval",
        n_queries: int = 64, batch: int = 16, theta: float = 8.0,
        n_sp_train: int = 32, impl: str = "auto", seed: int = 0,
        arrivals_per_step: Optional[int] = None, check: bool = False,
        n_train: int = 128, centroids: int = 0, gamma: float = 0.1,
        fit_steps: int = 60, T: Optional[int] = None, sketch_r: int = 0,
        top_c: int = 32, approx: bool = False, shards: int = 0) -> dict:
    """Build an engine over a synthetic-UCR corpus and stream a query
    workload through it; returns throughput / prune-rate / accuracy /
    latency-percentile metrics. ``sketch_r > 0`` serves through the
    sketch tier (DESIGN.md §13) with a ``top_c`` shortlist (``approx``
    skips the re-rank). With ``check``, exactness vs the dense path is
    asserted — in sketch mode that is covered-exactness: a full-coverage
    (top_c = corpus) pass must be bit-identical, and the served pass
    reports its measured recall instead. See the CLI flags in ``main``."""
    from repro.data import load
    kw = {} if T is None else {"T": T}
    ds = load(dataset, n_train=n_train, **kw)
    Xtr = jnp.asarray(ds.X_train)
    sp = learn_sparse_paths(Xtr[:n_sp_train], theta=theta)
    model = None
    fit_s = 0.0
    if centroids > 0:
        from repro.cluster import fit_class_centroids
        t0 = time.time()
        model = fit_class_centroids(Xtr, ds.y_train, sp.weights, gamma,
                                    n_per_class=centroids, steps=fit_steps,
                                    impl=impl)
        jax.block_until_ready(model.centroids)
        fit_s = time.time() - t0
    mode = "sketch" if sketch_r > 0 else \
        ("centroid" if centroids > 0 else "cascade")
    engine = SearchEngine(Xtr, ds.y_train, sp=sp, impl=impl,
                          centroid_model=model, mode=mode, seed=seed,
                          sketch_r=sketch_r, top_c=top_c, approx=approx,
                          shards=shards)
    queries, truth = _make_workload(ds, workload, n_queries, seed,
                                    with_labels=True)

    t0 = time.time()
    results = stream_search(engine, queries, batch=batch,
                            arrivals_per_step=arrivals_per_step)
    jax.block_until_ready(engine.index.corpus)
    dt = time.time() - t0

    out = {
        "dataset": dataset, "workload": workload, "backend":
        jax.default_backend(), "n_queries": len(results), "batch": batch,
        "corpus": engine.index.size, "theta": theta,
        "mode": engine.mode,
        "support_cells_frac": sp.n_cells / (ds.T * ds.T),
        "wall_s": dt, "queries_per_s": len(results) / dt,
        "mean_wait_steps": float(np.mean([r.wait_steps for r in results])),
        "stats": engine.stats(),
    }
    if model is not None:
        out["n_centroids"] = model.k
        out["centroid_fit_s"] = fit_s
    if workload == "classify":
        pred = np.array([r.label for r in results])
        out["accuracy"] = float(np.mean(pred == truth))
    if check:
        nn_got = np.array([r.nn for r in results])
        if engine.mode == "sketch":
            dense = np.asarray(engine.measure.cross(
                jnp.asarray(queries), Xtr, block=64))
            nn_true = dense.argmin(1)
            out["recall_at_1"] = float(np.mean(nn_got == nn_true))
            # covered-exactness: with the shortlist covering the whole
            # corpus the sketch path must be bit-identical to argmin
            nn_full, _ = engine.engine.knn(jnp.asarray(queries),
                                           impl=engine.impl, mode="sketch",
                                           top_c=engine.index.size)
            out["exact_match"] = bool((np.asarray(nn_full) == nn_true).all())
            assert out["exact_match"], \
                "full-coverage sketch re-rank diverged from full-Gram 1-NN"
        elif engine.mode == "centroid":
            # nearest-centroid is exact over the *centroid* set (same
            # impl as the engine: float ordering differs across engines)
            Dc = np.asarray(model.distances(jnp.asarray(queries),
                                            impl=engine.impl))
            out["exact_match"] = bool((nn_got == Dc.argmin(1)).all())
            assert out["exact_match"], \
                "engine diverged from brute-force nearest centroid"
        else:
            # exactness: bit-identical neighbours vs the dense full-Gram
            dense = np.asarray(engine.measure.cross(
                jnp.asarray(queries), Xtr, block=64))
            out["exact_match"] = bool((nn_got == dense.argmin(1)).all())
            assert out["exact_match"], \
                "cascade diverged from full-Gram 1-NN"
    return out


def main():
    """CLI entry: ``python -m repro.launch.search [--centroids N]
    [--check] ...`` (serving driver; DESIGN.md §8, §10)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="CBF")
    ap.add_argument("--workload", default="retrieval",
                    choices=("retrieval", "classify"))
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--theta", type=float, default=8.0)
    ap.add_argument("--impl", default="auto")
    ap.add_argument("--arrivals", type=int, default=None,
                    help="arrivals per step (default: all up front)")
    ap.add_argument("--check", action="store_true",
                    help="verify against the dense full-Gram path")
    ap.add_argument("--centroids", type=int, default=0,
                    help="serve nearest-centroid with N centroids per "
                         "class (0 = exact cascade)")
    ap.add_argument("--gamma", type=float, default=0.1,
                    help="soft-SP-DTW temperature for centroid fitting")
    ap.add_argument("--sketch", type=int, default=0, dest="sketch_r",
                    help="serve through the RWS sketch tier with R "
                         "anchors (0 = exact cascade; DESIGN.md §13)")
    ap.add_argument("--top-c", type=int, default=32,
                    help="sketch shortlist size (the recall dial)")
    ap.add_argument("--approx", action="store_true",
                    help="skip the sketch re-rank (fastest, recall-bound)")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard the corpus index over N mesh shards and "
                         "serve through the sharded cascade + global "
                         "top-k merge (0 = single-host; DESIGN.md §15)")
    args = ap.parse_args()
    out = run(args.dataset, args.workload, args.queries, args.batch,
              theta=args.theta, impl=args.impl,
              arrivals_per_step=args.arrivals, check=args.check,
              centroids=args.centroids, gamma=args.gamma,
              sketch_r=args.sketch_r, top_c=args.top_c, approx=args.approx,
              shards=args.shards)
    # the spans are for tools reading stats(), not for the console
    out["stats"].pop("trace", None)
    print(json.dumps(out, indent=1, default=float))
    lat = out["stats"].get("latency_ms", {})
    for stage in ("embed", "shortlist", "rerank", "total"):
        if stage in lat:
            p = lat[stage]
            print(f"latency[{stage:9s}] p50={p['p50']:8.2f}ms "
                  f"p95={p['p95']:8.2f}ms p99={p['p99']:8.2f}ms")


if __name__ == "__main__":
    main()
