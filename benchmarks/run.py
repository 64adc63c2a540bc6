"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure + the kernel wall-clock micro-bench +
the search-cascade bench. Prints a final ``name,us_per_call,derived`` CSV
summary per the harness contract.

Full-protocol runs: ``python -m benchmarks.run --full`` (slower, bigger
test splits). ``--smoke`` runs tiny shapes in seconds — a CI-grade sanity
sweep of the kernel walltime, fused-Gram, cascade and centroid benches
(the paper tables are skipped; smoke runs never overwrite the committed
BENCH_*.json artifacts, and their per-bench artifacts go to a tempdir by
default so a CI run can never dirty the tree). Artifacts land in
artifacts/bench/*.json.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

_DEFAULT_ART = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                            "bench")
ART = _DEFAULT_ART


def bench_kernel_walltime(B: int = 64, T: int = 128):
    """Wall-clock of the batched DP paths on CPU (jnp reference backend):
    full vs corridor vs learned-sparse, same pair batch."""
    import jax
    import jax.numpy as jnp
    from repro.core import learn_sparse_paths
    from repro.kernels import ref

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    Xtr = jnp.asarray((base[None] + 0.3 * rng.normal(size=(12, T))
                       ).astype(np.float32))
    sp = learn_sparse_paths(Xtr, theta=1.0)

    out = {}
    for name, fn in [
        ("dtw_full", lambda: ref.dtw_batch(x, y)),
        ("dtw_sc_r8", lambda: ref.dtw_band_batch(x, y, 8)),
        ("spdtw", lambda: ref.wdtw_batch(x, y, sp.weights)),
        ("log_krdtw", lambda: ref.log_krdtw_batch(x, y, 0.5)),
        ("sp_log_krdtw",
         lambda: ref.log_krdtw_masked_batch(x, y, 0.5, sp.support)),
    ]:
        fn()  # compile
        t0 = time.time()
        reps = 3
        for _ in range(reps):
            jax.block_until_ready(fn())
        out[name] = (time.time() - t0) / reps / B * 1e6  # us per pair
    out["sp_cells_fraction"] = sp.n_cells / (T * T)
    return out


def bench_engine_dispatch(B: int = 16, T: int = 64, reps: int = 15):
    """Engine-dispatch overhead micro-check (DESIGN.md §12).

    The fitted-engine redesign claims zero dispatch overhead: a
    fit-once ``SimilarityEngine.gram`` loop must not be measurably
    slower than the per-call module-level path that re-resolves
    ``weights -> plan`` every call (both hit the same cached resolver
    and the same execute kernel). Gated: the median-timed fit-once /
    per-call ratio must stay under 1.5x — this is what keeps the API
    redesign honest in CI.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import learn_sparse_paths
    from repro.core.engine import fit
    from repro.core.spec import MeasureSpec
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    Xtr = (base[None] + 0.3 * rng.normal(size=(12, T))).astype(np.float32)
    sp = learn_sparse_paths(jnp.asarray(Xtr), theta=1.0)
    Q = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(B, T)).astype(np.float32))
    engine = fit(MeasureSpec("spdtw"), sp=sp, T=T)

    def per_call():
        return ops._spdtw_gram(Q, C, weights=sp.weights)

    def fit_once():
        return engine.gram(Q, C)

    def median_time(fn):
        jax.block_until_ready(fn())            # compile + warm the caches
        ts = []
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(fn())
            ts.append(time.time() - t0)
        return float(np.median(ts))

    t_call = median_time(per_call)
    t_fit = median_time(fit_once)
    ratio = t_fit / t_call
    out = {"per_call_us": t_call * 1e6, "fit_once_us": t_fit * 1e6,
           "overhead_ratio": ratio, "ok": bool(ratio < 1.5)}
    assert out["ok"], (
        f"engine dispatch overhead {ratio:.2f}x vs per-call resolution "
        f"— the fit-once API must stay zero-overhead")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size dataset splits (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, seconds not minutes (CI sanity)")
    ap.add_argument("--skip", default="",
                    help="comma-separated benches to skip")
    ap.add_argument("--out", default=None,
                    help="artifact directory override (CI points smoke "
                         "runs here so the JSONs can be uploaded as "
                         "workflow artifacts; default: artifacts/bench, "
                         "or a fresh tempdir with --smoke)")
    args, _ = ap.parse_known_args(argv)
    fast = not args.full
    smoke = args.smoke
    skip = set(args.skip.split(",")) if args.skip else set()
    art = args.out if args.out is not None else ART
    if smoke and os.path.abspath(art) == os.path.abspath(_DEFAULT_ART):
        # repo hygiene: smoke artifacts never land in the tree (CI runs
        # must leave the checkout clean); monkeypatching ART redirects
        art = tempfile.mkdtemp(prefix="bench-smoke-")
    os.makedirs(art, exist_ok=True)

    results = {}
    timings = {}

    def run_bench(name, fn):
        if name in skip:
            return
        print(f"\n================ {name} ================", flush=True)
        t0 = time.time()
        results[name] = fn()
        timings[name] = time.time() - t0
        with open(os.path.join(art, f"{name}.json"), "w") as f:
            json.dump(results[name], f, indent=1, default=str)

    from . import anomaly_roc, prune_depth, search_cascade, sketch_recall
    if smoke:
        # tiny shapes end to end: kernels, fused Gram, cascade, centroid;
        # the paper tables (minutes of meta-parameter search) are skipped
        from . import centroid_speedup, gram_speedup, softgrad_speedup
        run_bench("kernel_walltime", lambda: bench_kernel_walltime(B=8, T=32))
        run_bench("engine_dispatch", lambda: bench_engine_dispatch(B=8, T=32))
        run_bench("gram_speedup",
                  lambda: gram_speedup.run(fast=True, smoke=True))
        run_bench("search_cascade",
                  lambda: search_cascade.run(fast=True, smoke=True))
        run_bench("prune_depth",
                  lambda: prune_depth.run(fast=True, smoke=True))
        run_bench("sketch_recall",
                  lambda: sketch_recall.run(fast=True, smoke=True))
        run_bench("anomaly_roc",
                  lambda: anomaly_roc.run(fast=True, smoke=True))
        run_bench("centroid_speedup",
                  lambda: centroid_speedup.run(fast=True, smoke=True))
        run_bench("softgrad_speedup",
                  lambda: softgrad_speedup.run(fast=True, smoke=True))
    else:
        run_bench("kernel_walltime", bench_kernel_walltime)
        run_bench("engine_dispatch", bench_engine_dispatch)

        from . import (centroid_speedup, gram_speedup, occupancy_fig,
                       softgrad_speedup, table2_knn, table4_svm,
                       table6_speedup)
        run_bench("gram_speedup", lambda: gram_speedup.run(fast=fast))
        run_bench("search_cascade", lambda: search_cascade.run(fast=fast))
        run_bench("prune_depth", lambda: prune_depth.run(fast=fast))
        run_bench("sketch_recall", lambda: sketch_recall.run(fast=fast))
        run_bench("anomaly_roc", lambda: anomaly_roc.run(fast=fast))
        run_bench("centroid_speedup", lambda: centroid_speedup.run(fast=fast))
        run_bench("softgrad_speedup", lambda: softgrad_speedup.run(fast=fast))
        run_bench("table6_speedup", lambda: table6_speedup.run(fast=fast))
        run_bench("table2_knn", lambda: table2_knn.run(fast=fast))
        run_bench("table4_svm", lambda: table4_svm.run(fast=fast))
        run_bench("occupancy_fig", lambda: occupancy_fig.run(fast=fast))

    # ---- harness contract: name,us_per_call,derived ----
    print("\nname,us_per_call,derived")
    kw = results.get("kernel_walltime", {})
    for k, v in kw.items():
        if k.endswith("fraction"):
            continue
        print(f"kernel/{k},{v:.1f},us_per_pair")
    if "engine_dispatch" in results:
        e = results["engine_dispatch"]
        print(f"engine/fit_once,{e['fit_once_us']:.1f},"
              f"{e['overhead_ratio']:.2f}x_vs_per_call")
    if "gram_speedup" in results:
        g = results["gram_speedup"]
        print(f"gram/dense,{g['dense_us_per_pair']:.1f},us_per_pair")
        print(f"gram/fused,{g['fused_us_per_pair']:.1f},us_per_pair")
        print(f"gram/speedup,{g['fused_us_per_pair']:.1f},"
              f"{g['speedup']:.2f}x")
    if "search_cascade" in results:
        for wl, r in results["search_cascade"]["workloads"].items():
            print(f"search/{wl}/cascade,{r['cascade_us_per_query']:.1f},"
                  f"us_per_query")
            print(f"search/{wl}/pre_dp_prune,"
                  f"{r['cascade_us_per_query']:.1f},"
                  f"{100*r['pre_dp_prune']:.0f}%")
    if "prune_depth" in results:
        p = results["prune_depth"]
        tight = p["sweep"][-1]
        print(f"prune/dp_cell_frac,{100*tight['dp_cell_frac']:.1f},"
              f"pct_of_grid_at_alpha{tight['alpha']}")
        print(f"prune/static_support,{100*p['static_support_frac']:.1f},"
              f"pct_of_grid")
    if "sketch_recall" in results:
        s = results["sketch_recall"]
        b = s["best"]
        print(f"sketch/cascade,{s['cascade']['us_per_query']:.1f},"
              f"us_per_query")
        print(f"sketch/best,{b['us_per_query']:.1f},"
              f"{b['speedup']:.2f}x_recall{b['recall_at_1']:.2f}")
    if "anomaly_roc" in results:
        a = results["anomaly_roc"]
        print(f"anomaly/roc_auc,{timings.get('anomaly_roc', 0)*1e6:.0f},"
              f"{a['roc_auc']:.3f}")
        print(f"anomaly/escalation,{timings.get('anomaly_roc', 0)*1e6:.0f},"
              f"{100*a['escalation_rate']:.0f}%")
        print(f"anomaly/p99_overhead,{1e3*a['p99_overhead_ms']:.0f},"
              f"{a['p99_overhead_ratio']:.2f}x")
    if "centroid_speedup" in results:
        for fam, r in results["centroid_speedup"]["families"].items():
            print(f"centroid/{fam},{r['centroid_us_per_query']:.1f},"
                  f"{r['speedup']:.2f}x")
            print(f"centroid/{fam}/acc_delta,"
                  f"{r['centroid_us_per_query']:.1f},"
                  f"{100*r['acc_delta']:.1f}pts")
    if "table6_speedup" in results:
        avg = results["table6_speedup"]["average_speedup"]
        for k, v in avg.items():
            print(f"table6/{k},{timings.get('table6_speedup', 0)*1e6:.0f},"
                  f"{v:.1f}")
    if "table2_knn" in results:
        for m, r in results["table2_knn"]["mean_rank"].items():
            print(f"table2/mean_rank/{m},"
                  f"{timings.get('table2_knn', 0)*1e6:.0f},{r:.2f}")
    if "table4_svm" in results:
        for m, r in results["table4_svm"]["mean_rank"].items():
            print(f"table4/mean_rank/{m},"
                  f"{timings.get('table4_svm', 0)*1e6:.0f},{r:.2f}")
    print(f"\nall benchmark artifacts: {os.path.join(art, '*.json')}")


if __name__ == "__main__":
    main()
