"""Benchmark of exact SP-DTW 1-NN serving on a TPU, one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout. A cell is ``bench/workloads/<cell>.json``:
it names a configuration (``bench/configs/<config>.json``: the deployment's
sizes and support-learning settings), a traffic mix
(``bench/traffic/<traffic>.json``: the loop, the query source, the batch and,
for an open loop, the rate) and the chips. The loop itself is
``bench/traffic/<loop>.py``; each per-layer metric is read by
``bench/metrics/<metric>.py``. Adding a cell, a configuration, a mix or a
metric adds files and edits none.

A run: generates the corpus and queries from ``--seed``, learns the support
and fits ``repro.launch.search.SearchEngine`` (``shards`` = the cell's chips),
serves one batch twice to load every program of the batch shape, and times
that as ``setup_s``. Then it drives the loop for ``--seconds`` through
``SearchEngine.search``. With ``--trace 0`` it prints the end-to-end
metrics; with ``--trace 1`` it traces the same window with the profiler and
prints the per-layer metrics. After the window it frees the program and
checks a seeded sample of the served answers against the plain reference
(``bench/check.py``). The last line of stdout is one JSON object; the last
lines of stderr are the numbers compared with their limits.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs, or when the program cannot be imported.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import check, data  # noqa: E402


def _read_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's file with its configuration and traffic mix inlined."""
    cell = _read_json("workloads", f"{name}.json")
    cell["name"] = name
    cell["config"] = _read_json("configs", f"{cell['config']}.json")
    cell["traffic"] = _read_json("traffic", f"{cell['traffic']}.json")
    return cell


def rng(seed: int, stream: int) -> np.random.Generator:
    """One independent generator per purpose, all from the run's seed."""
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def _loop(loop: str):
    return importlib.import_module(f"bench.traffic.{loop}").drive


def _readers() -> Dict[str, object]:
    """Every per-layer reader under ``bench/metrics``, by metric name."""
    out = {}
    mdir = os.path.join(BENCH, "metrics")
    for fn in sorted(os.listdir(mdir)):
        if fn.endswith(".py") and not fn.startswith("_"):
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{len(out)}", os.path.join(mdir, fn))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            out[fn[:-3]] = mod
    return out


class Context:
    """What a per-layer reader may read: the reduced trace (None when the
    run was not traced or the trace holds no chip), the cell and the
    program's own counters."""

    def __init__(self, cell, trace, stats, n_cells, device_kind):
        self.cell = cell
        self.loop = cell["traffic"]["loop"]
        self.trace = trace
        self.stats = stats
        self.n_cells = n_cells
        self.device_kind = device_kind

    def traced_batches(self) -> int:
        from bench.trace import SEARCH_SPAN
        return len(self.trace.spans_named(SEARCH_SPAN)) if self.trace else 0


def build(cell: dict, seed: int):
    """Data, support and the fitted engine: (serve, state, ds, support
    series, query pool). ``state`` holds every program object, so that
    dropping it frees the program."""
    import jax.numpy as jnp
    from repro.core import learn_sparse_paths
    from repro.launch.search import SearchEngine
    cfg, trf = cell["config"], cell["traffic"]
    ds = data.make_cbf(cfg["n_train"], cfg["n_test"], cfg["T"], rng(seed, 0))
    sub = rng(seed, 1).choice(cfg["n_train"], cfg["support"]["n_series"],
                              replace=False)
    support_series = ds.X_train[np.sort(sub)]
    sp = learn_sparse_paths(jnp.asarray(support_series),
                            theta=cfg["support"]["theta"])
    se = SearchEngine(ds.X_train, ds.y_train, sp=sp, impl="auto",
                      seed_k=cfg["serving"]["seed_k"],
                      prefix_frac=cfg["serving"]["prefix_frac"],
                      shards=int(cell["shards"]))
    if int(cell["shards"]) > 1 and se.sharded.path != "mesh":
        raise SystemExit(f"run: sharded serving took the {se.sharded.path!r}"
                         " path, not 'mesh'")
    batch = int(trf["batch"])
    if trf["loop"] == "open":
        n_pool = max(1, int(round(trf["rate_qps"] * cell["seconds"])))
    else:
        n_pool = max(batch, (cfg["n_test"] // batch) * batch)
    queries = data.QUERY_SOURCES[trf["queries"]](ds, n_pool, rng(seed, 2))
    return se.search, {"engine": se, "sp": sp}, ds, support_series, queries


class _ProgramBuilds:
    """Counts the programs JAX builds (compiles or loads from the cache)
    while the context is open: none is expected inside the window."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        self.count = 0
        self._open = False

    def _on(self, event, **kwargs):
        if self._open and event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_listener(self._on)
        self._open = True
        return self

    def __exit__(self, *exc):
        import jax
        self._open = False
        jax.monitoring.unregister_event_listener(self._on)


def _peak_bytes(n_chips: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:n_chips]:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, serve_wrap: Optional[Callable] = None) -> dict:
    """One run of a cell after the device check: the result object.

    ``serve_wrap`` wraps the served call; the tests use it to break the
    timed path and see ``correct`` come out false."""
    import jax
    from jax.profiler import TraceAnnotation
    cell = dict(cell, seconds=seconds)
    trf = cell["traffic"]
    serve, state, ds, support_series, queries = build(cell, seed)
    if serve_wrap is not None:
        serve = serve_wrap(serve)
    batch = int(trf["batch"])
    for _ in range(2):                       # load every program, then once
        serve(queries[:batch])               # more from the warm state
    state["engine"].reset_stats()
    n_cells = state["sp"].n_cells
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        # host events at level 1 keep the benchmark's own spans and drop
        # the runtime's; the Python tracer would add an event per call
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    builds = _ProgramBuilds()
    setup_s = time.perf_counter() - t_start
    try:
        with TraceAnnotation("bench.window"), builds:
            served = _loop(trf["loop"])(serve, queries, trf, seconds,
                                          rng(seed, 3), TraceAnnotation)
    finally:
        if trace:
            jax.profiler.stop_trace()
    print(f"window programs_built={builds.count}", file=sys.stderr,
          flush=True)
    stats = state["engine"].stats()
    memory_peak = _peak_bytes(int(cell["chips"]))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    del serve, state
    gc.collect()
    jax.clear_caches()

    out: Dict[str, object] = {}
    if trace:
        from bench import trace as tr
        reduced = tr.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = Context(cell, reduced, stats, n_cells, dev.device_kind)
        metrics = {}
        for name, mod in _readers().items():
            value = mod.read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
        if reduced is not None:
            device["busy_s"] = reduced.busy_s()
            device["window_s"] = reduced.window_s
            out["breakdown"] = {"device_ops": reduced.top_modules(),
                                "idle_gaps": reduced.idle_gaps()}
    else:
        metrics = end_to_end(served, trf)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    numbers = check.compare(served, ds.X_train, support_series,
                            cell["config"]["support"]["theta"], rng(seed, 4))
    invalid = int(np.sum((served.nn < 0) | (served.nn >= len(ds.X_train))
                         | ~np.isfinite(served.dist)))
    return {"correct": check.passed(numbers) and invalid == 0,
            "attempted": int(len(served.nn)), "failed": invalid,
            "metrics": metrics, "device": device, **out,
            "check": numbers}


def end_to_end(served, traffic: dict) -> Dict[str, dict]:
    """The cell's end-to-end metrics from what the window served."""
    from bench.stats import percentile_ms
    if traffic["loop"] == "open":
        out = {}
        for name, pct in (("latency_p95_ms", 95), ("latency_p50_ms", 50)):
            v = percentile_ms(served.latency_s, pct)
            if v is not None:
                out[name] = {"value": v, "unit": "ms"}
        return out
    return {"throughput_qps": {"value": len(served.nn) / served.elapsed_s,
                               "unit": "q/s"}}


def _device_check(chips: int) -> Optional[str]:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"JAX found no TPU (platform {devs[0].platform!r})"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devs)}"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}",
          file=sys.stderr, flush=True)
    fault = _device_check(int(cell["chips"]))
    if fault:
        print(f"run: {fault}", file=sys.stderr, flush=True)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START)
    for name, v in result["check"].items():
        print(f"check {name}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
