"""Find the highest rate an open-loop cell sustains, on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 16,20,24,28

Builds the cell once, measures its offline capacity (back-to-back full
batches), then offers each rate for ``--seconds`` through the cell's own
open loop and prints one JSON line per rate: p50 and p95 latency,
and the mean latency of the last tenth of the requests against the first
tenth (a backlog that grows through the window shows as a ratio well
above 1). The rate written into the cell's traffic file is about four
fifths of the highest rate whose backlog does not grow.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    cell = run.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run.ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation
    from bench.stats import percentile_ms
    from bench.traffic import offline, open as open_loop
    fault = run._device_check(int(cell["chips"]))
    if fault:
        print(f"sweep: {fault}", file=sys.stderr)
        return 2
    trf = dict(cell["traffic"])
    cell = dict(cell, seconds=max(rates) * args.seconds)
    serve, state, _, _, queries = run.build(cell, args.seed)
    batch = int(trf["batch"])
    for _ in range(2):
        serve(queries[:batch])
    cap = offline.drive(serve, queries, trf, args.seconds,
                        run.rng(args.seed, 5), TraceAnnotation)
    print(json.dumps({"offline_capacity_qps": len(cap.nn) / cap.elapsed_s,
                      "batch_s": cap.elapsed_s / cap.n_batches}), flush=True)
    for rate in rates:
        t = dict(trf, rate_qps=rate)
        s = open_loop.drive(serve, queries, t, args.seconds,
                            run.rng(args.seed, 3), TraceAnnotation)
        k = max(1, len(s.latency_s) // 10)
        print(json.dumps({
            "rate_qps": rate, "requests": len(s.latency_s),
            "p50_ms": percentile_ms(s.latency_s, 50),
            "p95_ms": percentile_ms(s.latency_s, 95),
            "max_ms": 1e3 * float(np.max(s.latency_s)),
            "last_over_first_tenth": float(np.mean(s.latency_s[-k:])
                                           / np.mean(s.latency_s[:k])),
            "batches": s.n_batches}), flush=True)
    del state
    jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
