"""The control: the plain reference in bfloat16 put in the program's place.

    python bench/control.py --workload <cell> --seeds 1,2,3

For each seed, draws the cell's corpus, support series and a check-sized
sample of its queries as a run does, answers them with the reference in
bfloat16 (the precision below the configuration's float32), and prints
the ``answer_err`` the check would read for those answers against the
float32 reference. A sound limit lies below every one of these readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import numpy as np  # noqa: E402

from bench import check, data, reference, run  # noqa: E402


def control_err(cell: dict, seed: int) -> float:
    """``answer_err`` of the bfloat16 reference's answers on one seed."""
    import jax.numpy as jnp
    cfg, trf = cell["config"], cell["traffic"]
    ds = data.make_cbf(cfg["n_train"], cfg["n_test"], cfg["T"],
                       run.rng(seed, 0))
    sub = run.rng(seed, 1).choice(cfg["n_train"], cfg["support"]["n_series"],
                                  replace=False)
    support = reference.learn_support(ds.X_train[np.sort(sub)],
                                      cfg["support"]["theta"])
    Q = data.QUERY_SOURCES[trf["queries"]](ds, check.SAMPLE,
                                           run.rng(seed, 2))
    D32 = reference.distances(Q, ds.X_train, support)
    D16 = reference.distances(Q, ds.X_train, support, dtype=jnp.bfloat16)
    nn = D16.argmin(axis=1)
    return check.answer_err(nn, D16[np.arange(len(nn)), nn], D32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run.ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    fault = run._device_check(int(cell["chips"]))
    if fault:
        print(f"control: {fault}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        err = control_err(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_answer_err": err,
                          "limit": check.LIMITS["answer_err"],
                          "seconds": time.perf_counter() - t0,
                          "device": jax.devices()[0].device_kind}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
