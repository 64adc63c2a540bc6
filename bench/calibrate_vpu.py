"""Measure the chip's float32 element-op rate on the vector unit.

The survivor DP is min-plus arithmetic, which the matrix unit cannot do,
so its roofline is the vector unit's float32 rate, which is not
published. This kernel measures the unit's best case: independent chains
of ``acc = min(acc + a, b)`` (two element operations) on full (8, 128)
float32 vregs held in VMEM, no HBM traffic inside the timed loop. The
best of a few block shapes and chain counts is the rate.

    python bench/calibrate_vpu.py        # on the chip; prints one JSON line
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ITERS = 1 << 16
GRID = 256


def _kernel(x_ref, o_ref, *, chains: int):
    x = x_ref[...]
    a = x * 0.5
    b = x + 3.0
    accs = tuple(x + float(c) for c in range(chains))

    def body(_, accs):
        return tuple(jnp.minimum(acc + a, b) for acc in accs)

    accs = jax.lax.fori_loop(0, ITERS, body, accs)
    out = accs[0]
    for acc in accs[1:]:
        out = out + acc
    o_ref[...] = out


@functools.partial(jax.jit, static_argnames=("rows", "chains"))
def _run(x, *, rows: int, chains: int):
    return pl.pallas_call(
        functools.partial(_kernel, chains=chains),
        grid=(GRID,),
        in_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((GRID * rows, 128), jnp.float32),
    )(x)


def measure(rows: int, chains: int, reps: int = 3) -> float:
    """Element operations per second of one block shape (best of reps)."""
    x = jnp.ones((GRID * rows, 128), jnp.float32)
    _run(x, rows=rows, chains=chains).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _run(x, rows=rows, chains=chains).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    ops = 2 * ITERS * chains * GRID * rows * 128
    return ops / best


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate_vpu: JAX found no TPU ({dev.platform})",
              file=sys.stderr)
        return 2
    rates = {f"rows{r}_chains{c}": measure(r, c)
             for r in (8, 16, 32, 64) for c in (2, 4, 6, 8)}
    for k, v in rates.items():
        print(f"{k}: {v!r} element-ops/s", flush=True)
    print(json.dumps({"device_kind": dev.device_kind,
                      "vpu_f32_elementops_per_s": max(rates.values()),
                      "best": max(rates, key=rates.get)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
