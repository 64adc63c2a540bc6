"""The survivor DP kernel's share of its roofline, offline.

Operations and bytes of the algorithm (``bench/roofline.py``): the
cascade's survivor pairs (``pairs_dp`` less the seed pairs, from
``SearchEngine.stats()``) times the learned support's cell count times
3d + 3, against the VPU's measured float32 rate and the HBM bandwidth in
``bench/peaks.json``. Time: the kernel's device time in the trace."""

from bench import roofline

UNIT = "%"
PROGRAM = r"_gram_spdtw_call"


def read(ctx):
    if ctx.loop != "offline" or ctx.trace is None or \
            "pairs_dp" not in ctx.stats:
        return None
    t = ctx.trace.module_time_s(PROGRAM)
    n_batches = ctx.traced_batches()
    if t <= 0 or not n_batches:
        return None
    cfg, trf = ctx.cell["config"], ctx.cell["traffic"]
    ops = roofline.survivor_ops(ctx.stats["pairs_dp"], ctx.stats["queries"],
                                cfg["serving"]["seed_k"], ctx.n_cells, cfg["d"])
    nbytes = roofline.survivor_bytes(n_batches, int(trf["batch"]),
                                     cfg["n_train"], cfg["T"], cfg["d"])
    r = roofline.roofline_share(ops, nbytes, t, ctx.device_kind)
    return None if r is None else r["share"]
