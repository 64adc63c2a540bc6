"""Device time of the survivor DP kernel per batch, offline: the summed
durations of the Pallas Gram kernel's program
(``gram_block._gram_spdtw_call``) in the trace, over the batches."""
UNIT = "ms"
PROGRAM = r"_gram_spdtw_call"


def read(ctx):
    if ctx.loop != "offline" or ctx.trace is None:
        return None
    n = ctx.traced_batches()
    t = ctx.trace.module_time_s(PROGRAM)
    return 1e3 * t / n if n and t > 0 else None
