"""Device time of the cascade's prefix-DP bound per batch, offline: the
summed durations of ``gram_block._gram_prefix_bound_call`` (an XLA scan
over the first tile rows) in the trace, over the batches."""
UNIT = "ms"
PROGRAM = r"_gram_prefix_bound_call"


def read(ctx):
    if ctx.loop != "offline" or ctx.trace is None:
        return None
    n = ctx.traced_batches()
    t = ctx.trace.module_time_s(PROGRAM)
    return 1e3 * t / n if n and t > 0 else None
