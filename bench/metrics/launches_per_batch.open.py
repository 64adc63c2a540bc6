"""Program executions on the chip per served batch, in an open-loop
cell: the eager cascade dispatches one program per operation, a fused
path one. Device trace; averaged over the chips."""
UNIT = "launches"


def read(ctx):
    if ctx.loop != "open" or ctx.trace is None:
        return None
    n = ctx.traced_batches()
    return ctx.trace.launches() / n if n else None
