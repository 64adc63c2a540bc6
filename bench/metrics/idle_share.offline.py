"""Share of the offline window with no operation running on the chip:
1 - (union of operation intervals) / window. Device trace; averaged
over the chips."""
UNIT = "%"


def read(ctx):
    if ctx.loop != "offline" or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
