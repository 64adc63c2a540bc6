"""Share of the time inside ``SearchEngine.search`` calls with no
operation running on the chip, in an open-loop cell: the serving
shell's host orchestration as the chip sees it. Device trace; averaged
over the chips."""
UNIT = "%"


def read(ctx):
    if ctx.loop != "open" or ctx.trace is None:
        return None
    from bench.trace import SEARCH_SPAN
    share = ctx.trace.idle_share_in(ctx.trace.spans_named(SEARCH_SPAN))
    return None if share is None else 100.0 * share
