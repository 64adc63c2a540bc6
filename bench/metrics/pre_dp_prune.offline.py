"""Share of (query, corpus) pairs the cascade's bounds settle before any
DP, offline: ``SearchEngine.stats()["pre_dp_prune"]`` over the window,
the program's own count ratio."""
UNIT = "%"


def read(ctx):
    if ctx.loop != "offline" or "pre_dp_prune" not in ctx.stats:
        return None
    return 100.0 * float(ctx.stats["pre_dp_prune"])
