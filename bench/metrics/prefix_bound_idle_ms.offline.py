"""Device idle ms per batch while the host is in the cascade's prefix
bound (span ``cascade.prefix_bound``: ``gram_prefix_bound`` and the
survivor mask), offline. Program span on the device trace."""
from bench import program_spans

UNIT = "ms"


def read(ctx):
    if ctx.loop != "offline":
        return None
    return program_spans.idle_ms_per_batch(ctx, ["cascade.prefix_bound"])
