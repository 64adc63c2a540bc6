"""Device idle ms per batch while the host is in the cascade's seed
stage (span ``cascade.seed_dp``: ``top_k`` of the bounds, the seed pair
DP, the thresholds and the first survivor mask), offline. Program span
on the device trace."""
from bench import program_spans

UNIT = "ms"


def read(ctx):
    if ctx.loop != "offline":
        return None
    return program_spans.idle_ms_per_batch(ctx, ["cascade.seed_dp"])
