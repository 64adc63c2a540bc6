"""Device idle ms per batch while the host is in the cascade's bound
stage (span ``cascade.bounds``: banded LB_Kim, LB_Keogh both ways and
the query envelopes), offline. Program span on the device trace."""
from bench import program_spans

UNIT = "ms"


def read(ctx):
    if ctx.loop != "offline":
        return None
    return program_spans.idle_ms_per_batch(ctx, ["cascade.bounds"])
