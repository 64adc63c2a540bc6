"""Device idle ms per batch while the host is in the survivor DP (span
``cascade.survivor_dp``: the Pallas Gram kernel over the survivors and
the merge of its values), offline. Program span on the device trace."""
from bench import program_spans

UNIT = "ms"


def read(ctx):
    if ctx.loop != "offline":
        return None
    return program_spans.idle_ms_per_batch(ctx, ["cascade.survivor_dp"])
