"""Device idle ms per batch while the host selects and reads back the
answers (spans ``cascade.select``: argmin and the prune counts, and
``search.readback``: the answers and counts fetched to the host),
offline. Program spans on the device trace."""
from bench import program_spans

UNIT = "ms"


def read(ctx):
    if ctx.loop != "offline":
        return None
    return program_spans.idle_ms_per_batch(
        ctx, ["cascade.select", "search.readback"])
