"""Host ms spent building programs inside the window of an open-loop
cell: the program's ``compile`` spans (each program JAX compiled or
loaded from its cache), clipped to the window and summed; 0 when none
was built. Program spans on the device trace's clock."""
from bench import program_spans
from bench.trace import merge

UNIT = "ms"


def read(ctx):
    if ctx.loop != "open":
        return None
    spans = program_spans.intervals(ctx, ["compile"])
    if spans is None:
        return None
    return 1e-6 * sum(e - s for s, e in merge(spans))
