"""Share of the cells the survivor DP kernel sweeps that lie in the
learned support, offline: 100 x support cells / (active tiles of the
plan (``plan_tiles``) x the tile's cells (``tile`` squared)), the
attributes of the ``cascade.survivor_dp`` spans. Where
``survivor_dp_fill.offline`` reads the pair lanes, this reads the cells
of each tile; between them they split the kernel's roofline gap into
dead pair lanes and swept cells outside the support."""
from bench import program_spans

UNIT = "%"


def read(ctx):
    rec = program_spans.recorded(ctx)
    if ctx.loop != "offline" or rec is None:
        return None
    plans = {(s["attrs"].get("tile"), s["attrs"].get("plan_tiles"))
             for s in rec["spans"] if s["name"] == "cascade.survivor_dp"}
    if len(plans) != 1:
        return None
    tile, plan_tiles = plans.pop()
    if not tile or not plan_tiles:
        return None
    return 100.0 * ctx.n_cells / (plan_tiles * tile * tile)
