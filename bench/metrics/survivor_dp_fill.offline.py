"""Share of the pair lanes the survivor DP kernel sweeps that do useful
work, offline: 100 x live pairs summed over the tile sweeps run
(``survivor_dp.alive_pair_sweeps``) / (tile sweeps run
(``survivor_dp.tile_sweeps``) x pairs in the kernel's block, the
``block_pairs`` of the ``cascade.survivor_dp`` spans). The program's
own counters over the window."""
from bench import program_spans

UNIT = "%"


def read(ctx):
    rec = program_spans.recorded(ctx)
    if ctx.loop != "offline" or rec is None:
        return None
    sweeps = rec["counters"].get("survivor_dp.tile_sweeps")
    live = rec["counters"].get("survivor_dp.alive_pair_sweeps")
    blocks = {s["attrs"].get("block_pairs") for s in rec["spans"]
              if s["name"] == "cascade.survivor_dp"}
    if not sweeps or live is None or len(blocks) != 1 or None in blocks:
        return None
    return 100.0 * live / (sweeps * blocks.pop())
