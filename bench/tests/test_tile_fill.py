"""The reader of the survivor DP plan's cell fill,
``survivor_dp_tile_fill.offline``, on a synthetic
``SearchEngine.stats()["trace"]``: the spans of
``test_program_spans`` with the plan of FordA at seed 1292441283 (32
tiles of 64 x 64 over 32,389 support cells) on the survivor DP spans."""
import pytest

from bench.run import Context
from bench.tests.test_program_spans import SPANS, STATS, _reader

N_CELLS = 32389
READER = "survivor_dp_tile_fill.offline"


def _stats(*plans):
    """STATS with the survivor DP spans' attributes replaced, one entry
    of ``plans`` per span in turn (the last repeats)."""
    spans, k = [], 0
    for s in SPANS:
        if s["name"] == "cascade.survivor_dp":
            s = dict(s, attrs=plans[min(k, len(plans) - 1)])
            k += 1
        spans.append(s)
    return {"trace": dict(STATS["trace"], spans=spans)}


PLAN = {"block_pairs": 1024, "tile": 64, "plan_tiles": 32}


def _ctx(loop, stats):
    return Context({"traffic": {"loop": loop}}, None, stats, N_CELLS,
                   "TPU v5 lite")


def test_tile_fill_reads_the_plan_attributes():
    got = _reader(READER).read(_ctx("offline", _stats(PLAN)))
    assert got == pytest.approx(100.0 * N_CELLS / (32 * 64 * 64))
    assert got == pytest.approx(24.71, abs=0.01)


@pytest.mark.parametrize("plans", [
    ({"block_pairs": 1024},),               # a program without the plan
    (PLAN, dict(PLAN, plan_tiles=31)),      # spans that disagree
    (dict(PLAN, plan_tiles=0),),            # an empty plan
], ids=["no-plan", "plans-disagree", "empty-plan"])
def test_tile_fill_is_silent_without_one_plan(plans):
    assert _reader(READER).read(_ctx("offline", _stats(*plans))) is None


def test_tile_fill_is_silent_in_the_open_loop():
    assert _reader(READER).read(_ctx("open", _stats(PLAN))) is None


def test_tile_fill_is_silent_without_program_spans():
    assert _reader(READER).read(_ctx("offline", {"queries": 32})) is None
