"""The readers of the program's own spans and counters, on a synthetic
trace and a synthetic ``SearchEngine.stats()["trace"]``."""
import importlib.util
import os

import pytest

from bench import program_spans
from bench.run import Context
from bench.trace import Device, Trace

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
US = 1000                   # ns
EPOCH = 1_700_000_000_000_000_000   # the program's clock at trace time 0


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(name, s_us, e_us, **attrs):
    return {"name": name, "start_ns": EPOCH + s_us * US,
            "end_ns": EPOCH + e_us * US, "id": 0, "parent": 0,
            "attrs": attrs}


# Window 100-1000 us. Two batches: bench.search 150-450 and 550-950, the
# program's search spans 160-440 and 560-940. Each batch: bounds on the
# host for 100 us, then the survivor DP. Chip 0 runs 200-250, 300-420,
# 600-900; chip 1 runs 160-440 and 560-940 (never idle inside search).
# A compile straddles the window's start.
BATCHES = [(160, 440), (560, 940)]
SPANS = [_span("compile", 50, 130, program="jit(x)")]
for s, e in BATCHES:
    SPANS += [_span("search", s, e, n=16, mode="cascade"),
              _span("cascade.bounds", s + 10, s + 110),
              _span("cascade.survivor_dp", s + 110, e - 20,
                    block_pairs=1024),
              _span("search.readback", e - 20, e - 5)]
STATS = {"trace": {"spans": SPANS,
                   "counters": {"survivor_dp.tile_sweeps": 40,
                                "survivor_dp.alive_pair_sweeps": 10240}}}
DEV0 = Device(ops=[(200 * US, 250 * US, "a"), (300 * US, 420 * US, "b"),
                   (600 * US, 900 * US, "c")], modules=[])
DEV1 = Device(ops=[(160 * US, 440 * US, "a"), (560 * US, 940 * US, "b")],
              modules=[])


def _ctx(loop, stats=STATS, devices=(DEV0, DEV1)):
    trace = Trace(window=(100 * US, 1000 * US), devices=list(devices),
                  spans=[(100 * US, 1000 * US, "bench.window"),
                         (150 * US, 450 * US, "bench.search"),
                         (550 * US, 950 * US, "bench.search")])
    traffic = {"loop": loop}
    return Context({"traffic": traffic}, trace, stats, 0, "TPU v5 lite")


def test_start_is_bounded_by_the_search_pairs():
    # program search starts 10 us after bench.search and ends 10 us
    # before it, in both batches: the bound is [EPOCH - 10us, EPOCH + 10us]
    assert program_spans.trace_start_ns(_ctx("offline")) == EPOCH


def test_unpaired_searches_map_nothing():
    stats = {"trace": {"spans": SPANS[:-4], "counters": {}}}
    assert program_spans.trace_start_ns(_ctx("offline", stats)) is None
    assert _reader("bounds_idle_ms.offline").read(
        _ctx("offline", stats)) is None


def test_idle_inside_spans_per_batch_averaged_over_chips():
    # bounds 170-270 and 570-670; chip 0 busy 200-250 and 600-670 there,
    # so idle 50 + 30 = 80 us; chip 1 idle 0; mean 40 us over 2 batches
    got = _reader("bounds_idle_ms.offline").read(_ctx("offline"))
    assert got == pytest.approx(40 * US * 1e-6 / 2)
    # survivor DP 270-420 and 670-920: chip 0 idle 270-300, 900-920
    got = _reader("survivor_dp_idle_ms.offline").read(_ctx("offline"))
    assert got == pytest.approx(25 * US * 1e-6 / 2)
    # readback 420-435, 920-935 (no select spans): chip 0 idle 15 + 15
    got = _reader("readback_idle_ms.offline").read(_ctx("offline"))
    assert got == pytest.approx(15 * US * 1e-6 / 2)
    # no prefix-bound or seed spans were recorded: no idle inside them
    assert _reader("prefix_bound_idle_ms.offline").read(
        _ctx("offline")) == 0.0
    assert _reader("seed_dp_idle_ms.offline").read(_ctx("offline")) == 0.0


def test_spans_are_clipped_to_the_window():
    # the compile span runs 50-130 us; only 100-130 lies in the window
    assert _reader("compile_ms.open").read(_ctx("open")) == \
        pytest.approx(30 * US * 1e-6)


def test_fill_reads_the_counters():
    assert _reader("survivor_dp_fill.offline").read(_ctx("offline")) == \
        pytest.approx(100.0 * 10240 / (40 * 1024))


@pytest.mark.parametrize("name,loop", [
    ("bounds_idle_ms.offline", "open"), ("seed_dp_idle_ms.offline", "open"),
    ("prefix_bound_idle_ms.offline", "open"),
    ("survivor_dp_idle_ms.offline", "open"),
    ("readback_idle_ms.offline", "open"),
    ("survivor_dp_fill.offline", "open"), ("compile_ms.open", "offline")])
def test_readers_are_silent_in_the_other_loop(name, loop):
    assert _reader(name).read(_ctx(loop)) is None


@pytest.mark.parametrize("name,loop", [
    ("bounds_idle_ms.offline", "offline"),
    ("survivor_dp_fill.offline", "offline"), ("compile_ms.open", "open")])
def test_readers_are_silent_without_program_spans(name, loop):
    assert _reader(name).read(_ctx(loop, stats={"queries": 32})) is None
