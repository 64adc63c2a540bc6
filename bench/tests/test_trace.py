"""The trace reduction, on a trace built to known numbers and on a small
trace recorded on a v5e (``bench/testdata``)."""
import gzip
import os

import pytest
from jax.profiler import ProfileData

from bench import trace as tr

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def _line(lid, name, events, meta):
    """events: (name, start_us, dur_us) -> an XLine in text format."""
    out = [f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0']
    for ev, s, d in events:
        key = meta.setdefault(ev, len(meta) + 1)
        out.append(f"events {{ metadata_id: {key} "
                   f"offset_ps: {int(s * 1e6)} duration_ps: {int(d * 1e6)} }}")
    out.append("}")
    return "\n".join(out)


def _plane(pid, name, lines):
    meta = {}
    body = [_line(i + 1, ln, evs, meta) for i, (ln, evs) in enumerate(lines)]
    md = [f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
          for n, k in meta.items()]
    return (f'planes {{ id: {pid} name: "{name}"\n' + "\n".join(body + md)
            + "\n}")


# Window 0-1000 us; two search spans 100-400 and 600-900; between them the
# host waits. Chip 0 runs ops 150-250 and 300-350 (program A), 650-850
# (program B); chip 1 runs 100-400 and 600-900 (A, B).
HOST = [("python", [("bench.window", 0, 1000), ("bench.search", 100, 300),
                    ("bench.search", 600, 300), ("bench.wait", 400, 200),
                    ("not_ours", 0, 50)])]
DEV0 = [("XLA Ops", [("fusion.1", 150, 100), ("custom-call", 300, 50),
                     ("fusion.2", 650, 200)]),
        ("XLA Modules", [("jit_a(11)", 150, 200), ("jit_b(12)", 650, 200)])]
DEV1 = [("XLA Ops", [("fusion.1", 100, 300), ("fusion.2", 600, 300)]),
        ("XLA Modules", [("jit_a(11)", 100, 300), ("jit_b(12)", 600, 300)])]


@pytest.fixture(scope="module")
def built():
    text = "\n".join([_plane(1, "/host:CPU", HOST),
                      _plane(2, "/device:TPU:0", DEV0),
                      _plane(3, "/device:TPU:1", DEV1),
                      _plane(4, "/device:TPU:0 SparseCore", DEV1)])
    return tr.reduce(ProfileData.from_text_proto(text))


def test_window_and_spans(built):
    assert built.window == (0, 1_000_000)
    assert built.window_s == pytest.approx(1e-3)
    assert len(built.devices) == 2                 # SparseCore plane ignored
    assert len(built.spans_named(tr.SEARCH_SPAN)) == 2


def test_busy_and_idle(built):
    # chip 0 busy 350 us, chip 1 busy 600 us
    assert built.busy_s() == pytest.approx(475e-6)
    # inside the 600 us of search spans: chip 0 idle 250, chip 1 idle 0
    idle = built.idle_share_in(built.spans_named(tr.SEARCH_SPAN))
    assert idle == pytest.approx((250 / 600 + 0) / 2)
    assert built.idle_share_in([]) is None


def test_programs_and_launches(built):
    assert built.launches() == 2
    assert built.module_time_s("jit_a") == pytest.approx(250e-6)
    top = dict(built.top_modules())
    assert top == {"jit_a": pytest.approx(250e-6),
                   "jit_b": pytest.approx(250e-6)}


def test_idle_gaps_named_by_host_span(built):
    gaps = built.idle_gaps()
    # chip 0 is idle 0-150 (no span), 250-300 (search), 350-650 (wait)
    # and 850-1000 (no span)
    assert gaps[0] == ["bench.wait", pytest.approx(300e-6)]
    names = [g[0] for g in gaps]
    assert "bench.search" in names and "bench.outside_spans" in names
    assert len(gaps) == 4


def test_no_window_or_no_chip_reads_nothing():
    host_only = _plane(1, "/host:CPU", HOST)
    assert tr.reduce(ProfileData.from_text_proto(host_only)) is None
    no_window = _plane(1, "/host:CPU", [("t", [("bench.search", 0, 5)])]) \
        + "\n" + _plane(2, "/device:TPU:0", DEV0)
    assert tr.reduce(ProfileData.from_text_proto(no_window)) is None


def test_interval_helpers():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.covered([(0, 3), (5, 8)], [(2, 6)]) == 2
    assert tr.complement([(0, 3), (5, 8)], (1, 10)) == [(3, 5), (8, 10)]
    assert tr.program_name("jit_foo(123)") == "jit_foo"


@pytest.fixture(scope="module")
def recorded():
    """One open-loop batch of the ElectricDevices cell, traced on a v5e
    (the window cut after the first ``bench.search`` span, the device's
    ``XLA Modules`` and ``XLA Ops`` lines and the benchmark's spans
    kept, as text)."""
    path = os.path.join(TESTDATA,
                        "electricdevices-open-one-batch.xspace.txt.gz")
    with gzip.open(path, "rt") as f:
        profile = ProfileData.from_text_proto(f.read())
    return profile, tr.reduce(profile)


def test_recorded_trace(recorded):
    profile, t = recorded
    assert len(t.devices) == 1
    assert t.window_s == pytest.approx(0.706943749)
    modules = [e for p in profile.planes if p.name == "/device:TPU:0"
               for line in p.lines if line.name == tr.MODULES_LINE
               for e in line.events]
    # one batch: every program the eager cascade launched, the survivor
    # DP and the prefix bound once each
    assert t.launches() == len(modules) == 927
    assert sum("_gram_spdtw_call" in e.name for e in modules) == 1
    assert sum("_gram_prefix_bound_call" in e.name for e in modules) == 1
    assert t.module_time_s("_gram_spdtw_call") == pytest.approx(0.127091132)
    assert t.module_time_s("_gram_prefix_bound_call") == \
        pytest.approx(0.020019741)
    busy = t.busy_s()
    assert 0.127 < busy < sum(e.duration_ns for e in modules) * 1e-9 + 1e-3
    assert busy == pytest.approx(0.152399002)
    idle = t.idle_share_in(t.spans_named(tr.SEARCH_SPAN))
    assert idle == pytest.approx(1 - (busy - 0) / 0.599223375, abs=0.01)
    top = t.top_modules(2)
    assert [n for n, _ in top] == ["jit__gram_spdtw_call",
                                   "jit__gram_prefix_bound_call"]
    gaps = t.idle_gaps()
    assert gaps[0][0] == "bench.wait" and len(gaps) == 10
