"""The span recorder (``repro.tracing``) and the serving spans built on it:
nesting under each batch's ``search`` span, the plan on the survivor DP
span, reset and ring bound, no spans under ``jit``, ``compile`` spans for
programs built mid-stream, the ``latency_ms`` block read from spans, and
the clock: every recorded interval matches its ``TraceAnnotation`` event
in a profiler trace."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.kernels import ops
from repro.launch.search import SearchEngine

CASCADE = ("cascade.bounds", "cascade.seed_dp", "cascade.prefix_bound",
           "cascade.survivor_dp", "cascade.select")


def _corpus(n=48, T=20, seed=0):
    rng = np.random.default_rng(seed)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    return (base[None] + 0.5 * rng.normal(size=(n, T))).astype(np.float32)


@pytest.fixture(scope="module")
def served():
    C, Q = _corpus(), _corpus(n=16, seed=9)     # held-out queries
    eng = SearchEngine(C, kind="spdtw", impl="scan")
    eng.search(Q[:4])
    eng.reset_stats()
    for lo in (4, 8, 12):
        eng.search(Q[lo:lo + 4])
    return eng, eng.stats()


def test_cascade_spans_nest_in_their_batch(served):
    _, st = served
    spans = st["trace"]["spans"]
    batches = {s["id"]: s for s in spans if s["name"] == "search"}
    assert len(batches) == 3
    assert all(s["parent"] == 0 and s["attrs"] == {"n": 4, "mode": "cascade"}
               for s in batches.values())
    by_id = {s["id"]: s for s in spans}
    for name in CASCADE + ("search.readback",):
        found = [s for s in spans if s["name"] == name]
        assert len(found) == 3, name
        for s in found:
            b = batches[s["parent"]]
            assert b["start_ns"] <= s["start_ns"] <= s["end_ns"] \
                <= b["end_ns"]
    # every span of a batch hangs off that batch's search span
    for s in spans:
        if s["name"] != "search":
            top = s
            while top["parent"] in by_id and top["name"] != "search":
                top = by_id[top["parent"]]
            assert top["name"] == "search"
    # stages run in order and do not overlap
    for b in batches.values():
        kids = sorted((s["start_ns"], s["end_ns"]) for s in spans
                      if s["parent"] == b["id"])
        assert all(e <= s for (_, e), (s, _) in zip(kids, kids[1:]))


def test_latency_ms_comes_from_the_search_spans(served):
    _, st = served
    assert set(st["latency_ms"]) == {"total"}
    assert set(st["latency_ms"]["total"]) == {"p50", "p95", "p99"}
    d = sorted((s["end_ns"] - s["start_ns"]) * 1e-6
               for s in st["trace"]["spans"] if s["name"] == "search")
    assert st["latency_ms"]["total"]["p50"] == pytest.approx(d[1])


def test_survivor_counters_reach_stats(served):
    _, st = served
    c = st["trace"]["counters"]
    assert c["survivor_dp.tile_sweeps"] > 0
    assert c["survivor_dp.alive_pair_sweeps"] >= \
        c["survivor_dp.tile_sweeps"]


def test_survivor_dp_span_carries_the_plan(served):
    eng, st = served
    bsp = eng.index.bsp
    spans = [s for s in st["trace"]["spans"]
             if s["name"] == "cascade.survivor_dp"]
    assert len(spans) == 3
    for s in spans:
        assert s["attrs"] == {"tile": bsp.tile, "plan_tiles": bsp.n_active}


def test_reset_clears_and_the_ring_stays_bounded(served):
    eng, _ = served
    eng.reset_stats()
    assert eng.stats() == {}
    assert eng._trace.snapshot() == {"spans": [], "counters": {}}
    rec = tracing.Recorder(capacity=8)
    with tracing.recording(rec):
        for i in range(20):
            with tracing.span("s", i=i):
                tracing.count("c", 2)
    snap = rec.snapshot()
    assert [s["attrs"]["i"] for s in snap["spans"]] == list(range(12, 20))
    assert snap["counters"] == {"c": 40}


def test_counters_sum_device_scalars_and_fold():
    rec = tracing.Recorder()
    with tracing.recording(rec):
        for i in range(tracing._FOLD + 5):
            tracing.count("dev", jnp.int32(i))
    assert len(rec._counts["dev"]) <= tracing._FOLD
    n = tracing._FOLD + 5
    assert rec.snapshot()["counters"]["dev"] == n * (n - 1) // 2


def test_no_spans_under_jit():
    C = _corpus(seed=1)
    eng = SearchEngine(C, kind="spdtw", impl="scan")
    index = eng.index
    rec = tracing.Recorder()
    with tracing.recording(rec):
        nn, _ = jax.jit(lambda q: ops._knn_cascade(q, index, impl="scan"))(
            jnp.asarray(C[:3]))
    np.asarray(nn)
    assert not [s for s in rec.spans if s[0].startswith("cascade.")]
    # the same call, eager, records every stage
    with tracing.recording(rec):
        ops._knn_cascade(jnp.asarray(C[:3]), index, impl="scan")
    assert {s[0] for s in rec.spans} >= set(CASCADE)


def test_compile_spans_for_a_new_batch_shape():
    C = _corpus(n=40, T=22, seed=2)
    eng = SearchEngine(C, kind="spdtw", impl="scan")
    for _ in range(2):
        eng.search(C[:5])
    eng.reset_stats()
    eng.search(C[5:10])                    # a shape served before
    st = eng.stats()
    assert "compile.programs" not in st["trace"]["counters"]
    assert not [s for s in st["trace"]["spans"] if s["name"] == "compile"]
    eng.search(C[10:17])                   # a new batch shape mid-stream
    st = eng.stats()
    comp = [s for s in st["trace"]["spans"] if s["name"] == "compile"]
    assert comp and st["trace"]["counters"]["compile.programs"] == len(comp)
    batch = [s for s in st["trace"]["spans"] if s["name"] == "search"][-1]
    assert all(batch["start_ns"] <= s["start_ns"] <= s["end_ns"]
               <= batch["end_ns"] for s in comp)
    assert all(s["attrs"]["program"] for s in comp)


def test_bound_stage_is_one_program_built_once():
    """Two same-shape batches build the bound stage's program once; the
    cascade traced under jit still returns the eager cascade's answers."""
    from repro.core import bounds
    C = _corpus(n=44, T=18, seed=4)
    eng = SearchEngine(C, kind="spdtw", impl="scan")
    bounds._cascade_bounds.clear_cache()
    eng.search(C[:6])
    eng.search(C[6:12])
    assert bounds._cascade_bounds._cache_size() == 1
    built = [s for s in eng.stats()["trace"]["spans"]
             if s["name"] == "compile"
             and "cascade_bounds" in s["attrs"]["program"]]
    assert len(built) == 1
    index = eng.index
    Q = jnp.asarray(C[12:18] + 0.1)
    nn, nnd = ops._knn_cascade(Q, index, impl="scan")
    jnn, jnnd = jax.jit(lambda q: ops._knn_cascade(q, index, impl="scan"))(Q)
    assert np.array_equal(np.asarray(jnn), np.asarray(nn))
    np.testing.assert_allclose(np.asarray(jnnd), np.asarray(nnd), rtol=1e-6)


def _profile_start_ns(profile) -> int:
    for plane in profile.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                return int(value)
    raise AssertionError("the trace records no profile_start_time")


def test_spans_share_the_profilers_clock(tmp_path):
    """The profiler writes host events as ns since its start and records
    that start: shifted by it, each recorded interval matches its
    TraceAnnotation event within 1 ms at both ends."""
    from jax.profiler import ProfileData
    C = _corpus(seed=3)
    eng = SearchEngine(C, kind="spdtw", impl="scan")
    eng.search(C[:4] + 0.05)
    eng.reset_stats()
    with jax.profiler.trace(str(tmp_path)):
        eng.search(C[4:8] + 0.05)
    spans = eng.stats()["trace"]["spans"]
    prof = ProfileData.from_file(
        glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    t0 = _profile_start_ns(prof)
    events = {}
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = t0 + int(ev.start_ns)
                    events.setdefault(ev.name, []).append(
                        (s, s + int(ev.duration_ns)))
    checked = 0
    for sp in spans:
        if sp["name"] not in CASCADE + ("search", "search.readback"):
            continue
        (s, e), = events[sp["name"]]
        assert abs(s - sp["start_ns"]) < 1e6, sp["name"]
        assert abs(e - sp["end_ns"]) < 1e6, sp["name"]
        checked += 1
    assert checked == len(CASCADE) + 2
