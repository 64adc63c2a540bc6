"""MLPerf-style scenario harness + serving artifact gate (ISSUE 8).

Acceptance contract: ``launch/scenarios.py --smoke`` produces a
schema-valid ``BENCH_serving.json`` whose exactness flag is true
(sharded top-1 bit-identical to the single-host cascade) — checked
in-process at tiny shapes and end-to-end through the CLI on a forced
4-device CPU mesh (the CI configuration). Also pinned here: the
latency-percentile clamp on empty / single-element streams and the
seeding of the Poisson arrival process from ``MeasureSpec.seed``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.check_artifacts import check_file
from repro.launch.search import SearchEngine
from repro.launch import scenarios
from repro.launch.stats import percentiles

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ------------------------------------------------- percentile clamp fix
def test_percentiles_empty_stream_clamps_to_zero():
    """No samples must not poison the artifact with NaN."""
    p = percentiles([])
    assert set(p) == {"p50", "p95", "p99"}
    assert all(v == 0.0 for v in p.values())


def test_percentiles_single_element_stream():
    """One sample reports that sample at every percentile (no NaN)."""
    p = percentiles([0.25])
    assert all(np.isfinite(v) and v == pytest.approx(250.0)
               for v in p.values())


def test_stats_latency_finite_on_degenerate_streams():
    """``SearchEngine.stats()['latency_ms']`` stays finite after a
    single served batch (the single-element stream of the issue)."""
    rng = np.random.default_rng(0)
    C = rng.normal(size=(16, 24)).astype(np.float32)
    eng = SearchEngine(C, kind="spdtw", impl="scan")
    eng.search(C[:3])
    lat = eng.stats()["latency_ms"]["total"]
    assert all(np.isfinite(v) for v in lat.values())


# ------------------------------------------------------ scenario driver
@pytest.fixture(scope="module")
def payload(tmp_path_factory):
    """One tiny in-process run shared by the schema/exactness tests."""
    return scenarios.run(dataset="CBF", n_queries=12, batch=4, shards=2,
                         n_train=20, T=24, n_sp_train=10, impl="scan",
                         seed=3)


def test_scenarios_payload_exact_and_complete(payload):
    """All three scenarios report, the exactness flag is true, and the
    shard story is in the payload."""
    assert payload["exact"] is True
    assert payload["n_shards"] == 2
    assert set(payload["scenarios"]) == set(scenarios.SCENARIOS)
    for sc in payload["scenarios"].values():
        assert sc["throughput_qps"] > 0
        assert all(np.isfinite(v) for v in sc["latency_ms"].values())


def test_serving_artifact_passes_schema_gate(payload, tmp_path):
    """The emitted artifact satisfies the BENCH_serving.json schema in
    ``benchmarks/check_artifacts.py`` (the CI gate)."""
    path = tmp_path / "BENCH_serving.json"
    path.write_text(json.dumps(payload, default=float))
    assert check_file(str(path)) == []


def test_serving_schema_rejects_inexact(payload, tmp_path):
    """The gate actually bites: a false exactness flag fails."""
    bad = dict(payload, exact=False)
    path = tmp_path / "BENCH_serving.json"
    path.write_text(json.dumps(bad, default=float))
    assert any("bit-identical" in e for e in check_file(str(path)))


def test_server_scenario_seeded_from_measure_spec():
    """The Poisson arrival process derives from ``MeasureSpec.seed``:
    the reported seed is the engine's, and an explicit override wins."""
    rng = np.random.default_rng(0)
    C = rng.normal(size=(16, 24)).astype(np.float32)
    eng = SearchEngine(C, kind="spdtw", impl="scan", seed=7, shards=2)
    Q = C[:8] + 0.05 * rng.normal(size=(8, 24)).astype(np.float32)
    out = scenarios.server_scenario(eng, Q, batch=4, rate_qps=500.0)
    assert out["seed"] == 7 == eng.engine.spec.seed
    out2 = scenarios.server_scenario(eng, Q, batch=4, rate_qps=500.0,
                                     seed=11)
    assert out2["seed"] == 11


# ------------------------------------------------- CLI on a forced mesh
def test_smoke_cli_on_forced_4_device_mesh(tmp_path):
    """End to end as CI runs it: the scenario driver under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` writes a
    schema-valid artifact from the shard_map mesh path."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    subprocess.run(
        [sys.executable, "-m", "repro.launch.scenarios", "--smoke",
         "--shards", "4", "--out", str(tmp_path)],
        env=env, cwd=ROOT, check=True, capture_output=True, text=True,
        timeout=600)
    art = tmp_path / "BENCH_serving.json"
    assert check_file(str(art)) == []
    data = json.loads(art.read_text())
    assert data["exact"] is True
    assert data["n_shards"] == 4 and data["shard_path"] == "mesh"
