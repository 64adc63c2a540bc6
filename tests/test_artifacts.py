"""Tier-1 guard on the CI artifact gate (``benchmarks/check_artifacts``)
and on the workflow file itself, so neither can rot silently."""
import json
import os

import numpy as np

from benchmarks import check_artifacts as ca

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_committed_artifacts_clean():
    """Every committed BENCH_*.json / artifacts/bench/*.json passes the
    schema — exactly what the CI step runs."""
    paths = ca.collect_artifacts(ROOT)
    names = {os.path.basename(p) for p in paths}
    # the headline artifacts must exist, not just validate when present
    assert {"BENCH_gram.json", "BENCH_search.json",
            "BENCH_centroid.json", "BENCH_sketch.json",
            "BENCH_anomaly.json", "BENCH_embed.json"} <= names
    for p in paths:
        assert ca.check_file(p) == [], p
    assert ca.main(["--root", ROOT]) == 0


def test_gate_rejects_nonfinite_numbers(tmp_path):
    bad = tmp_path / "whatever.json"
    bad.write_text(json.dumps({"a": {"b": [1.0, float("nan")]}}))
    errs = ca.check_file(str(bad))
    assert len(errs) == 1 and "non-finite" in errs[0]
    bad.write_text(json.dumps({"v": float("inf")}))
    assert any("non-finite" in e for e in ca.check_file(str(bad)))


def test_gate_rejects_schema_violations(tmp_path):
    # missing required key
    f = tmp_path / "BENCH_gram.json"
    f.write_text(json.dumps({"backend": "cpu", "speedup": 2.0}))
    errs = ca.check_file(str(f))
    assert any("missing required key" in e for e in errs)
    # exactness flag false
    f2 = tmp_path / "BENCH_search.json"
    f2.write_text(json.dumps({
        "backend": "cpu", "pre_dp_prune": 0.7,
        "workloads": {"retrieval": {"exact": False, "speedup": 1.5}}}))
    errs2 = ca.check_file(str(f2))
    assert any("exactness flag" in e for e in errs2)
    # accuracy gap above the centroid contract
    f3 = tmp_path / "BENCH_centroid.json"
    f3.write_text(json.dumps({
        "backend": "cpu", "max_acc_delta": 0.5, "min_speedup": 9.0,
        "families": {"CBF": {"cascade_exact": True}}}))
    errs3 = ca.check_file(str(f3))
    assert any("accuracy gap" in e for e in errs3)
    # sketch headline below the recall/speedup contract
    f4 = tmp_path / "BENCH_sketch.json"
    f4.write_text(json.dumps({
        "backend": "cpu", "cascade": {"us_per_query": 100.0},
        "curve": [{"recall_at_1": 0.5, "speedup": 9.0}],
        "best": {}, "recall_at_1": 0.5, "speedup": 2.0,
        "covered_exact": False}))
    errs4 = ca.check_file(str(f4))
    assert any("recall@1" in e for e in errs4)
    assert any("3x over the cascade" in e for e in errs4)
    assert any("exactness flag" in e for e in errs4)


def test_gate_rejects_anomaly_violations(tmp_path):
    """The monitor-tier contract (ISSUE 10): ROC-AUC >= 0.9, escalated
    decisions bit-identical to the exact cascade, sane drift behaviour
    and the monitor-on p99 overhead all gated."""
    base = {
        "backend": "cpu", "corpus": 24, "n_outliers": 4, "tau": 1.5,
        "roc_auc": 0.97, "decisions_exact": True, "flag_rate": 0.2,
        "escalation_rate": 0.3,
        "server": {"latency_ms": {"p99": 5.0}},
        "server_monitor": {"latency_ms": {"p99": 6.0}},
        "p99_overhead_ms": 1.0, "p99_overhead_ratio": 1.2,
        "monitor": {"n_scored": 24},
        "drift": {"silent_on_iid": True, "fires_on_shift": True}}
    f = tmp_path / "BENCH_anomaly.json"
    f.write_text(json.dumps(base))
    assert ca.check_file(str(f)) == []
    bad = dict(base, roc_auc=0.6, decisions_exact=False,
               drift={"silent_on_iid": False, "fires_on_shift": False})
    f.write_text(json.dumps(bad))
    errs = ca.check_file(str(f))
    assert any("ROC-AUC" in e for e in errs)
    assert any("bit-identical" in e for e in errs)
    assert any("i.i.d." in e for e in errs)
    assert any("shifted stream" in e for e in errs)
    f.write_text(json.dumps({"backend": "cpu"}))
    assert any("missing required key" in e for e in ca.check_file(str(f)))


def test_gate_rejects_embed_violations(tmp_path):
    good = {
        "n_series": 24, "R": 4, "n_components": 2, "seed": 0,
        "explained_var": [0.7, 0.2], "orthonormal_err": 1e-9,
        "coords": [[0.0, 1.0]] * 24,
        "classes": [{"label": 0, "n": 24, "centroid": [0.0, 1.0]}]}
    f = tmp_path / "BENCH_embed.json"
    f.write_text(json.dumps(good))
    assert ca.check_file(str(f)) == []
    bad = dict(good, orthonormal_err=0.5, explained_var=[1.7, 0.2],
               n_components=1)
    f.write_text(json.dumps(bad))
    errs = ca.check_file(str(f))
    assert any("orthonormal" in e for e in errs)
    assert any("explained_var" in e for e in errs)
    assert any("n_components" in e for e in errs)


def test_gate_rejects_unreadable_json(tmp_path):
    f = tmp_path / "BENCH_gram.json"
    f.write_text("{not json")
    errs = ca.check_file(str(f))
    assert len(errs) == 1 and "unreadable" in errs[0]


def test_gate_main_exit_codes(tmp_path):
    # empty dir: nothing to validate is a failure, not silent success
    assert ca.main(["--root", str(tmp_path)]) == 1
    good = tmp_path / "BENCH_custom.json"
    good.write_text(json.dumps({"ok": 1.0}))
    assert ca.main(["--root", str(tmp_path)]) == 0
    good.write_text(json.dumps({"ok": float("nan")}))
    assert ca.main(["--root", str(tmp_path)]) == 1


def test_ci_workflow_encodes_the_gate():
    """The workflow must run the tier-1 suite, the smoke sweep and the
    artifact gate — the exact commands the acceptance criteria name."""
    wf = os.path.join(ROOT, ".github", "workflows", "ci.yml")
    assert os.path.exists(wf)
    text = open(wf).read()
    assert "python -m pytest -x -q" in text
    assert "python -m benchmarks.run --smoke" in text
    assert "python -m benchmarks.check_artifacts" in text
    assert "timeout-minutes" in text
    assert "cache: pip" in text
    # serving gates: the simulated 4-way mesh smoke, the serving-artifact
    # schema check, the one supported jax pin and the 14-day artifact
    # upload must all stay wired
    assert "repro.launch.scenarios --smoke" in text
    assert "--xla_force_host_platform_device_count=4" in text
    assert "actions/upload-artifact@v4" in text
    assert "retention-days: 14" in text
    assert '"jax[cpu]==0.9.0"' in text and "0.4." not in text
    # ISSUE 10 monitor gate: the anomaly scenario smoke must stay wired
    assert "--scenario anomaly" in text


def test_gitignore_covers_scratch():
    gi = open(os.path.join(ROOT, ".gitignore")).read()
    for pat in ("__pycache__/", ".pytest_cache/", "bench-smoke-"):
        assert pat in gi
