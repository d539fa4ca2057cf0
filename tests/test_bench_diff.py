"""tools/bench_diff.py — the bench regression sentinel (ISSUE 10).

Schema-smoke in tier-1 so the tool can't rot: it must run CLEAN against
a BENCH_r0*.json trajectory, fail loudly on a synthetic
regression and on a blown absolute budget, and its built-in spec must
stay well-formed.
"""

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_RECORDS = None


def _records_dir() -> str:
    """A one-point trajectory shaped like the driver's records. None is
    checked in (the rounds-1-5 ones were deleted in PR 21), so the tool's
    default root is pointed here for the whole module."""
    global _RECORDS
    if _RECORDS is None:
        import tempfile

        _RECORDS = tempfile.mkdtemp(prefix="bench_records_")
        with open(os.path.join(_RECORDS, "BENCH_r05.json"), "w") as f:
            json.dump(
                {"n": 5, "parsed": {"value": 2554.1, "vs_baseline": 1.0216}}, f
            )
    return _RECORDS


def _tool():
    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO, "tools", "bench_diff.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._REPO_ROOT = _records_dir()
    return mod


def test_default_spec_is_well_formed():
    mod = _tool()
    assert mod.DEFAULT_SPEC
    for entry in mod.DEFAULT_SPEC:
        assert entry["direction"] in ("up", "down", "max", "min")
        if entry["direction"] in ("max", "min"):
            assert "bound" in entry
        else:
            assert entry.get("tol_pct", 0) >= 0
    # the documented observability budgets are enforced as absolutes
    keys = {e["key"] for e in mod.DEFAULT_SPEC}
    assert "observability.link_probe_overhead_pct" in keys
    assert "observability.request_tracing_overhead_pct" in keys
    # the alerting & history plane (ISSUE 15): amortized tick budget
    # plus the zero-false-firing gate on the default ruleset
    assert "observability.alerting_overhead_pct" in keys
    assert "observability.alerts_fired_on_healthy_run" in keys
    # the cost-attribution plane (ISSUE 11): run-time overhead budget,
    # per-executable compile budgets, and the every-workload
    # expected-vs-measured presence gate
    assert "attribution.attribution_overhead_pct" in keys
    assert "attribution.expected_vs_measured_missing" in keys
    for exe in ("train_step", "gossip_round", "serve_decode",
                "serve_prefill_max", "spec_propose", "spec_verify"):
        assert f"attribution.compile_ms.{exe}" in keys
    # the speculative serving block (ISSUE 13): gain floor + trajectory
    # direction, acceptance floor, zero-recompile gates on both engines
    assert "serving.spec.spec_tokens_per_sec_gain" in keys
    assert "serving.spec.spec.acceptance_rate" in keys
    assert "serving.spec.spec.zero_recompiles_after_warmup" in keys
    assert "serving.spec.baseline.zero_recompiles_after_warmup" in keys
    # the concurrency-correctness plane (ISSUE 14): per-pass wall
    # budgets for the AST passes, the lockdep smoke budget, zero active
    # findings
    for p in ("host_sync", "locks", "threads", "lockorder", "docs_drift"):
        assert f"analysis.pass_seconds.{p}" in keys
    assert "analysis.lockdep_smoke_seconds" in keys
    assert "analysis.active_findings" in keys
    # the protocol-model + lifecycle passes (ISSUE 19): the lifecycle
    # escape lint rides the 2 s AST budget, the exhaustive model
    # checker holds a 30 s wall budget of its own
    assert "analysis.pass_seconds.lifecycle" in keys
    assert "analysis.pass_seconds.model" in keys
    model_bounds = {e["bound"] for e in mod.DEFAULT_SPEC
                    if e["key"] == "analysis.pass_seconds.model"}
    assert model_bounds == {30.0}
    # the fused kernel tier (ISSUE 16): bit-exactness + HBM-bytes gates
    # on the serving fused_attention block, floor-ratio budgets (down
    # trajectory AND absolute ceiling) per hot-path stage, compile
    # walls on the two fused executables
    assert "serving.fused_attention.bit_exact" in keys
    assert "serving.fused_attention.hbm_bytes_ratio" in keys
    for stage in ("serve_decode", "serve_decode_fused", "serve_prefill",
                  "spec_verify", "spec_verify_fused"):
        key = f"attribution.floor_ratio.{stage}"
        dirs = {e["direction"] for e in mod.DEFAULT_SPEC
                if e["key"] == key}
        assert dirs == {"down", "max"}, key
    assert "attribution.compile_ms.serve_decode_fused" in keys
    assert "attribution.compile_ms.spec_verify_fused" in keys
    # the wide-event accounting plane (ISSUE 17): per-terminal emit
    # overhead budget plus the rollup-must-balance gate
    assert "observability.wide_event_overhead_pct" in keys
    assert "observability.tenant_rollup_mismatch" in keys
    # the fleet tier (ISSUE 20): zero lost streams, router overhead
    # under 1% of a p50 request, scored placement no worse than
    # round-robin on the imbalanced mix, zero recompiles after warmup on
    # every replica, canary promoted inside the soak wall budget
    assert "fleet.lost_streams" in keys
    assert "fleet.router_overhead_pct" in keys
    assert "fleet.placement_ttft_ratio" in keys
    assert "fleet.zero_recompiles_after_warmup" in keys
    assert "fleet.canary_promoted" in keys
    assert "fleet.canary_soak_wall_s" in keys


def test_wide_event_gates_enforced_on_fresh_result(tmp_path, capsys):
    """A fresh bench whose wide-event plane blows the emit budget or
    whose rollup fails to re-derive the engine totals fails; the
    healthy shape passes."""
    mod = _tool()
    fresh = {
        "parsed": {"value": 2554.1, "vs_baseline": 1.02},
        "observability": {
            "wide_event_overhead_pct": 3.2,
            "tenant_rollup_mismatch": 4,
        },
    }
    path = tmp_path / "fresh.json"
    path.write_text(json.dumps(fresh))
    rc = mod.main([str(path), "--json", "-"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    failed = {r["key"] for r in doc["rows"] if r["status"] == "regression"}
    assert "observability.wide_event_overhead_pct" in failed
    assert "observability.tenant_rollup_mismatch" in failed

    healthy = {
        "parsed": {"value": 2554.1, "vs_baseline": 1.02},
        "observability": {
            "wide_event_overhead_pct": 0.04,
            "tenant_rollup_mismatch": 0,
        },
    }
    path2 = tmp_path / "healthy.json"
    path2.write_text(json.dumps(healthy))
    rc = mod.main([str(path2), "--json", "-"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    ok = {r["key"]: r["status"] for r in doc["rows"]}
    assert ok["observability.wide_event_overhead_pct"] == "ok"
    assert ok["observability.tenant_rollup_mismatch"] == "ok"


def test_fleet_gates_enforced_on_fresh_result(tmp_path, capsys):
    """A fresh bench that lost an accepted stream, blew the router
    overhead budget, or whose canary never promoted fails; the healthy
    fleet shape passes every gate."""
    mod = _tool()

    def run(fleet):
        fresh = {
            "parsed": {"value": 2554.1, "vs_baseline": 1.02},
            "fleet": fleet,
        }
        path = tmp_path / "fresh.json"
        path.write_text(json.dumps(fresh))
        rc = mod.main([str(path), "--json", "-"])
        return rc, json.loads(capsys.readouterr().out)

    healthy = {
        "lost_streams": 0,
        "router_overhead_pct": 0.2,
        "placement_ttft_ratio": 0.7,
        "zero_recompiles_after_warmup": True,
        "canary_promoted": True,
        "canary_soak_wall_s": 3.5,
    }
    rc, doc = run(healthy)
    assert rc == 0, doc
    blown = dict(
        healthy,
        lost_streams=1,
        router_overhead_pct=2.0,
        placement_ttft_ratio=1.4,
        canary_promoted=False,
    )
    rc, doc = run(blown)
    assert rc == 1
    failed = {r["key"] for r in doc["rows"] if r["status"] == "regression"}
    assert "fleet.lost_streams" in failed
    assert "fleet.router_overhead_pct" in failed
    assert "fleet.placement_ttft_ratio" in failed
    assert "fleet.canary_promoted" in failed
    ok = {r["key"]: r["status"] for r in doc["rows"]}
    assert ok["fleet.zero_recompiles_after_warmup"] == "ok"
    assert ok["fleet.canary_soak_wall_s"] == "ok"


def test_analysis_budgets_enforced_on_fresh_result(tmp_path, capsys):
    """A fresh bench whose analysis section blows a pass-time budget,
    the lockdep smoke budget, or reports an active finding fails."""
    mod = _tool()
    fresh = {
        "parsed": {"value": 2554.1, "vs_baseline": 1.02},
        "analysis": {
            "pass_seconds": {
                "host_sync": 0.6, "locks": 0.4, "threads": 9.0,
                "lockorder": 0.4, "docs_drift": 0.5,
                "lifecycle": 3.1, "model": 29.0,
            },
            "active_findings": 2,
            "lockdep_smoke_seconds": 45.0,
        },
    }
    path = tmp_path / "fresh.json"
    path.write_text(json.dumps(fresh))
    rc = mod.main([str(path), "--json", "-"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    failed = {r["key"] for r in doc["rows"] if r["status"] == "regression"}
    assert "analysis.pass_seconds.threads" in failed
    assert "analysis.pass_seconds.lifecycle" in failed
    assert "analysis.active_findings" in failed
    assert "analysis.lockdep_smoke_seconds" in failed
    ok = {r["key"]: r["status"] for r in doc["rows"]}
    assert ok["analysis.pass_seconds.host_sync"] == "ok"
    # 29 s of model checking is within its own (30 s) budget
    assert ok["analysis.pass_seconds.model"] == "ok"


def test_min_direction_enforces_floors(tmp_path, capsys):
    """A fresh bench whose speculative block loses its tokens/s gain,
    acceptance floor, or zero-recompile gate fails; a healthy block
    passes. Booleans gate as min-1 floors (true == 1)."""
    mod = _tool()

    def run(spec_block):
        fresh = {
            "parsed": {"value": 2554.1, "vs_baseline": 1.02},
            "serving": {"spec": spec_block},
        }
        path = tmp_path / "fresh.json"
        path.write_text(json.dumps(fresh))
        rc = mod.main([str(path), "--repo-root", mod._REPO_ROOT])
        return rc, capsys.readouterr().out

    healthy = {
        "spec_tokens_per_sec_gain": 2.3,
        "baseline": {"zero_recompiles_after_warmup": True},
        "spec": {
            "acceptance_rate": 1.0,
            "zero_recompiles_after_warmup": True,
        },
    }
    rc, _out = run(healthy)
    assert rc == 0
    bad = json.loads(json.dumps(healthy))
    bad["spec_tokens_per_sec_gain"] = 1.1  # floor is 1.5
    bad["spec"]["acceptance_rate"] = 0.5  # proxy floor is 0.95
    bad["spec"]["zero_recompiles_after_warmup"] = False
    rc, out = run(bad)
    assert rc == 1
    assert "below the absolute floor" in out


def test_attribution_budgets_enforced_on_fresh_result(tmp_path, capsys):
    """A fresh bench whose attribution section blows the run-time
    budget or misses an expected-vs-measured pairing fails the gate."""
    mod = _tool()
    fresh = {
        "parsed": {"value": 2554.1, "vs_baseline": 1.02},
        "attribution": {
            "attribution_overhead_pct": 3.0,  # budget is <1%
            "expected_vs_measured_missing": 1,  # must be 0
            "compile_ms": {"train_step": 500.0},
        },
    }
    path = tmp_path / "fresh.json"
    path.write_text(json.dumps(fresh))
    rc = mod.main([str(path), "--json", "-"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    failed = {r["key"] for r in doc["rows"] if r["status"] == "regression"}
    assert "attribution.attribution_overhead_pct" in failed
    assert "attribution.expected_vs_measured_missing" in failed
    ok = {
        r["key"]: r["status"] for r in doc["rows"]
    }
    assert ok["attribution.compile_ms.train_step"] == "ok"


def test_fused_attention_gates_enforced_on_fresh_result(tmp_path, capsys):
    """A fresh bench whose fused block lost bit-exactness, touched MORE
    HBM bytes than the gather path, or whose floor ratios blew their
    absolute ceilings fails; a healthy block passes the same gates."""
    mod = _tool()

    def run(serving, attribution):
        fresh = {
            "parsed": {"value": 2554.1, "vs_baseline": 1.02},
            "serving": serving,
            "attribution": attribution,
        }
        path = tmp_path / "fresh.json"
        path.write_text(json.dumps(fresh))
        rc = mod.main([str(path), "--json", "-"])
        return rc, json.loads(capsys.readouterr().out)

    healthy_ratio = {
        "serve_decode": 7.6, "serve_decode_fused": 6.0,
        "serve_prefill": 5.0, "spec_verify": 5.7,
        "spec_verify_fused": 8.3,
    }
    rc, doc = run(
        {"fused_attention": {"bit_exact": 1, "hbm_bytes_ratio": 0.93}},
        {"floor_ratio": dict(healthy_ratio)},
    )
    assert rc == 0, doc
    blown = dict(healthy_ratio)
    blown["serve_decode_fused"] = 250.0  # ceiling is 100x floor
    rc, doc = run(
        {"fused_attention": {"bit_exact": 0, "hbm_bytes_ratio": 1.2}},
        {"floor_ratio": blown},
    )
    assert rc == 1
    failed = {r["key"] for r in doc["rows"] if r["status"] == "regression"}
    assert "serving.fused_attention.bit_exact" in failed
    assert "serving.fused_attention.hbm_bytes_ratio" in failed
    assert "attribution.floor_ratio.serve_decode_fused" in failed
    ok = {r["key"]: r["status"] for r in doc["rows"]}
    assert ok["attribution.floor_ratio.serve_decode"] == "ok"


def test_runs_clean_against_its_own_trajectory(capsys):
    """A trajectory agrees with itself: its newest point diffed against
    the trajectory is not a regression."""
    mod = _tool()
    rc = mod.main([os.path.join(mod._REPO_ROOT, "BENCH_r05.json")])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "bench-diff PASSED" in out
    assert "regression" not in out.split("bench-diff")[0]


def test_regression_and_budget_violations_exit_nonzero(tmp_path, capsys):
    mod = _tool()
    fresh = {
        "parsed": {
            "value": 1000.0,  # ~60% below the trajectory's 2554
            "vs_baseline": 0.4,
        },
        # blown absolute budgets (docs promise <1% / zero false firing)
        "observability": {
            "request_tracing_overhead_pct": 2.5,
            "alerting_overhead_pct": 1.8,
            "alerts_fired_on_healthy_run": 1,
        },
    }
    path = tmp_path / "fresh.json"
    path.write_text(json.dumps(fresh))
    rc = mod.main([str(path), "--json", "-"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    failed = {r["key"] for r in doc["rows"] if r["status"] == "regression"}
    assert "value" in failed
    assert "observability.request_tracing_overhead_pct" in failed
    assert "observability.alerting_overhead_pct" in failed
    assert "observability.alerts_fired_on_healthy_run" in failed
    assert doc["counts"]["regressions"] >= 3


def test_direction_semantics_up_down_and_tolerance():
    mod = _tool()
    ref = {"value": 100.0, "serving": {"ttft_p99_ms": 50.0}}
    spec = [
        {"key": "value", "direction": "up", "tol_pct": 10.0},
        {"key": "serving.ttft_p99_ms", "direction": "down", "tol_pct": 20.0},
    ]
    ok = mod.diff({"value": 91.0, "serving": {"ttft_p99_ms": 59.0}}, ref, spec)
    assert ok["ok"] and ok["counts"]["checked"] == 2
    worse = mod.diff(
        {"value": 89.0, "serving": {"ttft_p99_ms": 61.0}}, ref, spec
    )
    assert not worse["ok"]
    assert [r["status"] for r in worse["rows"]] == ["regression"] * 2


def test_missing_metrics_are_skipped_not_failed(capsys):
    mod = _tool()
    report = mod.diff({"value": 2554.1}, {"value": 2554.1}, mod.DEFAULT_SPEC)
    assert report["ok"]
    assert report["counts"]["skipped"] > 0
    for row in report["rows"]:
        if row["status"] == "skipped":
            assert "why" in row


def test_unreadable_inputs_exit_2(tmp_path, capsys):
    mod = _tool()
    assert mod.main([str(tmp_path / "nope.json")]) == 2
    assert "cannot read" in capsys.readouterr().err
    bad = tmp_path / "fresh.json"
    bad.write_text("{}")
    empty = tmp_path / "emptyrepo"
    empty.mkdir()
    assert mod.main([str(bad), "--repo-root", str(empty)]) == 2
    assert "no trajectory" in capsys.readouterr().err
