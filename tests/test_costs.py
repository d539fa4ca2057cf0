"""Cost-attribution plane (ISSUE 11): compiled cost ledger, three-way
HBM reconciliation, on-demand /profile capture, xprof --json.

CPU tier-1 coverage for obs/costs.py + obs/memviz.py + the /profile
endpoint: every registered executable has a cost row, compile counters
are monotonic, the zero-recompile contract survives ledger wiring
(compile_counts unchanged through a serving e2e), the analytic vs
compiled vs live reconciliation lands within a loose CPU band, /profile
is single-flight with dir-quota rotation, and xprof_summary's family
grouping no longer merges distinct dotted kernel names.
"""

import gzip
import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from consensusml_tpu.obs.costs import CostLedger
from consensusml_tpu.obs.memviz import (
    HbmAccountant,
    compiled_footprint,
    live_array_bytes,
    reconcile_config,
)
from consensusml_tpu.obs.metrics import MetricsRegistry, parse_metric_key

pytestmark = pytest.mark.profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _xprof_tool():
    spec = importlib.util.spec_from_file_location(
        "xprof_summary", os.path.join(REPO, "tools", "xprof_summary.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_engine(reg=None, **cfg):
    from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM
    from consensusml_tpu.serve import Engine, ServeConfig

    model = GPT2LM(
        config=GPT2Config(
            vocab_size=64, hidden=32, layers=2, heads=2, max_len=64,
            dropout=0.0,
        )
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return Engine(
        model, params,
        ServeConfig(num_slots=4, max_len=64, max_new_tokens=8, **cfg),
    )


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


def test_ledger_row_carries_cost_memory_and_compile_time():
    reg = MetricsRegistry()
    led = CostLedger(registry=reg)
    f = jax.jit(lambda x: (x @ x).sum())
    row = led.register(
        "toy.matmul", f, jax.ShapeDtypeStruct((64, 64), jnp.float32)
    )
    assert row.flops > 0 and row.bytes_accessed > 0
    assert row.compile_s > 0
    assert row.peak_bytes == (
        row.argument_bytes + row.temp_bytes + row.output_bytes
        - row.alias_bytes
    )
    # the row landed on the labeled gauge families
    keys = {m.key for m in reg.metrics()}
    assert 'consensusml_cost_flops{executable="toy.matmul"}' in keys
    assert 'consensusml_compile_seconds{executable="toy.matmul"}' in keys


def test_compile_counters_are_monotonic():
    reg = MetricsRegistry()
    led = CostLedger(registry=reg)
    f = jax.jit(lambda x: x * 2)
    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    led.register("a", f, x)
    n1 = reg.counter("consensusml_compile_total").value
    s1 = reg.counter("consensusml_compile_seconds_total").value
    led.register("b", f, x)
    led.register("a", f, x)  # re-register still counts a compile
    n2 = reg.counter("consensusml_compile_total").value
    s2 = reg.counter("consensusml_compile_seconds_total").value
    assert n2 == n1 + 2
    assert s2 > s1
    # transfers are not compiles
    led.register_transfer("stage", jnp.ones((16,)))
    assert reg.counter("consensusml_compile_total").value == n2


def test_attribution_pairs_expected_and_measured():
    led = CostLedger(
        registry=MetricsRegistry(),
        peak_flops_per_s=1e9,
        peak_bytes_per_s=1e9,
    )
    f = jax.jit(lambda x: (x @ x).sum())
    row = led.register(
        "toy", f, jax.ShapeDtypeStruct((64, 64), jnp.float32)
    )
    attr = led.observe_measured("toy", 0.01)
    assert attr["bound"] in ("compute", "memory")
    assert attr["expected_s"] == pytest.approx(
        max(row.flops, row.bytes_accessed) / 1e9
    )
    assert attr["ratio_to_floor"] == pytest.approx(
        0.01 / attr["expected_s"]
    )
    assert attr["unattributed_s"] == pytest.approx(
        0.01 - attr["expected_s"]
    )
    with pytest.raises(KeyError):
        led.observe_measured("nope", 1.0)


def test_transfer_rows_floor_on_staging_bandwidth():
    """Transfer rows floor against the host<->device staging bandwidth,
    NOT the HBM-bus anchor compiled rows use — the hot-swap stage at
    line rate must read ~1x its floor, not 30x over."""
    led = CostLedger(
        registry=MetricsRegistry(),
        peak_bytes_per_s=1e12,  # deliberately absurd HBM anchor
        peak_transfer_bytes_per_s=1e9,
    )
    led.register_transfer("stage", {"w": jnp.ones((1000,), jnp.float32)})
    attr = led.attribution("stage")
    assert attr["bound"] == "transfer"
    assert attr["expected_s"] == pytest.approx(4000 / 1e9)


def test_every_serving_executable_has_a_cost_row():
    reg = MetricsRegistry()
    led = CostLedger(registry=reg)
    with _tiny_engine() as eng:
        rows = eng.register_costs(led)
        expected = {f"serve.prefill.b{b}" for b in eng.buckets}
        expected |= {
            "serve.decode", "serve.decode.fused", "serve.hotswap.stage"
        }
        assert set(rows) == expected
        assert set(led.names()) == expected
        for name in expected:
            r = led.row(name)
            assert r is not None
            if r.kind == "compiled":
                assert r.flops > 0 and r.compile_s > 0
            else:
                assert r.argument_bytes > 0  # the staged params bytes
        # decode's meta names the pool geometry the row was lowered at
        assert rows["serve.decode"].meta["num_slots"] == 4


def test_zero_recompile_contract_survives_ledger_wiring():
    """compile_counts() byte-identical across register_costs AND a
    served request mix afterwards — the ledger's AOT path must never
    touch the jit dispatch caches."""
    led = CostLedger(registry=MetricsRegistry())
    with _tiny_engine() as eng:
        before = eng.warmup()
        eng.register_costs(led)
        assert eng.compile_counts() == before
        handles = [
            eng.submit([1 + i % 30] * (3 + i % 7)) for i in range(8)
        ]
        for h in handles:
            assert h.result(timeout=300).finish_reason in (
                "max_tokens", "eos"
            )
        assert eng.compile_counts() == before


def test_pool_hbm_gauges_track_free_blocks():
    from consensusml_tpu.obs import get_registry

    reg = get_registry()
    with _tiny_engine() as eng:
        total = reg.gauge("consensusml_pool_hbm_bytes").value
        free0 = reg.gauge("consensusml_pool_hbm_free_bytes").value
        # full headroom at init (trash block excluded from free)
        assert total > 0 and 0 < free0 < total
        assert free0 == eng._pool.free_blocks * eng._block_nbytes
        assert reg.gauge("consensusml_serve_params_bytes").value > 0
        h = eng.submit([1, 2, 3, 4], max_new_tokens=8)
        h.result(timeout=300)
        # the decode path refreshed the headroom gauge mid-request: it
        # is sampled per decode step (while the stream's blocks are
        # held), so it reads BELOW the idle headroom — the pressure
        # signal a router sees during traffic
        free1 = reg.gauge("consensusml_pool_hbm_free_bytes").value
        assert 0 < free1 < free0


# ---------------------------------------------------------------------------
# HBM accounting + three-way reconciliation
# ---------------------------------------------------------------------------


def test_live_array_bytes_sees_new_arrays():
    before = live_array_bytes()["bytes"]
    keep = jnp.ones((1024, 256), jnp.float32)  # 1 MiB
    after = live_array_bytes()["bytes"]
    assert after - before >= keep.nbytes


def test_reconcile_sets_drift_gauges():
    reg = MetricsRegistry()
    acct = HbmAccountant(registry=reg)
    acct.tick()
    doc = acct.reconcile(analytic_bytes=120.0, compiled_bytes=100.0)
    assert doc["drift_pct"]["analytic_vs_compiled"] == pytest.approx(20.0)
    keys = {m.key for m in reg.metrics()}
    assert 'consensusml_hbm_drift_pct{pair="analytic_vs_compiled"}' in keys
    assert "consensusml_hbm_live_bytes" in keys


def test_three_way_reconciliation_on_tiny_config():
    """Analytic vs compiled vs live for mnist_mlp smoke at world=1.

    CPU band is deliberately loose: the activation coefficients model
    TPU scheduling and the live side is a floor without memory_stats —
    but all three must land within the SAME order of magnitude, and the
    state-dominated analytic-vs-compiled pair much closer than that.
    """
    reg = MetricsRegistry()
    led = CostLedger(registry=reg)
    doc = reconcile_config("mnist_mlp", "smoke", registry=reg, ledger=led)
    a, c, l = (
        doc["analytic_bytes"], doc["compiled_bytes"], doc["live_peak_bytes"]
    )
    assert a > 0 and c > 0 and l > 0
    assert 0.25 < a / c < 4.0, (a, c)
    assert 0.25 < c / max(l, 1) < 4.0, (c, l)
    for pair in ("analytic_vs_compiled", "compiled_vs_live",
                 "analytic_vs_live"):
        assert pair in doc["drift_pct"]
    # the compiled side came through the ledger: the row exists
    assert led.row("train.step.mnist_mlp") is not None


def test_compiled_footprint_matches_hbm_model_measure_definition():
    f = jax.jit(lambda x: (x @ x).sum())
    ma = (
        f.lower(jax.ShapeDtypeStruct((32, 32), jnp.float32))
        .compile()
        .memory_analysis()
    )
    assert compiled_footprint(ma) == (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    )


# ---------------------------------------------------------------------------
# /profile endpoint
# ---------------------------------------------------------------------------


def _get(url, timeout=60):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_profile_endpoint_single_flight_and_rotation(tmp_path):
    from consensusml_tpu.obs import MetricsServer

    reg = MetricsRegistry()
    srv = MetricsServer(
        registry=reg, profile_dir=str(tmp_path), profile_quota=2
    )
    try:
        results = {}

        def first():
            results["a"] = _get(srv.url("/profile?ms=700"))

        t = threading.Thread(target=first)
        t.start()
        time.sleep(0.25)  # the first capture is mid-window now
        code_b, doc_b = _get(srv.url("/profile?ms=50"))
        t.join()
        code_a, doc_a = results["a"]
        # the concurrent double-request contract: second gets 409 + the
        # in-flight capture id, never two overlapping profiler sessions
        assert code_a == 200 and code_b == 409
        assert doc_b["capture_id"] == doc_a["capture_id"]
        assert doc_a["trace_json"] and os.path.exists(doc_a["trace_json"])
        assert reg.counter("consensusml_profile_rejected_total").value == 1

        # two more captures -> quota 2 leaves exactly 2 dirs, newest kept
        code_c, doc_c = _get(srv.url("/profile?ms=50"))
        code_d, doc_d = _get(srv.url("/profile?ms=50"))
        assert code_c == code_d == 200
        caps = sorted(
            d for d in os.listdir(str(tmp_path)) if d.startswith("cap-")
        )
        assert len(caps) == 2
        assert os.path.basename(doc_d["dir"]) in caps
        assert not os.path.exists(doc_a["dir"])  # oldest rotated out
        assert reg.counter("consensusml_profile_captures_total").value == 3
    finally:
        srv.close()


def test_profile_capture_parses_via_xprof_summary_json(tmp_path):
    """Acceptance: /profile on a LIVE ServeServer yields a capture that
    xprof_summary --json parses (machine-readable op/host tables)."""
    import socket

    from consensusml_tpu.serve.server import ServeServer

    with _tiny_engine() as eng:
        eng.warmup()
        srv = ServeServer(eng, port=0, metrics_port=0)
        srv.metrics.profile_dir = str(tmp_path)
        try:
            results: dict = {}

            def cap():
                results["r"] = _get(srv.metrics.url("/profile?ms=600"))

            t = threading.Thread(target=cap)
            t.start()
            # real traffic through the live socket while the capture runs
            with socket.create_connection(srv.address, timeout=30) as s:
                s.sendall(
                    (json.dumps({"ids": [1, 2, 3], "max_new_tokens": 4})
                     + "\n").encode()
                )
                f = s.makefile()
                while True:
                    line = json.loads(f.readline())
                    if "tokens" in line or "error" in line:
                        break
                assert "tokens" in line
            t.join()
            code, doc = results["r"]
            assert code == 200 and doc["trace_json"]
            # the endpoint already linked the machine-readable summary
            assert doc["summary"] is not None
            assert "device_total_ms" in doc["summary"]
            # ... and the CLI parses the same capture standalone
            mod = _xprof_tool()
            out = mod.summarize(doc["trace_json"])
            assert out["event_count"] > 0
            assert isinstance(out["ops"], list)
        finally:
            srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# xprof_summary: --json + the .N family fix
# ---------------------------------------------------------------------------


def _write_trace(path, names_durs):
    ev = [
        {
            "ph": "M", "name": "process_name", "pid": 1,
            "args": {"name": "/device:TPU:0"},
        }
    ]
    for name, dur in names_durs:
        ev.append({"ph": "X", "pid": 1, "name": name, "dur": dur, "ts": 0})
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": ev}, f)


def test_op_family_grouping_keeps_distinct_dotted_kernels(tmp_path):
    """XLA duplicates (`fusion`, `fusion.1`) merge; two pallas kernels
    whose FAMILY names differ only by a numeric dotted suffix
    (`fused_pack.4` vs `fused_pack.8`, no bare sibling) stay distinct —
    the old unconditional `.N` strip merged them into one bogus row."""
    p = str(tmp_path / "t.trace.json.gz")
    _write_trace(
        p,
        [
            ("fusion", 100), ("fusion.1", 50), ("fusion.2", 25),
            ("fused_pack.4", 10), ("fused_pack.8", 20),
        ],
    )
    mod = _xprof_tool()
    out = mod.summarize(p)
    ops = {o["op"]: o["ms"] for o in out["ops"]}
    assert ops["fusion"] == pytest.approx(0.175, abs=0.01)  # 175 us merged
    assert "fusion.1" not in ops and "fusion.2" not in ops
    assert "fused_pack.4" in ops and "fused_pack.8" in ops
    assert "fused_pack" not in ops


def test_xprof_summary_json_cli(tmp_path, capsys):
    p = str(tmp_path / "t.trace.json.gz")
    _write_trace(p, [("fusion", 1000), ("copy.1", 500)])
    # the capture's host process: program spans, and the Python tracer's
    # per-call events, which the report leaves out
    with gzip.open(p) as f:
        doc = json.load(f)
    doc["traceEvents"] += [
        {"ph": "M", "name": "process_name", "pid": 2, "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 2, "name": "train.round", "dur": 1500.0, "ts": 0},
        {"ph": "X", "pid": 2, "name": "train.round", "dur": 500.0, "ts": 0},
        {"ph": "X", "pid": 2, "name": "$builtins len", "dur": 1.0, "ts": 0},
    ]
    with gzip.open(p, "wt") as f:
        json.dump(doc, f)
    mod = _xprof_tool()
    import sys
    old = sys.argv
    try:
        sys.argv = ["xprof_summary", p, "--json"]
        rc = mod.main()
    finally:
        sys.argv = old
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["device_total_ms"] == pytest.approx(1.5)
    assert doc["event_count"] == 5
    assert {o["op"] for o in doc["ops"]} == {"fusion", "copy.1"}
    (row,) = doc["host_spans"]
    assert row == {"span": "train.round", "count": 2, "total_ms": 2.0, "mean_ms": 1.0}


# ---------------------------------------------------------------------------
# cluster aggregation carries the attribution table
# ---------------------------------------------------------------------------


def test_cluster_aggregate_builds_attribution_section(tmp_path):
    from consensusml_tpu.obs import ClusterWriter
    from consensusml_tpu.obs.cluster import aggregate

    reg = MetricsRegistry()
    led = CostLedger(
        registry=reg, peak_flops_per_s=1e9, peak_bytes_per_s=1e9
    )
    f = jax.jit(lambda x: (x @ x).sum())
    led.register("toy.step", f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
    led.observe_measured("toy.step", 0.005)
    acct = HbmAccountant(registry=reg)
    acct.tick()
    acct.reconcile(analytic_bytes=110.0, compiled_bytes=100.0)
    ClusterWriter(str(tmp_path), rank=0, registry=reg).write(round=3)
    doc = aggregate(str(tmp_path))
    attr = {r["executable"]: r for r in doc["attribution"]}
    assert "toy.step" in attr
    row = attr["toy.step"]
    assert row["flops"] > 0 and row["compile_s"] > 0
    assert row["measured_s"] == pytest.approx(0.005)
    assert row["floor_ratio"] > 0
    assert doc["hbm"]["analytic_bytes"] == pytest.approx(110.0)
    assert doc["hbm"]["drift_pct"]["analytic_vs_compiled"] == pytest.approx(
        10.0
    )
