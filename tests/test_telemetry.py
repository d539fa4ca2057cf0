"""Observability subsystem: span tracer, metrics registry, flight
recorder, and the train.py telemetry surface (docs/observability.md).

``pytest -m telemetry`` runs this tier; everything here is also tier-1
fast (no subprocesses, 3-round smoke at MLP scale).
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from consensusml_tpu.obs import (
    FlightRecorder,
    MetricsRegistry,
    SpanTracer,
    get_registry,
    get_tracer,
)

pytestmark = pytest.mark.telemetry


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_ring_is_bounded():
    t = SpanTracer(capacity=8)
    for i in range(32):
        with t.span("s", i=i):
            pass
    evs = t.events()
    assert len(evs) == 8
    # oldest dropped: the survivors are the LAST 8
    assert [e["args"]["i"] for e in evs] == list(range(24, 32))


def test_span_nesting_depth_and_duration():
    t = SpanTracer()
    with t.span("outer"):
        with t.span("inner"):
            pass
    inner, outer = t.events()
    assert inner["name"] == "inner" and inner["depth"] == 1
    assert outer["name"] == "outer" and outer["depth"] == 0
    assert outer["dur_us"] >= inner["dur_us"]
    # child's interval is contained in the parent's (how Perfetto nests)
    assert outer["ts_us"] <= inner["ts_us"]
    assert (
        inner["ts_us"] + inner["dur_us"]
        <= outer["ts_us"] + outer["dur_us"] + 1e-3
    )


def test_disabled_tracer_records_nothing():
    t = SpanTracer(enabled=False)
    with t.span("s"):
        pass
    t.instant("i")
    t.complete("c", 0.001)
    assert t.events() == [] and not t.recording()


def test_chrome_trace_export_is_valid_trace_event_json(tmp_path):
    t = SpanTracer()
    with t.span("gossip.round", backend="simulated"):
        with t.span("bucket.pack", buckets=3):
            pass
    t.instant("mark")
    path = t.write_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"  # process_name metadata
    by_name = {e["name"]: e for e in evs if e.get("ph") == "X"}
    assert by_name["bucket.pack"]["args"]["buckets"] == 3
    assert by_name["gossip.round"]["dur"] >= by_name["bucket.pack"]["dur"]
    for e in evs:
        if e["ph"] == "X":
            assert {"name", "pid", "tid", "ts", "dur"} <= set(e)
    assert any(e["ph"] == "i" for e in evs)


def test_span_works_inside_jit_tracing():
    t = SpanTracer()

    @jax.jit
    def f(x):
        with t.span("jitted.region"):
            return x * 2

    assert float(f(jnp.float32(3))) == 6.0
    assert [e["name"] for e in t.events()] == ["jitted.region"]
    float(f(jnp.float32(4)))  # cached: no re-trace, no new span
    assert len(t.events()) == 1


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_exposition():
    r = MetricsRegistry()
    r.counter("t_requests_total", "requests").inc(3)
    r.gauge("t_depth").set(2.5)
    h = r.histogram("t_latency_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.to_prometheus()
    assert "# TYPE t_requests_total counter" in text
    assert "t_requests_total 3" in text
    assert "t_depth 2.5" in text
    assert 't_latency_seconds_bucket{le="0.1"} 1' in text
    assert 't_latency_seconds_bucket{le="1"} 2' in text
    assert 't_latency_seconds_bucket{le="+Inf"} 3' in text
    assert "t_latency_seconds_count 3" in text
    assert text.endswith("\n")


def test_counter_rejects_decrease_and_type_conflicts():
    r = MetricsRegistry()
    c = r.counter("t_x_total")
    with pytest.raises(ValueError):
        c.inc(-1)
    assert r.counter("t_x_total") is c  # get-or-create is idempotent
    with pytest.raises(ValueError):
        r.gauge("t_x_total")


def test_prometheus_write_is_atomic_and_snapshot_ring_bounded(tmp_path):
    r = MetricsRegistry(snapshot_keep=4)
    r.gauge("t_g").set(1)
    path = str(tmp_path / "m.prom")
    r.write_prometheus(path)
    assert "t_g 1" in open(path).read()
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]
    for i in range(9):
        r.snapshot({"round": i})
    snaps = r.snapshots()
    assert len(snaps) == 4
    assert [s["round"] for s in snaps] == [5, 6, 7, 8]
    assert snaps[-1]["metrics"]["t_g"] == 1.0


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_dump_contains_spans_and_snapshots(tmp_path):
    t = SpanTracer()
    r = MetricsRegistry()
    with t.span("gossip.round"):
        pass
    r.counter("t_rounds_total").inc(7)
    r.snapshot({"round": 6})
    rec = FlightRecorder(str(tmp_path / "fr"), tracer=t, registry=r)
    path = rec.dump("unit-test", detail="boom")
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["reason"] == "unit-test"
    assert doc["detail"] == "boom"
    assert [s["name"] for s in doc["spans"]] == ["gossip.round"]
    assert any(
        e.get("name") == "gossip.round" for e in doc["trace_events"]
    )
    assert doc["metric_snapshots"][0]["round"] == 6
    assert doc["metrics_final"]["metrics"]["t_rounds_total"] == 7


def test_flight_recorder_sigterm_dump_chains(tmp_path):
    """The SIGTERM trigger (launcher preemption): the dump lands and the
    PREVIOUS handler still runs. A benign handler is installed first so
    the chained default disposition never kills pytest."""
    import os as _os
    import signal
    import sys
    import time as _time

    t, r = SpanTracer(), MetricsRegistry()
    with t.span("gossip.round"):
        pass
    r.counter("t_rounds_total").inc(2)
    rec = FlightRecorder(str(tmp_path / "fr"), tracer=t, registry=r)
    import threading

    seen = []
    prev_sig = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    prev_hook = sys.excepthook
    prev_thread_hook = threading.excepthook
    try:
        rec.install(sigterm=True)
        _os.kill(_os.getpid(), signal.SIGTERM)
        deadline = _time.monotonic() + 10.0
        while not seen and _time.monotonic() < deadline:
            _time.sleep(0.01)  # signal delivery is between bytecodes
    finally:
        signal.signal(signal.SIGTERM, prev_sig)
        sys.excepthook = prev_hook
        threading.excepthook = prev_thread_hook
    assert seen == [signal.SIGTERM]  # the chained handler ran
    assert rec.last_dump_path and os.path.exists(rec.last_dump_path)
    doc = json.load(open(rec.last_dump_path))
    assert doc["reason"] == "sigterm"
    assert [s["name"] for s in doc["spans"]] == ["gossip.round"]
    assert doc["metrics_final"]["metrics"]["t_rounds_total"] == 2


def test_flight_recorder_excepthook_chains(tmp_path):
    import sys
    import threading

    t, r = SpanTracer(), MetricsRegistry()
    rec = FlightRecorder(str(tmp_path / "fr"), tracer=t, registry=r)
    prev_hook = sys.excepthook
    prev_thread_hook = threading.excepthook
    seen = []
    sys.excepthook = lambda *a: seen.append(a)
    try:
        rec.install(sigterm=False)
        try:
            raise RuntimeError("synthetic crash")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
    finally:
        sys.excepthook = prev_hook
        threading.excepthook = prev_thread_hook
    assert rec.last_dump_path and os.path.exists(rec.last_dump_path)
    doc = json.load(open(rec.last_dump_path))
    assert doc["reason"] == "unhandled-exception"
    assert "synthetic crash" in doc["detail"]
    assert len(seen) == 1  # the previous hook still ran


# ---------------------------------------------------------------------------
# engine telemetry accessors
# ---------------------------------------------------------------------------


def _tiny_params():
    return {"w": jnp.zeros((256, 64), jnp.float32), "b": jnp.zeros((64,))}


def test_engine_telemetry_exact_and_compressed():
    from consensusml_tpu.compress import topk_int8_compressor
    from consensusml_tpu.consensus import ConsensusEngine, GossipConfig
    from consensusml_tpu.topology import RingTopology

    shapes = jax.eval_shape(_tiny_params)
    exact = ConsensusEngine(GossipConfig(topology=RingTopology(4)))
    t = exact.telemetry(shapes)
    assert t["compression_ratio"] == pytest.approx(1.0)
    assert t["gossip_buckets"] >= 1
    assert t["neighbor_sends_per_round"] == 2  # ring: left + right
    assert t["wire_bytes_per_neighbor"] * 2 == t["wire_bytes_per_round"]

    comp = ConsensusEngine(
        GossipConfig(
            topology=RingTopology(4),
            compressor=topk_int8_compressor(chunk=64, k=4),
            gamma=0.5,
        )
    )
    tc = comp.telemetry(shapes)
    assert tc["compression_ratio"] > 4
    assert tc["wire_bytes_per_round"] < t["wire_bytes_per_round"]

    # gossip_steps multiplies the round's wire but NOT the codec's ratio
    # or the per-send payload
    import dataclasses

    multi = ConsensusEngine(
        dataclasses.replace(comp.config, gossip_steps=2)
    )
    tm = multi.telemetry(shapes)
    assert tm["wire_bytes_per_round"] == 2 * tc["wire_bytes_per_round"]
    assert tm["wire_bytes_per_neighbor"] == tc["wire_bytes_per_neighbor"]
    assert tm["compression_ratio"] == pytest.approx(tc["compression_ratio"])


def test_engine_choco_residual():
    from consensusml_tpu.compress import topk_int8_compressor
    from consensusml_tpu.consensus import ConsensusEngine, GossipConfig
    from consensusml_tpu.topology import RingTopology

    eng = ConsensusEngine(
        GossipConfig(
            topology=RingTopology(4),
            compressor=topk_int8_compressor(chunk=64, k=4),
            gamma=0.5,
        )
    )
    state = eng.init_state(_tiny_params(), world_size=4)
    assert eng.choco_residual(state) == pytest.approx(0.0)
    exact = ConsensusEngine(GossipConfig(topology=RingTopology(4)))
    assert exact.choco_residual(exact.init_state(_tiny_params())) is None


# ---------------------------------------------------------------------------
# MetricsLogger shim (backward-compat layer over the registry)
# ---------------------------------------------------------------------------


def test_metrics_logger_context_manager_closes_and_feeds_registry(tmp_path):
    import io

    from consensusml_tpu.utils import MetricsLogger

    reg = MetricsRegistry()
    path = str(tmp_path / "m.jsonl")
    stream = io.StringIO()
    with MetricsLogger(path, stream=stream, registry=reg) as logger:
        logger.log(0, {"loss": 1.5, "consensus_error": 0.25})
        f = logger._file
    assert f is not None and f.closed  # __exit__ closed the handle
    rec = json.loads(open(path).read().splitlines()[0])
    assert rec["round"] == 0 and rec["loss"] == 1.5
    assert reg.gauge("consensusml_loss").value == 1.5
    assert reg.gauge("consensusml_consensus_error").value == 0.25
    assert "loss=1.5000" in stream.getvalue()


def test_metrics_logger_close_is_exception_safe(tmp_path):
    from consensusml_tpu.utils import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    with pytest.raises(RuntimeError):
        with MetricsLogger(path, registry=MetricsRegistry()) as logger:
            f = logger._file
            raise RuntimeError("mid-run crash")
    assert f.closed


# ---------------------------------------------------------------------------
# tools/xprof_summary.py: program spans from the capture + clear missing-path errors
# ---------------------------------------------------------------------------


def test_xprof_summary_missing_dir_clear_error(monkeypatch, capsys):
    import importlib.util
    import sys as _sys

    spec = importlib.util.spec_from_file_location(
        "xprof_summary",
        os.path.join(os.path.dirname(__file__), "..", "tools", "xprof_summary.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(_sys, "argv", ["xprof_summary.py", "/nonexistent/prof"])
    rc = mod.main()
    assert rc == 1
    err = capsys.readouterr().err
    assert "does not exist" in err and "Traceback" not in err


def test_xprof_summary_lists_program_spans_from_a_capture(tmp_path):
    """The program's spans are TraceAnnotations: the profiler's own dump
    holds them, and the tool lists them from there beside JAX's dispatch
    events — no second trace file on another clock to lay beside it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "xprof_summary",
        os.path.join(os.path.dirname(__file__), "..", "tools", "xprof_summary.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    t = SpanTracer(enabled=False)
    double = jax.jit(lambda x: x * 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            with t.span("train.round", round=i):
                double(jnp.ones(4)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    out = mod.summarize(mod.find_trace_json(str(tmp_path)))
    rows = {r["span"]: r for r in out["host_spans"]}
    assert rows["train.round"]["count"] == 3
    assert rows["train.round"]["total_ms"] > 0
    assert any(name.startswith("PjitFunction(") for name in rows)
    assert not any(name.startswith("$") for name in rows)  # no per-call events


# ---------------------------------------------------------------------------
# the 3-round CPU smoke: train.py with every sink on (acceptance run)
# ---------------------------------------------------------------------------


def test_train_smoke_writes_prom_and_trace(tmp_path, capsys):
    import train as train_cli

    trace_path = tmp_path / "trace.json"
    prom_path = tmp_path / "metrics.prom"
    tracer = get_tracer()
    was_enabled = tracer.enabled
    try:
        rc = train_cli.main(
            [
                "--config", "mnist_mlp",
                "--device", "cpu",
                "--backend", "simulated",
                "--rounds", "3",
                "--telemetry-every", "2",
                "--trace-events", str(trace_path),
                "--metrics-prom", str(prom_path),
                "--metrics-port", "0",
            ]
        )
    finally:
        tracer.enabled = was_enabled
    assert rc == 0
    # the live /metrics endpoint came up on a free port and was
    # announced (closed again by the CLI's exit stack)
    assert "metrics endpoint: http://127.0.0.1:" in capsys.readouterr().out

    # (a) Perfetto-loadable trace with nested gossip.round -> bucket spans
    doc = json.load(open(trace_path))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    for e in evs:
        assert {"name", "pid", "tid", "ts", "dur"} <= set(e)
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["train.round"]) == 3
    (g,) = by_name["gossip.round"]  # compile-round engine trace
    (pack,) = by_name["bucket.pack"]
    # nesting: the bucket stage lies inside the gossip round's interval
    assert g["ts"] <= pack["ts"]
    assert pack["ts"] + pack["dur"] <= g["ts"] + g["dur"] + 1e-3
    assert "bucket.unpack" in by_name and "train.inner_loop" in by_name

    # (b) Prometheus textfile with the headline families
    text = open(prom_path).read()
    assert "# TYPE consensusml_round_latency_seconds histogram" in text
    assert "consensusml_round_latency_seconds_count" in text
    assert "# TYPE consensusml_wire_bytes_total counter" in text
    assert "# TYPE consensusml_consensus_distance gauge" in text
    assert "# TYPE consensusml_rounds_total counter" in text
    assert "consensusml_wire_bytes_per_neighbor" in text

    # the registry really accumulated the run's rounds
    reg = get_registry()
    assert reg.counter("consensusml_rounds_total").value >= 3
