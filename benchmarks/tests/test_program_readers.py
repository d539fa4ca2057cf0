"""The readers that take their numbers from the program itself: the span ring
(``readers/spans.py``) and the compile log (``readers/compiles.py``)."""

import json
import os

import pytest

import run as harness
from test_rehearsal import SOLO, TRAIN_CFG, _write

NEW = ("trace_lower_s.train", "backend_compile_s.train", "feed_busy_ms.train", "feed_wait_max_ms.train")
MS = 1_000_000


def _reader(name):
    return harness._load_module("readers", name, [harness.HERE])


def _args(metric):
    with open(os.path.join(harness.HERE, "layer_metrics", f"{metric}.json")) as f:
        return json.load(f)["args"]


@pytest.fixture
def program(monkeypatch):
    """A hand-made ring and log in the program's place."""
    from consensusml_tpu.obs import MetricsRegistry, SpanTracer, compile_log, tracer

    ring = SpanTracer()
    log = compile_log.CompileLog(registry=MetricsRegistry(), tracer=SpanTracer(enabled=False))
    monkeypatch.setattr(tracer, "_GLOBAL", ring)
    monkeypatch.setattr(compile_log, "_LOG", log)
    return ring, log


def _span(ring, name, start_ms, dur_ms, tid):
    ring.complete(name, dur_ms / 1e3, end_ns=(start_ms + dur_ms) * MS)
    ring._events[-1]["tid"] = tid


def test_span_reader_on_a_hand_made_ring(program):
    ring, _ = program
    spans = _reader("spans")
    assert spans.read({}, **_args("feed_busy_ms.train")) is None  # nothing to read: never 0
    assert spans.read({}, **_args("feed_wait_max_ms.train")) is None
    # the producer (thread 2): a drain left from before the window, then three
    # batches of pull + stage (+ drain), the third cut by the window's end
    _span(ring, "feed.drain", 0, 50, tid=2)
    for i, (pull, stage, drain) in enumerate([(4, 2, 1), (6, 2, 0), (40, 0, 0)]):
        t = 100 + 100 * i
        _span(ring, "feed.pull", t, pull, tid=2)
        if stage:
            _span(ring, "feed.stage", t + pull, stage, tid=2)
        if drain:
            _span(ring, "feed.drain", t + pull + stage, drain, tid=2)
    for i, wait in enumerate([0.05, 3.0, 0.1]):  # the consumer (thread 1)
        _span(ring, "feed.wait", 110 + 100 * i, wait, tid=1)
    _span(ring, "round.fence", 120, 600, tid=1)  # another span is not counted
    assert spans.read({}, **_args("feed_busy_ms.train")) == pytest.approx(7.5)  # median of 7 and 8
    assert spans.read({}, **_args("feed_wait_max_ms.train")) == pytest.approx(3.0)


def test_compile_reader_counts_the_programs_built_before_the_window(program):
    ring, log = program
    compiles = _reader("compiles")
    trace, lower, backend = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )
    for fun, (t, l, b) in {"init": (1.0, 0.5, 2.0), "train_step": (20.0, 6.0, 1.5)}.items():
        log.on_duration(trace, t, fun_name=fun)
        log.on_duration(lower, l, fun_name=f"jit({fun})")
        log.on_duration(backend, b, fun_name=f"jit({fun})")
    # no span in the ring yet: nothing marks the window
    assert compiles.read({}, **_args("trace_lower_s.train")) is None
    _span(ring, "feed.wait", 0, 1, tid=1)
    ring._events[-1].update(start_ns=max(r["end_ns"] for r in log.records()) + 1, dur_ns=1)
    # the reference's programs, compiled by check() after the window, are not set-up
    log.on_duration(trace, 100.0, fun_name="follow")
    log.on_duration(lower, 100.0, fun_name="jit(follow)")
    log.on_duration(backend, 100.0, fun_name="jit(follow)")
    assert compiles.read({}, **_args("trace_lower_s.train")) == pytest.approx(27.5)
    assert compiles.read({}, **_args("backend_compile_s.train")) == pytest.approx(3.5)
    # a ring that was already recording while a program was built is no window
    _span(ring, "feed.wait", 0, 1, tid=1)
    ring._events[-1]["start_ns"] = min(r["end_ns"] for r in log.records()) - 10 * MS
    assert compiles.read({}, **_args("trace_lower_s.train")) is None


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    """The tiny cell with the four committed metric files, found beside run.py."""
    root = str(tmp_path_factory.mktemp("bench_program"))
    d = os.path.join(root, "benchmarks")
    _write(os.path.join(d, "configs", "tiny_choco.json"), TRAIN_CFG)
    _write(os.path.join(d, "traffic", "tiny_choco.solo.json"), SOLO)
    _write(os.path.join(d, "peaks.json"), {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "rehearsal"}})
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        committed = json.load(f)
    bench = {
        "command": committed["command"], "paths": ["benchmarks"], "run_seconds": 3,
        "configs": [{"name": "tiny_choco", "source": "test", "file": "benchmarks/configs/tiny_choco.json", "reduced": [], "why": "t"}],
        "workloads": [{"name": "tiny_choco.solo", "config": "tiny_choco", "traffic": "solo", "chips": 1, "why": "t"}],
        "end_to_end": [dict(m, workloads=["tiny_choco.solo"]) for m in committed["end_to_end"]],
        "per_layer": [dict(m, workloads=["tiny_choco.solo"]) for m in committed["per_layer"] if m["name"] in NEW],
    }
    path = os.path.join(root, "BENCHMARK.json")
    _write(path, bench)
    return path


def _run(bench_file, capsys, trace):
    rc = harness.main(
        ["--workload", "tiny_choco.solo", "--seed", "3000000007", "--seconds", "2", "--trace", str(trace)],
        bench_file=bench_file, require_chip=False,
    )
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_traced_rehearsal_reads_the_four_metrics_and_an_untraced_one_leaves_no_span(bench_file, capsys):
    from consensusml_tpu.obs import get_tracer

    line = _run(bench_file, capsys, trace=1)
    assert [m for m in NEW if m in line["metrics"]] == list(NEW)
    got = {m: line["metrics"][m]["value"] for m in NEW}
    assert all(v >= 0 for v in got.values()) and got["trace_lower_s.train"] > 0 and got["feed_busy_ms.train"] > 0
    names = {e["name"] for e in get_tracer().events()}
    assert {"feed.wait", "feed.pull", "feed.stage", "feed.drain"} <= names
    get_tracer().clear()
    untraced = _run(bench_file, capsys, trace=0)
    assert get_tracer().events() == []  # no session, no sink: the ring stays empty
    # every program the traced run counted was built after this process began and
    # before this second window: set-up, as run.py counts it, holds their seconds
    assert got["trace_lower_s.train"] + got["backend_compile_s.train"] < untraced["metrics"]["setup_s"]["value"]
