"""HBM in use, sampled by the program where the work happens (PR 35): the one
sampler (``obs/memviz.py``), ``feed.stage``'s late arguments, the compile
log's two fields and the accountant's "live" side, on a stub device whose
``memory_stats()`` returns set numbers; and the collector's pauses
(``host.gc``). The CPU backend has no ``memory_stats()``: unstubbed, nothing
is recorded and nothing raises."""

import gc
import math
import threading
import time

import jax
import numpy as np
import pytest

from consensusml_tpu.data.prefetch import DevicePrefetcher
from consensusml_tpu.obs import MetricsRegistry, SpanTracer, get_registry, memviz, tracer
from consensusml_tpu.obs.compile_log import CompileLog

pytestmark = pytest.mark.telemetry

GB = 10**9
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


class StubDevice:
    """A device whose allocator reads what the test sets, and counts the reads."""

    def __init__(self, in_use=8 * GB, peak=9 * GB, limit=16 * GB, reserved=4 * GB):
        self.in_use, self.peak, self.limit, self.reserved, self.calls = in_use, peak, limit, reserved, 0

    def memory_stats(self):
        self.calls += 1
        return {
            "bytes_in_use": self.in_use, "peak_bytes_in_use": self.peak,
            "bytes_limit": self.limit, "bytes_reserved": self.reserved,
        }

    def run(self, temporaries):
        """A program runs: the peak takes what it held at once."""
        self.peak = max(self.peak, self.in_use + temporaries)


@pytest.fixture
def stub(monkeypatch):
    device = StubDevice()
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [device])
    return device


def _batches(n):
    return iter([{"x": np.full((4,), i, np.float32)} for i in range(n)])


def test_the_sampler_reads_the_fullest_device_and_none_without_stats(stub):
    assert memviz.hbm_sample() == (8 * GB, 9 * GB, 16 * GB, 4 * GB)
    fuller = StubDevice(in_use=10 * GB, peak=10 * GB, reserved=0)

    class NoReserve:  # a runtime without the key: no workspace is known of
        def memory_stats(self):
            return {"bytes_in_use": 1, "peak_bytes_in_use": 2, "bytes_limit": 3}

    assert memviz.hbm_sample([NoReserve()]) == memviz.HbmSample(in_use=1, peak=2, limit=3, reserved=0)

    class NoStats:
        def memory_stats(self):
            return None

    class Raises:
        def memory_stats(self):
            raise RuntimeError("no allocator")

    assert memviz.hbm_sample([stub, NoStats(), fuller, Raises()]) == (10 * GB, 10 * GB, 16 * GB, 0)
    assert memviz.hbm_sample([NoStats(), Raises()]) is None
    assert memviz.hbm_sample([]) is None


def test_the_cpu_backend_records_nothing_and_raises_nothing(global_ring):
    """Unstubbed: no ``memory_stats()`` here, so no argument, no gauge, no field."""
    assert memviz.hbm_sample() is None
    reg = MetricsRegistry()
    assert memviz.record_hbm("feed.stage", reg) is None
    assert not [m for m in reg.metrics() if m.name.startswith("consensusml_hbm_in_use")]
    assert len(list(DevicePrefetcher(_batches(3), depth=2))) == 3
    stages = [e for e in global_ring.events() if e["name"] == "feed.stage"]
    assert len(stages) == 3 and not any("hbm_in_use" in e.get("args", {}) for e in stages)
    log = CompileLog(registry=reg, tracer=SpanTracer(enabled=False))
    log.on_duration(LOWER, 0.1, fun_name="jit(step)")
    log.on_duration(BACKEND, 0.2, fun_name="jit(step)")
    (rec,) = log.records()
    assert rec["hbm_in_use_bytes"] is None and rec["hbm_peak_bytes"] is None and rec["hbm_reserved_bytes"] is None
    assert log.hbm_trail() == []
    tick = memviz.HbmAccountant(registry=reg).tick()
    assert math.isnan(tick["runtime_in_use_bytes"]) and math.isnan(tick["runtime_peak_bytes"])


def test_feed_stage_spans_carry_the_reading_while_the_ring_records(stub, global_ring):
    assert len(list(DevicePrefetcher(_batches(4), depth=2))) == 4
    stages = [e for e in global_ring.events() if e["name"] == "feed.stage"]
    assert len(stages) == 4 and stub.calls == 4  # one reading a staged batch
    for e in stages:
        assert e["args"] == {"hbm_in_use": 8 * GB, "hbm_peak": 9 * GB, "hbm_reserved": 4 * GB}
    # the other feed spans carry none, and the Chrome export has the arguments
    others = [e for e in global_ring.events() if e["name"] in ("feed.pull", "feed.drain", "feed.wait")]
    assert others and not any("hbm_in_use" in e.get("args", {}) for e in others)
    exported = [e for e in global_ring.trace_events() if e["name"] == "feed.stage"]
    assert exported[0]["args"]["hbm_in_use"] == 8 * GB
    reg = get_registry()
    assert reg.gauge("consensusml_hbm_in_use_bytes", labels={"where": "feed.stage"}).value == 8 * GB


def test_an_untraced_run_takes_no_sample_in_the_producer(stub):
    """No profiler session and no sink: the producer pays one boolean a batch."""
    from consensusml_tpu.obs import get_tracer

    assert not get_tracer().recording()
    assert len(list(DevicePrefetcher(_batches(5), depth=2))) == 5
    assert stub.calls == 0


def test_the_reading_is_no_part_of_the_spans_time(stub, global_ring, monkeypatch):
    """``at_close`` runs after the body was timed: a slow allocator does not
    lengthen ``feed.stage`` (what ``feed_busy_ms.train`` sums)."""
    slow = stub.memory_stats

    def memory_stats():
        time.sleep(0.05)
        return slow()

    monkeypatch.setattr(stub, "memory_stats", memory_stats)
    assert len(list(DevicePrefetcher(_batches(2), depth=2))) == 2
    stages = [e for e in global_ring.events() if e["name"] == "feed.stage"]
    assert all("hbm_in_use" in e["args"] for e in stages)
    assert max(e["dur_us"] for e in stages) < 40_000


def test_at_close_joins_the_record_only_when_it_is_recorded():
    ring, calls = SpanTracer(enabled=True), []

    def late():
        calls.append(1)
        return {"bytes": 5, "round": 9}

    with ring.span("outer", round=3):
        with ring.span("inner", scope=False, at_close=late, kind="a"):
            pass
        with ring.span("bare", scope=False, at_close=lambda: None):
            pass
    inner, bare, _ = ring.events()
    assert inner["args"] == {"kind": "a", "round": 9, "bytes": 5}
    assert bare["args"] == {"round": 3}  # nothing late: what it inherited alone
    quiet = SpanTracer(enabled=False)
    with quiet.span("inner", scope=False, at_close=late):
        pass
    assert len(calls) == 1 and quiet.events() == []


def test_compile_records_name_the_program_whose_run_raised_the_peak(stub):
    reg = MetricsRegistry()
    log = CompileLog(registry=reg, tracer=SpanTracer(enabled=False))

    def build(fun):
        log.on_duration(LOWER, 0.1, fun_name=f"jit({fun})")
        log.on_duration(BACKEND, 0.2, fun_name=f"jit({fun})")

    stub.in_use, stub.peak = 0, 0
    build("init")
    stub.in_use = 8 * GB  # init ran: the state is live
    stub.run(0)
    build("train_step")
    stub.run(2 * GB)  # the round: 2 GB of temporaries
    build("<lambda>")
    stub.run(3 * GB)  # the harness's copy of a moment: 3 GB beside the state
    build("delta")
    stub.run(1 * GB)
    records = log.records()
    assert [r["fun"] for r in records] == ["init", "train_step", "<lambda>", "delta"]
    assert [r["hbm_peak_bytes"] for r in records] == [0, 8 * GB, 10 * GB, 11 * GB]
    assert [r["hbm_in_use_bytes"] for r in records] == [0, 8 * GB, 8 * GB, 8 * GB]
    trail = log.hbm_trail(last_peak=stub.peak)
    assert [(t["fun"], t["raised_bytes"]) for t in trail] == [
        ("init", 8 * GB), ("train_step", 2 * GB), ("<lambda>", 1 * GB), ("delta", 0),
    ]
    # the last to raise the mark set the lifetime peak: the harness's program, not the round
    assert [t["fun"] for t in trail if t["raised_bytes"]][-1] == "<lambda>"
    assert trail[-1]["peak_after_bytes"] == 11 * GB and log.hbm_trail()[-1]["raised_bytes"] is None
    # a .lower() that is never compiled has a reading too; so has a compile alone
    log.on_duration(LOWER, 0.1, fun_name="jit(lowered_only)")
    log.on_duration(BACKEND, 0.3, fun_name="jit(compiled_only)")
    assert all(r["hbm_peak_bytes"] == 11 * GB for r in log.records()[-2:])
    assert reg.gauge("consensusml_hbm_in_use_max_bytes", labels={"where": "compile"}).value == 8 * GB


def test_the_accountants_live_side_prefers_the_in_round_maximum(stub):
    reg = MetricsRegistry()
    acct = memviz.HbmAccountant(registry=reg, device=stub)
    # nothing sampled in a round yet: the lifetime peak, set-up included, and the workspaces
    assert acct.live_peak_bytes == (9 + 4) * GB
    tick = acct.tick()  # a tick is between rounds: it is no in-round sample
    assert tick["runtime_in_use_bytes"] == 8 * GB and tick["runtime_peak_bytes"] == 9 * GB
    assert tick["runtime_limit_bytes"] == 16 * GB and tick["runtime_reserved_bytes"] == 4 * GB
    assert acct.live_peak_bytes == (9 + 4) * GB
    assert reg.gauge("consensusml_hbm_in_use_bytes", labels={"where": "tick"}).value == 8 * GB
    for in_use in (6 * GB, 7 * GB, 5 * GB):  # the feed's thread, while rounds run
        stub.in_use = in_use
        memviz.record_hbm(memviz.IN_ROUND, reg)
    assert reg.gauge("consensusml_hbm_in_use_bytes", labels={"where": "feed.stage"}).value == 5 * GB
    assert reg.gauge("consensusml_hbm_in_use_max_bytes", labels={"where": "feed.stage"}).value == 7 * GB
    assert reg.gauge("consensusml_hbm_reserved_bytes", labels={"where": "feed.stage"}).value == 4 * GB
    # the arrays a round held at most and the workspaces: not the 9 GB that set-up left
    assert acct.live_peak_bytes == (7 + 4) * GB
    doc = acct.reconcile(analytic_bytes=11 * GB, compiled_bytes=22 * GB)
    assert doc["live_peak_bytes"] == 11 * GB
    assert doc["drift_pct"]["analytic_vs_live"] == pytest.approx(0.0)
    assert doc["drift_pct"]["compiled_vs_live"] == pytest.approx(100.0)
    names = {m.name for m in reg.metrics()}
    assert "consensusml_hbm_live_peak_bytes" in names and "consensusml_hbm_limit_bytes" in names
    # taken out in PR 35: nothing read them
    assert "consensusml_hbm_peak_bytes" not in names and "consensusml_hbm_live_arrays" not in names


def test_the_running_maximum_survives_two_threads(stub):
    reg = MetricsRegistry()
    stub.in_use = 1

    def sample(values):
        for v in values:
            stub.in_use = v
            memviz.record_hbm("race", reg)

    threads = [threading.Thread(target=sample, args=(range(k, 4000, 4),)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    stub.in_use = 10**6
    memviz.record_hbm("race", reg)
    stub.in_use = 5
    memviz.record_hbm("race", reg)
    assert reg.gauge("consensusml_hbm_in_use_max_bytes", labels={"where": "race"}).value == 10**6


@pytest.fixture
def gc_hook(monkeypatch):
    """A hook of the test's own on a ring and a registry of the test's own."""
    monkeypatch.setattr(tracer, "_GC_HOOK", None)
    monkeypatch.setattr(tracer, "_GC_SPAN_MIN_NS", 0)
    ring, reg = SpanTracer(enabled=True), MetricsRegistry()
    hook = tracer.install_gc_hook(ring, reg)
    try:
        yield ring, reg, hook
    finally:
        gc.callbacks.remove(hook)


def test_a_collection_closes_a_span_and_counts_its_seconds(gc_hook):
    ring, reg, hook = gc_hook
    assert tracer.install_gc_hook(ring, reg) is hook and gc.callbacks.count(hook) == 1  # idempotent
    with ring.span("train.round", round=4, scope=False):
        gc.collect(2)
    spans = [e for e in ring.events() if e["name"] == "host.gc"]
    assert spans and spans[-1]["args"]["gen"] == 2 and spans[-1]["args"]["round"] == 4
    assert isinstance(spans[-1]["args"]["collected"], int)
    pause = reg.counter("consensusml_gc_pause_seconds_total", labels={"gen": 2}).value
    assert 0 < pause < 5 and pause >= spans[-1]["dur_ns"] / 1e9 * 0.5
    # a quiet ring gets no span, the counter still counts
    ring.enabled, n = False, len(ring.events())
    gc.collect(2)
    assert len(ring.events()) == n
    assert reg.counter("consensusml_gc_pause_seconds_total", labels={"gen": 2}).value > pause


def test_short_collections_stay_out_of_the_ring(gc_hook, monkeypatch):
    ring, reg, _ = gc_hook
    monkeypatch.setattr(tracer, "_GC_SPAN_MIN_NS", 60 * 10**9)
    gc.collect(0)
    assert not [e for e in ring.events() if e["name"] == "host.gc"]
    assert reg.counter("consensusml_gc_pause_seconds_total", labels={"gen": 0}).value > 0


def test_digest_rows_get_a_gc_column_beside_the_feeds():
    ring = SpanTracer(enabled=True)
    ms = 1_000_000
    # between rounds, on the consumer's thread: goes to the next round, as the pop does
    ring.complete("host.gc", 0.002, end_ns=10 * ms, gen=2, collected=7)
    ring.complete("feed.wait", 0.001, end_ns=12 * ms)
    with ring.span("train.round", round=0, scope=False):
        ring.complete("host.gc", 0.003, gen=1, collected=1)
        ring.complete("host.gc", 0.004, gen=0, collected=0)
    with ring.span("train.round", round=1, scope=False):
        pass
    rows = {r["round"]: r for r in ring.digest()["rounds"]}
    assert rows[0]["gc_us"] == pytest.approx(9000.0) and rows[0]["feed_us"] == pytest.approx(1000.0)
    assert "gc_us" not in rows[1] and "dur_us" in rows[1]
    assert ring.digest()["spans"]["host.gc"]["count"] == 3
