"""The compile log: one record per top-level program, from jax.monitoring."""

import jax
import jax.numpy as jnp
import pytest

from consensusml_tpu.compile_cache import enable_compile_cache
from consensusml_tpu.obs import MetricsRegistry, SpanTracer
from consensusml_tpu.obs.compile_log import (
    CompileLog,
    get_compile_log,
    install,
)

pytestmark = pytest.mark.telemetry

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def _mine(prefix):
    return [r for r in get_compile_log().records() if r["fun"].startswith(prefix)]


def test_a_jitted_function_is_counted_once_and_a_second_call_not_at_all():
    enable_compile_cache()  # what every entry point calls; installs the log
    assert install() is get_compile_log()  # idempotent: one listener

    @jax.jit
    def compile_log_once(x):
        return x * 3

    compile_log_once(jnp.ones(3)).block_until_ready()
    compile_log_once(jnp.ones(3)).block_until_ready()
    (rec,) = _mine("compile_log_once")
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["backend_s"] > 0
    assert rec["end_ns"] > 1.7e18  # time.time_ns()
    compile_log_once(jnp.ones(5)).block_until_ready()  # a new shape is a new program
    assert len(_mine("compile_log_once")) == 2


def test_a_nested_trace_is_not_counted_twice():
    enable_compile_cache()

    @jax.jit
    def compile_log_inner(x):
        return x + 1

    @jax.jit
    def compile_log_outer(x):
        return jax.lax.scan(
            lambda c, a: (compile_log_inner(c) * a, a), x, jnp.ones((2, 3))
        )[0]

    before = get_compile_log().records()
    compile_log_outer(jnp.ones(3)).block_until_ready()
    new = get_compile_log().records()[len(before):]
    # the inner function was traced (inside the outer's trace) and the scan's
    # lowering jitted helpers of its own: neither has a record
    assert [r["fun"] for r in new if r["fun"].startswith("compile_log")] == [
        "compile_log_outer"
    ]
    assert not _mine("compile_log_inner")


def test_records_counters_and_ring_spans_from_a_hand_made_event_stream():
    reg, tracer = MetricsRegistry(), SpanTracer()
    log = CompileLog(registry=reg, tracer=tracer)
    # inner's trace ends first, inside step's; lowering traces a helper
    log.on_duration(TRACE, 0.5, fun_name="inner")
    log.on_duration(TRACE, 2.0, fun_name="step")
    log.on_duration(TRACE, 0.01, fun_name="less")
    log.on_duration(LOWER, 1.0, fun_name="jit(step)")
    log.on_duration(BACKEND, 4.0, fun_name="jit(step)")
    log.on_duration("/jax/some/other_duration", 9.0, fun_name="jit(step)")
    (rec,) = log.records()
    assert (rec["fun"], rec["trace_s"], rec["lower_s"], rec["backend_s"]) == (
        "step", 2.0, 1.0, 4.0
    )
    assert reg.counter("consensusml_jax_trace_seconds_total").value == 2.0
    assert reg.counter("consensusml_jax_lower_seconds_total").value == 1.0
    assert reg.counter("consensusml_jax_backend_compile_seconds_total").value == 4.0
    assert reg.counter("consensusml_jax_programs_total").value == 1
    spans = [(e["name"], e["args"]["fun"], e["dur_ns"]) for e in tracer.events()]
    assert spans == [
        ("jax.trace", "step", 2_000_000_000),
        ("jax.lower", "step", 1_000_000_000),
        ("jax.compile", "step", 4_000_000_000),
    ]
    # a cached trace lowered again has no pending trace. It is never
    # compiled: the next compile of that name, with no lowering before it on
    # its thread, does not attach to the stale record
    log.on_duration(LOWER, 0.25, fun_name="jit(again)")
    log.on_duration(LOWER, 0.25, fun_name="jit(third)")
    log.on_duration(BACKEND, 3.0, fun_name="jit(again)")
    assert [
        (r["fun"], r["trace_s"], r["lower_s"], r["backend_s"])
        for r in log.records()[1:]
    ] == [
        ("again", 0.0, 0.25, 0.0), ("third", 0.0, 0.25, 0.0),
        ("again", 0.0, 0.0, 3.0),
    ]
    # a thread that traces and never lowers holds a bounded list of them
    for _ in range(1000):
        log.on_duration(TRACE, 0.001, fun_name="shape_only")
    assert len(log._tls.pending) <= 256


def test_a_programs_trace_outlasts_the_helpers_its_lowering_traces():
    """Lowering a program with Pallas kernels traces hundreds of small
    helpers (index maps, ``jnp`` wrappers) AFTER the program's own trace
    ended and before its lowering does: the hybrid round's 256 newest
    pending traces held none of ``train_step``'s, and ``trace_s`` read 0."""
    log = CompileLog(registry=MetricsRegistry(), tracer=SpanTracer())
    log.on_duration(TRACE, 6.0, fun_name="train_step")
    for i in range(1000):
        log.on_duration(TRACE, 0.001, fun_name=("subtract", "add", f"helper_{i}")[i % 3])
    assert len(log._tls.pending) <= 256
    log.on_duration(LOWER, 2.0, fun_name="jit(train_step)")
    (rec,) = log.records()
    assert (rec["fun"], rec["trace_s"], rec["lower_s"]) == ("train_step", 6.0, 2.0)
