"""Compile log: what tracing, lowering and compiling each program cost.

``jax.monitoring`` reports three durations for every program JAX builds,
each with the function's name: ``jaxpr_trace_duration`` (Python ->
jaxpr), ``jaxpr_to_mlir_module_duration`` (jaxpr -> StableHLO) and
``backend_compile_duration`` (XLA, or the read from the persistent
cache). The log listens to all three and keeps one record per TOP-LEVEL
program in a bounded list::

    {"fun": "train_step", "trace_s": 21.3, "lower_s": 5.9,
     "backend_s": 1.2, "end_ns": 1790808461000000000,
     "hbm_in_use_bytes": 8000000000, "hbm_peak_bytes": 8100000000,
     "hbm_reserved_bytes": 3500000000}

``end_ns`` is ``time.time_ns()`` when the last of its events arrived, and
the three ``hbm_*`` fields are the allocator's reading then
(``obs/memviz.py:record_hbm``; None off a backend with ``memory_stats()``):
a program is built before it first runs, so the lifetime peak at program
k+1's record is what program k's first run left behind, and
:meth:`CompileLog.hbm_trail` says which program raised the high-water
mark, by how much, by name.

A nested trace is not counted twice. JAX reports the trace of every
jitted function a program calls (inside its parent's trace, so before
it) and of the helpers its lowering jits, but lowers only the program
itself: a record is made when a program is LOWERED, from the longest
pending trace of that name on that thread (one is kept per name) — a nested trace lies inside
its parent's and is never the longer — and every other pending trace is
dropped, its time already inside the one kept. One overlap is left in:
a program that is built WHILE another is being traced (an eager op on
concrete values in the traced function's body) has a record of its own,
and its seconds also lie inside the outer program's ``trace_s``.

The backend's time goes to the record that the same thread lowered last,
if it has that name (JAX compiles a program where it lowered it); a
``.lower()`` that is never compiled keeps ``backend_s`` 0.0, and a compile
with no lowering seen on its thread gets a record of its own.

Each record also goes to the span ring (``jax.trace`` / ``jax.lower`` /
``jax.compile`` with ``fun=``; recorded under the tracer's usual rule),
so a program that compiles inside a traced window is on the timeline
under its name, and to four counter families for the operator.
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Any

from consensusml_tpu.obs.memviz import record_hbm
from consensusml_tpu.obs.metrics import MetricsRegistry, get_registry
from consensusml_tpu.obs.tracer import SpanTracer, get_tracer

__all__ = ["CompileLog", "install", "get_compile_log"]

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
# lowering and the backend name the module: "jit(train_step)"
_MODULE = re.compile(r"^\w+\((.*)\)$")
_CAPACITY = 4096  # records kept
# names kept per thread, the shortest trace dropped first: a thread may trace
# and never lower, and a program's own trace must outlast the hundreds of
# helpers that its lowering traces after it (Pallas index maps: PR 28)
_PENDING = 256


def _fun(module_name: str) -> str:
    m = _MODULE.match(module_name)
    return m.group(1) if m else module_name


class CompileLog:
    """Bounded list of per-program compile records (see module docstring).

    :meth:`on_duration` has the signature of a ``jax.monitoring``
    duration listener; :func:`install` registers the process-wide one.
    """

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        tracer: SpanTracer | None = None,
    ):
        self._records: deque[dict[str, Any]] = deque(maxlen=_CAPACITY)
        self._lock = threading.Lock()
        # .pending: fun -> (seconds, end_ns) of its longest trace not yet lowered;
        # .lowered: the record this thread lowered last, until compiled
        self._tls = threading.local()
        self._tracer = tracer if tracer is not None else get_tracer()
        reg = self._registry = registry if registry is not None else get_registry()
        self._trace_s = reg.counter(
            "consensusml_jax_trace_seconds_total",
            "seconds tracing top-level programs to jaxprs (nested traces "
            "counted once, inside their parent's)",
        )
        self._lower_s = reg.counter(
            "consensusml_jax_lower_seconds_total",
            "seconds lowering top-level programs to StableHLO",
        )
        self._backend_s = reg.counter(
            "consensusml_jax_backend_compile_seconds_total",
            "seconds in the backend per program: XLA compilation, or the "
            "read from the persistent cache",
        )
        self._programs = reg.counter(
            "consensusml_jax_programs_total",
            "top-level programs lowered (each is one compile-log record)",
        )

    def on_duration(
        self, event: str, seconds: float, fun_name: str = "", **_kw
    ) -> None:
        if event == _TRACE:
            pending = getattr(self._tls, "pending", None)
            if pending is None:
                pending = self._tls.pending = {}
            if seconds >= pending.get(fun_name, (0.0, 0))[0]:
                pending[fun_name] = (float(seconds), time.time_ns())
                if len(pending) > _PENDING:
                    del pending[min(pending, key=lambda f: pending[f][0])]
        elif event == _LOWER:
            self._lowered(_fun(fun_name), float(seconds))
        elif event == _BACKEND:
            self._compiled(_fun(fun_name), float(seconds))

    def _hbm(self) -> dict[str, int | None]:
        in_use, peak, _, reserved = record_hbm("compile", self._registry) or (None,) * 4
        return {"hbm_in_use_bytes": in_use, "hbm_peak_bytes": peak, "hbm_reserved_bytes": reserved}

    def _lowered(self, fun: str, lower_s: float) -> None:
        now = time.time_ns()
        pending = getattr(self._tls, "pending", None) or {}
        mine = fun in pending
        trace_s, trace_end = pending.get(fun, (0.0, now))
        pending.clear()
        rec = {
            "fun": fun, "trace_s": trace_s, "lower_s": lower_s,
            "backend_s": 0.0, "end_ns": now, **self._hbm(),
        }
        with self._lock:
            self._records.append(rec)
        self._tls.lowered = rec
        self._trace_s.inc(trace_s)
        self._lower_s.inc(lower_s)
        self._programs.inc()
        if mine:
            self._tracer.complete("jax.trace", trace_s, end_ns=trace_end, fun=fun)
        self._tracer.complete("jax.lower", lower_s, end_ns=now, fun=fun)

    def _compiled(self, fun: str, backend_s: float) -> None:
        now = time.time_ns()
        rec = getattr(self._tls, "lowered", None)
        self._tls.lowered = None
        hbm = self._hbm()
        with self._lock:
            if rec is None or rec["fun"] != fun:
                rec = {
                    "fun": fun, "trace_s": 0.0, "lower_s": 0.0,
                    "backend_s": 0.0, "end_ns": now,
                }
                self._records.append(rec)
            rec.update(hbm, backend_s=backend_s, end_ns=now)
        self._backend_s.inc(backend_s)
        self._tracer.complete("jax.compile", backend_s, end_ns=now, fun=fun)

    def records(self) -> list[dict[str, Any]]:
        """Snapshot, oldest first."""
        with self._lock:
            return [dict(r) for r in self._records]

    def hbm_trail(
        self, last_peak: int | None = None, before_ns: int | None = None
    ) -> list[dict[str, Any]]:
        """Which program's first run raised the lifetime peak: a row for
        each record that carries a reading, ``peak_before_bytes`` as its
        own record closed (the program built, not yet run),
        ``peak_after_bytes`` as the next record did — ``last_peak`` for the
        last, a later reading such as the first ``feed.stage`` span's
        ``hbm_peak`` — and ``raised_bytes`` between them: the program's
        run, and whatever else the process did before it built the next.
        ``before_ns`` keeps the records that closed before it (set-up's,
        when it is the window's first span's start)."""
        read = [
            r for r in self.records()
            if r.get("hbm_peak_bytes") is not None
            and (before_ns is None or r["end_ns"] <= before_ns)
        ]
        after = [r["hbm_peak_bytes"] for r in read[1:]] + [last_peak]
        return [
            {
                "fun": r["fun"],
                "in_use_bytes": r["hbm_in_use_bytes"],
                "reserved_bytes": r["hbm_reserved_bytes"],
                "peak_before_bytes": r["hbm_peak_bytes"],
                "peak_after_bytes": nxt,
                "raised_bytes": None if nxt is None else nxt - r["hbm_peak_bytes"],
            }
            for r, nxt in zip(read, after)
        ]


_LOG: CompileLog | None = None
_INSTALL_LOCK = threading.Lock()


def install() -> CompileLog:
    """Register the process-wide log with ``jax.monitoring``. Idempotent:
    ``enable_compile_cache()`` calls it from every entry point, before any
    program is built."""
    global _LOG
    with _INSTALL_LOCK:
        if _LOG is None:
            import jax

            _LOG = CompileLog()
            jax.monitoring.register_event_duration_secs_listener(
                _LOG.on_duration
            )
        return _LOG


def get_compile_log() -> CompileLog | None:
    """The installed log, or None before :func:`install`."""
    return _LOG
