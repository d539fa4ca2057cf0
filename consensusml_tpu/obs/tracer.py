"""Span tracer: host spans on the profiler's clock + device-visible scopes.

One ``span("gossip.round")`` does three things at once:

- opens a ``jax.profiler.TraceAnnotation`` of that name, so whenever a
  profiler session is open (``benchmarks/run.py --trace 1``, ``train.py
  --profile-dir``, ``GET /profile``) the span is an event of the
  profiler's own trace — host plane, the interpreter's line — on the
  clock the device planes use. With no session it is one inactive TraceMe;
- enters a ``jax.named_scope`` with the same name, so when the span body
  is being TRACED by jit the resulting HLO ops carry the label;
- records the span into a bounded ring buffer when ``enabled`` is set
  (``train.py``'s sinks, the flight recorder) OR a profiler session is
  open: a traced run has its spans in memory, an untraced one has none,
  with no flag to pass. The ring exports as Chrome trace-event JSON that
  Perfetto / ``chrome://tracing`` loads directly.

A record holds ``id``, ``parent`` (the enclosing span on that thread, or
None), ``name``, ``start_ns`` / ``dur_ns`` from ``time.time_ns()`` — the
clock TraceMe stamps with, so a ring span and its profiler event differ
by the session's start alone — ``tid``, ``depth`` and its ``args``. A span
inherits ``round=`` / ``request=`` from its parent: the spans of one round
or one request share that identifier.

Spans placed inside jitted code (the consensus engine's round functions,
``train.grad`` / ``train.optimizer``) therefore fire on the host only
while the program is being traced — typically round 0 — and are pure
named scopes afterwards. That is the design, not a limitation:
steady-state rounds must not pay host work per engine stage, while the
compile-round trace still shows the full nesting (``train.round`` ->
``gossip.round`` -> ``bucket.pack`` -> ...).

The ring buffer is bounded (``capacity`` spans, oldest dropped) so the
tracer can stay on for a week-long run and still hand the flight recorder
the LAST N rounds of evidence at crash time.

The collector's pauses are on the same clock: :func:`install_gc_hook`
(called by ``enable_compile_cache()``, as the compile log's install is)
adds every collection's seconds to ``consensusml_gc_pause_seconds_total{gen}``
and closes a ``host.gc(gen=, collected=)`` span for each that took a
tenth of a millisecond or more, under the ring's usual rule.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Iterator

__all__ = ["SpanTracer", "get_tracer", "span", "null_scope", "install_gc_hook"]

# attributes a span takes from its parent: what ties the spans of one
# round or one request together
_INHERITED = ("round", "request")

_HOOKS: tuple | None = None


def _jax_hooks() -> tuple:
    """``(named_scope, TraceAnnotation)``, resolved once. The import is
    lazy: the tracer must stay importable (and cheap) from host-only code
    like the native loader before jax is configured."""
    global _HOOKS
    if _HOOKS is None:
        import jax

        _HOOKS = (jax.named_scope, jax.profiler.TraceAnnotation)
    return _HOOKS


def null_scope():
    return contextlib.nullcontext()


class SpanTracer:
    """Bounded ring buffer of completed spans.

    With ``enabled=False`` and no profiler session :meth:`span` is the
    bare ``TraceAnnotation`` + ``jax.named_scope`` (no host recording, no
    ring append) — the path a run with no trace sink configured stays on.
    """

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        self._events: deque[dict[str, Any]] = deque(maxlen=capacity)
        self._tls = threading.local()
        # RLock: appends and snapshots share it (a snapshot during an
        # append must not see a mutating deque), and the flight
        # recorder's signal-handler dump may interrupt an append on the
        # same thread — reentrancy keeps that from deadlocking
        self._lock = threading.RLock()
        self.enabled = enabled
        self._ids = itertools.count(1)
        # exports give times relative to this anchor; the epoch form lets
        # a flight-recorder reader correlate spans with log timestamps
        self._anchor_ns = time.time_ns()
        self._anchor_epoch = self._anchor_ns / 1e9

    # -- recording ---------------------------------------------------------
    def recording(self) -> bool:
        """True when a span opened now would land in the ring."""
        return self.enabled or _jax_hooks()[1].is_enabled()

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _record(
        self, name: str, start_ns: int, dur_ns: int, attrs: dict, **extra
    ) -> dict[str, Any]:
        """One record; ``parent`` and the inherited attributes come from
        the innermost span open on this thread."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        args = {k: _jsonable(v) for k, v in attrs.items()}
        if parent is not None:
            for key in _INHERITED:
                if key not in args and key in parent.get("args", ()):
                    args[key] = parent["args"][key]
        ev: dict[str, Any] = {
            "id": next(self._ids),
            "parent": parent["id"] if parent is not None else None,
            "name": name,
            "start_ns": start_ns,
            "dur_ns": dur_ns,
            "tid": threading.get_ident(),
            "depth": len(stack),
            **extra,
        }
        if args:
            ev["args"] = args
        return ev

    @contextlib.contextmanager
    def span(
        self, name: str, *, scope: bool = True, at_close=None, **attrs
    ) -> Iterator[None]:
        """``with tracer.span("gossip.round", round=3): ...``

        ``scope=False`` opens no ``jax.named_scope``: for a span around a
        Pallas kernel whose device op has to keep the enclosing scope's
        name (a kernel's op is named after the innermost scope, and the
        benchmark finds flash attention by its block's).

        ``at_close`` is called once the body has ended and been timed,
        only when the span is recorded, and what it returns (a dict, or
        None) joins the record's ``args`` in the ring and the Chrome
        export: a reading taken where the work happened that is no part
        of the span's own time (``feed.stage``'s ``hbm_in_use``)."""
        named_scope, annotation = _jax_hooks()
        if not scope:
            named_scope = lambda _name: null_scope()  # noqa: E731
        if not self.recording():
            with annotation(name, **attrs), named_scope(name):
                yield
            return
        # open: the record is on the thread's stack (its children read
        # ``id`` and the inherited args) and joins the ring when it ends
        ev = self._record(name, time.time_ns(), 0, attrs)
        stack = self._stack()
        stack.append(ev)
        try:
            with annotation(name, **ev.get("args", {})), named_scope(name):
                yield
        finally:
            ev["dur_ns"] = max(time.time_ns() - ev["start_ns"], 0)
            stack.pop()
            late = at_close() if at_close is not None else None
            if late:
                ev.setdefault("args", {}).update(
                    (k, _jsonable(v)) for k, v in late.items()
                )
            with self._lock:
                self._events.append(ev)

    def complete(
        self, name: str, dur_s: float, end_ns: int | None = None, **attrs
    ) -> None:
        """Append an externally-timed completed span ending at ``end_ns``
        (``time.time_ns()``; NOW when None).

        For durations that arrive already measured — the compile log's
        ``jax.trace`` / ``jax.lower`` / ``jax.compile`` records come from
        ``jax.monitoring`` as seconds — so they ride the same ring,
        digest, and Chrome export as ``with``-recorded spans.
        """
        if not self.recording():
            return
        dur_ns = int(max(float(dur_s), 0.0) * 1e9)
        end_ns = time.time_ns() if end_ns is None else end_ns
        ev = self._record(name, end_ns - dur_ns, dur_ns, attrs)
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration marker event (watchdog beats, fault rounds)."""
        if not self.recording():
            return
        ev = self._record(name, time.time_ns(), 0, attrs, instant=True)
        with self._lock:
            self._events.append(ev)

    # -- export ------------------------------------------------------------
    def events(self) -> list[dict[str, Any]]:
        """Snapshot of the ring (oldest first). Each record also carries
        ``ts_us`` (since the tracer's anchor) and ``dur_us``, derived from
        ``start_ns`` / ``dur_ns``: what the Chrome export, the digest and
        the flight recorder's dump are written in."""
        with self._lock:
            ring = list(self._events)
        return [
            dict(
                ev,
                ts_us=(ev["start_ns"] - self._anchor_ns) / 1e3,
                dur_us=ev["dur_ns"] / 1e3,
            )
            for ev in ring
        ]

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def trace_events(self) -> list[dict[str, Any]]:
        """Chrome trace-event ("X"/"i" phase) dicts for the current ring."""
        pid = os.getpid()
        out: list[dict[str, Any]] = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "args": {"name": "consensusml host"},
            }
        ]
        for ev in self.events():
            rec: dict[str, Any] = {
                "name": ev["name"],
                "pid": pid,
                "tid": ev["tid"] % 2**31,  # Perfetto wants small tids
                "ts": round(ev["ts_us"], 3),
            }
            if ev.get("instant"):
                rec["ph"] = "i"
                rec["s"] = "t"
            else:
                rec["ph"] = "X"
                rec["dur"] = round(ev["dur_us"], 3)
            if "args" in ev:
                rec["args"] = ev["args"]
            out.append(rec)
        return out

    def digest(self, max_rounds: int = 64) -> dict[str, Any]:
        """Compact summary of the ring for cluster snapshots.

        Two parts (docs/observability.md "Cross-rank round timeline"):

        - ``spans`` — per-name count/total/max, the whole ring;
        - ``rounds`` — one row per round index found in span attrs, last
          ``max_rounds`` rows: ``train.round`` (stamped ``round=`` by the
          train loop), ``round.fence`` (inside it, so it inherits the
          round) and ``feed.wait`` — the prefetcher's queue pop, which
          runs BEFORE its round's ``train.round`` opens and so goes to the
          next round seen on that thread; ``gc_us`` is the sum of the
          round's ``host.gc`` spans (a collection between two rounds goes
          to the next, as the pop does). The aggregator merges these
          across ranks into the round timeline that attributes a
          straggler round to its phase.

        A few hundred bytes per rank per snapshot — cheap enough to ride
        every :class:`~consensusml_tpu.obs.cluster.ClusterWriter` write.
        """
        names: dict[str, dict[str, float]] = {}
        rounds: dict[int, dict[str, Any]] = {}
        per_round_key = {
            "train.round": "dur_us",
            "feed.wait": "feed_us",
            "round.fence": "fence_us",
        }
        waiting: dict[int, float] = {}  # tid -> a feed.wait not yet in a round
        gc_waiting: dict[int, float] = {}  # tid -> host.gc time not yet in a round
        for ev in self.events():
            d = names.setdefault(
                ev["name"], {"count": 0, "total_us": 0.0, "max_us": 0.0}
            )
            d["count"] += 1
            d["total_us"] += ev["dur_us"]
            d["max_us"] = max(d["max_us"], ev["dur_us"])
            rnd = (ev.get("args") or {}).get("round")
            in_round = isinstance(rnd, (int, float))
            if ev["name"] == "host.gc":
                gc_waiting[ev["tid"]] = gc_waiting.get(ev["tid"], 0.0) + ev["dur_us"]
                if not in_round:
                    continue
            elif ev["name"] not in per_round_key:
                continue
            elif not in_round:
                if ev["name"] == "feed.wait":
                    waiting[ev["tid"]] = ev["dur_us"]
                continue
            row = rounds.setdefault(int(rnd), {"round": int(rnd)})
            if ev["name"] in per_round_key:
                row[per_round_key[ev["name"]]] = round(ev["dur_us"], 1)
            if ev["tid"] in waiting:
                row.setdefault("feed_us", round(waiting.pop(ev["tid"]), 1))
            if ev["tid"] in gc_waiting:
                row["gc_us"] = round(row.get("gc_us", 0.0) + gc_waiting.pop(ev["tid"]), 1)
        return {
            "anchor_epoch_s": self._anchor_epoch,
            "spans": {
                k: {
                    "count": int(v["count"]),
                    "total_us": round(v["total_us"], 1),
                    "max_us": round(v["max_us"], 1),
                }
                for k, v in sorted(names.items())
            },
            "rounds": [rounds[r] for r in sorted(rounds)][-max_rounds:],
        }

    def write_chrome_trace(self, path: str) -> str:
        """Dump the ring as a Perfetto-loadable trace-event JSON file."""
        doc = {
            "traceEvents": self.trace_events(),
            "displayTimeUnit": "ms",
            "metadata": {
                "anchor_epoch_s": self._anchor_epoch,
                "source": "consensusml_tpu.obs.tracer",
            },
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return float(v)
    except Exception:
        return str(v)


_GLOBAL = SpanTracer(enabled=False)


def get_tracer() -> SpanTracer:
    """The process-wide tracer every instrumented module records into.

    Starts DISABLED (pure named scopes, no host recording) so importing
    instrumented modules costs nothing; ``train.py`` enables
    it when a trace or flight-recorder sink is configured.
    """
    return _GLOBAL


def span(name: str, **attrs):
    """Module-level shorthand: ``with obs.span("bucket.pack"): ...``"""
    return _GLOBAL.span(name, **attrs)


# a generation-0 collection takes tens of microseconds and comes hundreds of
# times a second while a program is traced: the counter has them all, the
# ring only the pauses long enough to matter beside a round
_GC_SPAN_MIN_NS = 100_000
_GC_HOOK = None


def install_gc_hook(tracer: SpanTracer | None = None, registry=None):
    """Time every collection of the cyclic garbage collector
    (``gc.callbacks``): its seconds go to
    ``consensusml_gc_pause_seconds_total{gen}``, and one that took at least
    a tenth of a millisecond closes a ``host.gc(gen=, collected=)`` span
    through :meth:`SpanTracer.complete`, on the profiler's clock beside
    the idle gaps it may explain. Idempotent; returns the hook (which
    ``gc.callbacks.remove`` takes out again)."""
    global _GC_HOOK
    if _GC_HOOK is not None:
        return _GC_HOOK
    from consensusml_tpu.obs.metrics import get_registry

    tracer = tracer if tracer is not None else _GLOBAL
    reg = registry if registry is not None else get_registry()
    pauses = [
        reg.counter(
            "consensusml_gc_pause_seconds_total",
            "seconds the cyclic garbage collector held the interpreter, "
            "by the generation collected",
            labels={"gen": gen},
        )
        for gen in range(3)
    ]
    started = [0]

    def hook(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = time.perf_counter_ns()
            return
        dur_ns = time.perf_counter_ns() - started[0]
        gen = info.get("generation", 2)
        pauses[min(gen, 2)].inc(dur_ns / 1e9)
        # no import of jax from inside a collection: with the hooks not yet
        # resolved no profiler session can be open
        if dur_ns >= _GC_SPAN_MIN_NS and (tracer.enabled or _HOOKS is not None):
            tracer.complete(
                "host.gc", dur_ns / 1e9, gen=gen, collected=info.get("collected", 0)
            )

    gc.callbacks.append(hook)
    _GC_HOOK = hook
    return hook
