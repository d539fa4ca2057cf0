"""Overlapped host→device feed: a double-buffered round prefetcher.

A feed that runs its host work (batch assembly, H2D staging) serialized
with device compute stalls the chip for all of it. This module closes
that gap structurally (``feed_stall_ms.train`` and ``feed_wait_max_ms.train``
are its metrics in the benchmark's cells, PERF.md section 3):

- :class:`DevicePrefetcher` pulls host round-batches from a source
  iterator on a *background thread* and stages each one on device via
  non-blocking :func:`jax.device_put` — so while the jitted round for
  batch ``r`` executes, the transfer for ``r+1`` (and the host-side
  assembly for ``r+2``) are already in flight. The consumer's
  ``__next__`` is a queue pop: no host work, no transfer, **no
  ``block_until_ready``** on the critical path between rounds.
- :class:`FeedItem` carries an optional ``on_done`` callback with each
  batch, invoked once the device transfer for that batch has completed
  — this is what lets the native C++ ring hand out *zero-copy views of
  its own slots* (``NativeLoader.acquire_view``): the slot is pinned as
  the staging buffer and released straight back to the producer threads
  the moment the bytes are on device, eliminating the per-batch
  allocation+copy the consume side used to pay.

Feed-stall telemetry (docs/observability.md) goes to the PR-2 metrics
registry: ``consensusml_feed_stall_seconds`` (gauge, the wait the last
round paid for its batch — ~0 when the overlap is working),
``consensusml_feed_stall_seconds_total`` / ``consensusml_feed_batches_total``
(counters, for overlap ratios over a window), and
``consensusml_feed_inflight`` (queue occupancy at pop — the double
buffer's fill level).

Spans (``obs/tracer.py``; in the profiler's trace whenever a session is
open): ``feed.wait`` around the consumer's queue pop, and on the producer
thread ``feed.pull`` (the source's ``next``), ``feed.stage`` (the
``device_put``s) and ``feed.drain`` (the wait for the oldest transfer).
The producer blocked on a full queue is idle, not working: no span.
The producer wakes when the consumer pops a batch, which is when a round
starts, so it is the one thread that runs WHILE the device executes a
round: a recorded ``feed.stage`` span carries ``hbm_in_use``,
``hbm_peak`` and ``hbm_reserved``, the allocator's bytes read once the
``device_put``s have returned (``obs/memviz.py:record_hbm``: arrays, their
lifetime peak, the programs' workspace; outside the span's own time; no
reading while the ring does not record, or off a backend with
``memory_stats()``).

Staging-buffer safety, by backend:

- Accelerator backends: ``jax.device_put`` *copies* host memory to the
  device asynchronously. A host buffer may be rewritten only after that
  transfer completed, so the prefetcher keeps a bounded in-flight window
  and, before pulling a new item from the source, blocks (on the
  *background* thread) until the oldest in-flight transfer is done —
  then fires its ``on_done``. Sources that rotate their own host buffers
  must rotate more than ``depth + 1`` of them (the native ring sizes its
  slot count accordingly, see ``native_pipeline.plan_ring``).
- CPU backend: ``device_put``/``jnp.asarray`` may *alias* numpy memory
  instead of copying, so buffer reuse can never be made safe after the
  fact. The prefetcher therefore copies numpy leaves before placement on
  CPU. The copy happens on the background thread — still overlapped —
  and keeps the CPU test backend byte-exact under any reuse pattern.

Determinism: one producer thread, a FIFO queue, and sources that are
pure functions of ``(seed, round)`` — the delivered batch sequence is
byte-identical regardless of prefetch depth, ring threads, or whether
overlap is on at all (pinned by tests/test_prefetch.py).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, NamedTuple

import numpy as np

from consensusml_tpu.analysis import guarded_by
from consensusml_tpu.obs import get_registry, span
from consensusml_tpu.obs.memviz import IN_ROUND, record_hbm

__all__ = ["FeedItem", "DevicePrefetcher", "prefetch_to_device"]

# feed-path telemetry (docs/observability.md): is the round ever waiting
# on its data, and how full is the double buffer
_STALL = get_registry().gauge(
    "consensusml_feed_stall_seconds",
    "host wait for the current round's batch at the prefetch queue "
    "(~0 when transfer fully overlaps compute)",
)
_STALL_TOTAL = get_registry().counter(
    "consensusml_feed_stall_seconds_total",
    "cumulative feed-stall wait across rounds",
)
_BATCHES_OUT = get_registry().counter(
    "consensusml_feed_batches_total",
    "round batches delivered by the device prefetcher",
)
_INFLIGHT = get_registry().gauge(
    "consensusml_feed_inflight",
    "staged round batches ready in the prefetch queue (sampled at pop)",
)
# the prefetch window's device-byte tag for the live HBM accounting
# (obs/memviz.py): staged-ahead batches are real resident HBM the
# three-way reconciliation must be able to name, not anonymous "live"
_STAGED_BYTES = get_registry().gauge(
    "consensusml_feed_staged_bytes",
    "device bytes of round batches staged ahead by the prefetcher "
    "(queue occupancy x per-batch bytes, sampled at pop)",
)


def _hbm_args() -> dict | None:
    """``feed.stage``'s late arguments: what the chip holds while the round
    that popped the last batch runs (arrays, and the programs' workspace)
    and the process's high-water mark of its arrays."""
    sample = record_hbm(IN_ROUND)
    if sample is None:
        return None
    return {"hbm_in_use": sample.in_use, "hbm_peak": sample.peak, "hbm_reserved": sample.reserved}


class FeedItem(NamedTuple):
    """One source item: a host batch plus an optional completion hook.

    ``on_done()`` is called (on the prefetcher's background thread) once
    the device transfer of ``batch`` has completed — the point where the
    host memory backing the batch may be reused. The native ring's
    zero-copy view feed passes its slot-release here.

    ``pool`` declares how many host buffers the source rotates (the
    native ring's slot count): every undrained transfer pins one, so the
    prefetcher clamps its in-flight window to ``pool - 1`` — the
    deadlock invariant lives here, next to the pinning, not in each
    caller's sizing arithmetic.
    """

    batch: Any
    on_done: Callable[[], None] | None = None
    pool: int | None = None


class _Stop(Exception):
    """Internal: consumer closed while the producer was blocked."""


_END = object()  # the source is exhausted


@guarded_by(
    "_lock", "_closed", "_exhausted", "_error",
    "stall_seconds_total", "last_stall_s", "batches_out",
)
class DevicePrefetcher:
    """Stage the next round-batches on device while the current round runs.

    Wraps a host batch iterator (plain trees or :class:`FeedItem`s) and
    yields *device-resident* batches. ``depth`` is the double-buffer
    depth: how many staged batches may sit ready ahead of the consumer
    (2 = classic double buffering; the transfer for round r+1 overlaps
    the compute of round r).

    ``placement`` controls where leaves land: ``None`` (default device),
    a ``jax.sharding.Sharding`` / device applied to every leaf (e.g.
    ``WorkerMesh.stacked_sharding()`` so collective-backend batches land
    pre-sharded and the jitted step does no second transfer), or a
    callable ``batch -> per-leaf tree`` evaluated once on the first
    batch. ``place=False`` skips device placement entirely (multi-
    controller runs, where global arrays are assembled downstream) —
    the prefetcher then only overlaps the host-side work.

    Iterate it (it is its own iterator) or use it as a context manager;
    it closes itself when the source is exhausted, and ``close()`` is
    idempotent for early exits.
    """

    def __init__(
        self,
        source: Iterable[Any],
        depth: int = 2,
        *,
        placement: Any = None,
        place: bool = True,
        max_inflight: int | None = None,
    ):
        self.depth = max(1, int(depth))
        # how many H2D transfers may be outstanding before the producer
        # blocks on the oldest one (and fires its on_done). Sources that
        # recycle a fixed pool of host buffers — the native ring above
        # all — need this capped BELOW their pool size or the pool
        # drains and the pipeline deadlocks; pooled sources declare
        # FeedItem.pool and _run clamps the window to pool-1 itself, so
        # this knob only ever shrinks the window further. 0 = fence
        # every transfer immediately (serialized but never deadlocked).
        self.max_inflight = (
            self.depth if max_inflight is None else max(0, int(max_inflight))
        )
        self._source = iter(source)
        self._placement = placement
        self._place = place
        self._queue: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        # shared between the consumer thread, the producer thread and
        # whatever thread GC runs __del__ on — see @guarded_by above;
        # enforced by the cml-check lock-discipline pass
        self._lock = threading.Lock()
        self._error: BaseException | None = None
        self._closed = False
        self._exhausted = False
        # stats mirrored outside the registry so the benchmark and tests can read
        # this feed's numbers without diffing process-global counters
        self.stall_seconds_total = 0.0
        self.last_stall_s = 0.0
        self.batches_out = 0
        # per-batch device bytes (fixed round shape), measured on the
        # first delivered batch for the staged-bytes HBM tag
        self._batch_nbytes: int | None = None
        import jax

        self._jax = jax
        # CPU backend: jnp.asarray/device_put may ALIAS numpy memory, so
        # reused host buffers must be copied before placement (see module
        # docstring); the copy runs on the background thread.
        self._copy_host = jax.default_backend() == "cpu"
        self._thread = threading.Thread(
            target=self._run, name="device-prefetch", daemon=True
        )
        self._thread.start()

    # -- producer side (background thread) --------------------------------

    def _leaf_placement(self, batch: Any) -> Any:
        # a callable placement (sharding factory) resolves once, on the
        # first batch's structure; Sharding/Device instances are not
        # callable so the check is unambiguous
        if callable(self._placement):
            self._placement = self._placement(batch)
        return self._placement

    def _put_leaf(self, x: Any, target: Any):
        jax = self._jax
        if isinstance(x, jax.Array) and (
            target is None or getattr(x, "sharding", None) == target
        ):
            return x  # already placed — never a second transfer
        if self._copy_host and isinstance(x, np.ndarray):
            x = x.copy()
        return jax.device_put(x) if target is None else jax.device_put(x, target)

    def _stage(self, batch: Any) -> Any:
        if not self._place:
            return batch
        jax = self._jax
        with span("feed.stage", at_close=_hbm_args):
            placement = self._leaf_placement(batch)
            if placement is None or not isinstance(
                placement, (dict, list, tuple)
            ):
                return jax.tree.map(
                    lambda x: self._put_leaf(x, placement), batch
                )
            return jax.tree.map(self._put_leaf, batch, placement)

    def _enqueue(self, item: Any) -> None:
        while True:
            if self._stop.is_set():
                raise _Stop
            try:
                self._queue.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def _drain_one(self, pending: list) -> None:
        staged, on_done = pending.pop(0)
        with span("feed.drain"):
            if self._place:
                # block on the BACKGROUND thread until the H2D transfer
                # of this batch completed — only then may its host buffer
                # be rewritten / its ring slot released
                self._jax.block_until_ready(staged)
            if on_done is not None:
                on_done()

    def _run(self) -> None:
        pending: list = []  # (staged device batch, on_done), oldest first
        window = self.max_inflight
        try:
            while not self._stop.is_set():
                with span("feed.pull"):
                    item = next(self._source, _END)
                if item is _END:
                    break
                if not isinstance(item, FeedItem):
                    item = FeedItem(item)
                if item.on_done is not None and not self._place:
                    # zero-copy sources pin host memory until the
                    # transfer completes; without placement there is no
                    # transfer event to key the release on, and firing
                    # it early would hand out buffers still in use
                    raise RuntimeError(
                        "FeedItem sources (zero-copy views) require "
                        "device placement (place=True)"
                    )
                if item.pool is not None:
                    # each undrained transfer pins one buffer of the
                    # source's pool — always leave >= 1 free for its
                    # producers, whatever the caller configured
                    window = min(window, max(0, item.pool - 1))
                staged = self._stage(item.batch)
                pending.append((staged, item.on_done))
                # bound the in-flight transfer window: sources rotating K
                # host buffers are safe for K > window + 1
                while len(pending) > window:
                    self._drain_one(pending)
                self._enqueue(staged)
        except _Stop:
            pass
        except BaseException as e:  # surfaced to the consumer
            with self._lock:
                self._error = e
        finally:
            try:
                while pending:
                    self._drain_one(pending)
            except BaseException as e:
                with self._lock:
                    if self._error is None:
                        self._error = e
            try:
                self._enqueue(None)  # end-of-stream sentinel
            except _Stop:
                pass

    # -- consumer side -----------------------------------------------------

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        with self._lock:
            if self._exhausted:
                raise StopIteration
        _INFLIGHT.set(self._queue.qsize())
        with span("feed.wait"):
            t0 = time.perf_counter()
            item = self._queue.get()
            wait = time.perf_counter() - t0
        if item is None:
            with self._lock:
                self._exhausted = True
            self.close()
            with self._lock:
                err = self._error
            if err is not None:
                raise err
            raise StopIteration
        with self._lock:
            self.last_stall_s = wait
            self.stall_seconds_total += wait
            self.batches_out += 1
        _STALL.set(wait)
        _STALL_TOTAL.inc(wait)
        _BATCHES_OUT.inc()
        if self._batch_nbytes is None:
            self._batch_nbytes = sum(
                int(getattr(x, "nbytes", 0))
                for x in self._jax.tree.leaves(item)
            )
        _STAGED_BYTES.set(self._batch_nbytes * (self._queue.qsize() + 1))
        return item

    def close(self) -> None:
        """Stop the background thread and close the source. Idempotent
        — and safe against the consumer and a GC-driven ``__del__``
        racing into it from two threads (the check-and-set is atomic)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            # a next() after close() must see StopIteration, not block
            # on a queue no producer will ever feed again
            self._exhausted = True
        self._stop.set()
        # unblock a producer stuck in queue.put by draining
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        # ... and a CONSUMER blocked in __next__'s queue.get(): the
        # drain above may have swallowed the producer's end-of-stream
        # sentinel (and the producer, once stopped, never re-posts it),
        # so re-post one best-effort — a waiter wakes to StopIteration
        # instead of blocking forever on a dead queue
        try:
            self._queue.put_nowait(None)
        except queue.Full:
            pass
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            # wedged producer (hung transfer): closing the source now
            # would raise "generator already executing" over whatever
            # error the caller is propagating — leave it to the thread
            return
        close = getattr(self._source, "close", None)
        if close is not None:
            try:
                close()
            except Exception:
                pass  # teardown must never mask the caller's exception

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def prefetch_to_device(
    source: Iterable[Any],
    depth: int = 2,
    *,
    placement: Any = None,
    place: bool = True,
) -> Iterable[Any]:
    """Wrap ``source`` in a :class:`DevicePrefetcher`; ``depth <= 0``
    returns the source unchanged (overlap off — the A/B lever the
    determinism tests and ``train.py --prefetch-depth 0`` use)."""
    if depth <= 0:
        return source
    return DevicePrefetcher(source, depth, placement=placement, place=place)
