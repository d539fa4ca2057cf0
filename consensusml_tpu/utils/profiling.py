"""Tracing/profiling subsystem (SURVEY.md §5 aux subsystems).

Two layers, smallest first (a region of traced computation is named by
``obs.span``):

- :class:`RoundTimer` — wall-clock stats over training rounds. Dispatch
  is asynchronous, so the timer fences each lap by fetching a scalar to
  the host: the value cannot arrive before the round that produced it.
  The fence is the ``round.fence`` span.
- :func:`trace` — a context manager around ``jax.profiler`` start/stop
  that dumps an xprof/TensorBoard trace directory for deep dives
  (per-op device timelines, HBM traffic, ICI collectives).

Wired into ``train.py`` via ``--profile-dir`` (trace of a few steady-state
rounds) and the end-of-run round-time summary.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Iterator

import jax
import numpy as np

from consensusml_tpu.obs.tracer import span

__all__ = ["RoundTimer", "RoundStats", "trace", "fence"]


def fence(tree: Any) -> None:
    """Execution barrier: fetch one scalar element per leaf to host.

    A device->host copy cannot complete before the producing computation
    has, so fetching is a valid fence on every backend (and the round
    loop needs the scalar anyway).
    """
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "addressable_shards"):
            shard = leaf.addressable_shards[0].data
            # slice ON DEVICE first: device_get of the raw shard would copy
            # the whole buffer to host, a hidden D2H if fencing on params
            first = shard.reshape(-1)[:1] if shard.size else shard
            np.asarray(jax.device_get(first))
        else:
            np.asarray(leaf).ravel()[:1]


@dataclasses.dataclass(frozen=True)
class RoundStats:
    """Summary of per-round wall times (seconds)."""

    count: int
    mean_s: float
    p50_s: float
    p95_s: float
    min_s: float
    max_s: float
    total_s: float

    def format(self) -> str:
        return (
            f"rounds={self.count} mean={self.mean_s * 1e3:.1f}ms "
            f"p50={self.p50_s * 1e3:.1f}ms p95={self.p95_s * 1e3:.1f}ms "
            f"min={self.min_s * 1e3:.1f}ms max={self.max_s * 1e3:.1f}ms"
        )


class RoundTimer:
    """Collects per-round wall times with an honest device fence per lap.

    Usage::

        timer = RoundTimer(warmup=1)
        for batch in batches:
            with timer.lap(metrics_fn=lambda: metrics):
                state, metrics = step(state, batch)
        print(timer.stats().format())

    ``lap`` fences on whatever the ``metrics_fn`` thunk returns AFTER the
    body ran (the body rebinds ``metrics``), so the measured lap includes
    the full device execution of the step, not just its dispatch. The
    first ``warmup`` laps (compilation) are recorded separately.
    """

    def __init__(self, warmup: int = 1):
        self._warmup = warmup
        self._laps: list[float] = []
        self._warmup_laps: list[float] = []
        # how long the last lap's FENCE blocked: dispatch returned, the
        # host sat waiting for the device to drain — the stall the
        # overlap-gossip scheduling is supposed to shrink. Exposed so
        # telemetry can gauge it (consensusml_round_stall_seconds).
        self.last_fence_s: float = 0.0
        self.last_lap_s: float = 0.0

    @contextlib.contextmanager
    def lap(self, metrics_fn=None) -> Iterator[None]:
        t0 = time.time()
        yield
        if metrics_fn is not None:
            t_fence = time.time()
            with span("round.fence"):
                fence(metrics_fn())
            self.last_fence_s = time.time() - t_fence
        else:
            self.last_fence_s = 0.0
        dt = time.time() - t0
        self.last_lap_s = dt
        if len(self._warmup_laps) < self._warmup:
            self._warmup_laps.append(dt)
        else:
            self._laps.append(dt)

    @property
    def laps(self) -> list[float]:
        return list(self._laps)

    def stats(self) -> RoundStats:
        laps = self._laps or self._warmup_laps
        if not laps:
            return RoundStats(0, math.nan, math.nan, math.nan, math.nan, math.nan, 0.0)
        a = np.asarray(laps)
        return RoundStats(
            count=len(laps),
            mean_s=float(a.mean()),
            p50_s=float(np.percentile(a, 50)),
            p95_s=float(np.percentile(a, 95)),
            min_s=float(a.min()),
            max_s=float(a.max()),
            total_s=float(a.sum()),
        )


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Dump an xprof trace of the enclosed block to ``log_dir``.

    View with TensorBoard's profile plugin or xprof. Wraps
    ``jax.profiler.start_trace``/``stop_trace`` so a mid-block exception
    still stops the trace (leaving a valid dump).
    """
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
