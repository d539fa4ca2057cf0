"""Per-link probes: measured latency/bandwidth for every gossip edge.

The gossip round itself runs inside one XLA program — per-edge timing
cannot be read out of it without poisoning the hot path with host syncs.
So link health is measured by a SIDEBAND probe: at telemetry cadence
(``train.py --link-probes`` + ``--telemetry-every``), the
:class:`LinkProber` times a small device-to-device transfer across each
directed edge of the active topology and feeds per-(src, dst) labeled
metrics:

- ``consensusml_link_latency_seconds{src,dst}`` — roundtrip histogram
  per edge (fine microsecond buckets — ICI one-hops live there);
- ``consensusml_link_bandwidth_bytes_per_sec{src,dst}`` — payload /
  latest latency;
- ``consensusml_link_wire_bytes_per_round{src,dst}`` — the STEADY-STATE
  gossip bytes each edge carries per round, from the engine's wire
  accounting (:func:`link_wire_bytes`);
- ``consensusml_link_probe_*`` — probe bookkeeping (rounds, total time
  spent probing — the numerator of the probes' overhead).

The probe transfer is a ``jax.device_put`` of a device-resident buffer
from the source rank's device to the destination rank's device plus a
``block_until_ready`` fence — deliberate host syncs OUTSIDE jit, on the
telemetry path only (baselined in .cml-check-baseline). On the simulated
backend every rank maps to the same device and the probe degrades to a
timed self-copy: the numbers stop meaning "wire" but stay cheap,
deterministic in shape, and keep the report schema identical.

``ConsensusEngine`` seam: :func:`link_wire_bytes` distributes
``wire_bytes_per_round`` over the topology's directed edges using the
same shift arithmetic both backends execute, so the future topology
auto-tuner (ROADMAP item 3) can rank edges by measured latency *and*
carried bytes from one metrics family.
"""

from __future__ import annotations

import time
from typing import Any, Callable

from consensusml_tpu.obs.metrics import (
    DEFAULT_LINK_LATENCY_BUCKETS,
    MetricsRegistry,
    get_registry,
)

__all__ = ["LinkProber", "link_wire_bytes", "edge_sends_per_round"]


def edge_sends_per_round(topology) -> dict[tuple[int, int], float]:
    """Payload sends per round along each directed edge.

    Parallel shifts that land on the same edge (a ring of 2's +1/-1)
    count as SEPARATE sends — they are separate ppermutes on the wire —
    which is why this walks shifts rather than :meth:`Topology.edges`
    (whose merged weights lose multiplicity). Dense (psum) topologies
    count one send to every peer (the all-reduce's logical edge set);
    time-varying topologies average over the period.
    """
    if topology.is_time_varying:
        acc: dict[tuple[int, int], float] = {}
        for phase in topology.phases:
            for e, n in edge_sends_per_round(phase).items():
                acc[e] = acc.get(e, 0.0) + n / topology.period
        return acc
    n = topology.world_size
    if topology.uses_psum:
        return {
            (src, dst): 1.0
            for dst in range(n)
            for src in range(n)
            if src != dst
        }
    out: dict[tuple[int, int], float] = {}
    for shift in topology.shifts:
        for dst in range(n):
            src = topology.shift_src(dst, shift)
            if src != dst:
                out[(src, dst)] = out.get((src, dst), 0.0) + 1.0
    return out


def link_wire_bytes(engine, params: Any) -> dict[tuple[int, int], float]:
    """Steady-state gossip bytes per round on each directed edge.

    One send's payload (``telemetry()``'s ``wire_bytes_per_neighbor``)
    times that edge's sends per round times ``gossip_steps`` — summed
    over ONE worker's outgoing edges this reproduces its
    ``wire_bytes_per_round`` (up to push-sum's mass scalar) for ppermute
    topologies; the full edge dict covers every worker. Dense (psum) topologies spread
    the all-reduce payload over every logical peer edge, so their sum
    intentionally exceeds the one-send accounting. ``params`` may be
    shape structs."""
    t = engine.telemetry(params)
    per_send = t["wire_bytes_per_neighbor"] * max(
        engine.config.gossip_steps, 1
    )
    return {
        e: per_send * sends
        for e, sends in edge_sends_per_round(engine.topology).items()
    }


class LinkProber:
    """Times one transfer per directed topology edge and feeds the
    ``consensusml_link_*`` families.

    ``devices``: rank -> jax.Device list (the collective backend's mesh
    order). None => single-device mode (simulated backend): all ranks
    share ``jax.devices()[0]`` and probes are self-copies. On
    multi-controller runs each process keeps only the edges between its
    OWN devices (a local ``device_put`` cannot reach another host);
    cross-host edges land on ``consensusml_link_edges_remote``.
    ``transfer``: override ``(src, dst) -> None`` — the
    test/chaos hook (a transfer that sleeps makes that link measurably
    slow, which must surface in ``slowest()`` and the cluster report).
    ``max_edges``: probe at most this many edges per round (dense
    world-N is N*(N-1) edges); the overflow is counted loudly on
    ``consensusml_link_edges_skipped``, never silently dropped.
    """

    def __init__(
        self,
        topology,
        registry: MetricsRegistry | None = None,
        devices: list | None = None,
        payload_bytes: int = 1 << 16,
        transfer: Callable[[int, int], None] | None = None,
        max_edges: int = 512,
    ):
        self.topology = topology
        self.registry = registry if registry is not None else get_registry()
        self.payload_bytes = int(payload_bytes)
        self._devices = devices
        self._transfer = transfer
        self._bufs: dict[int, Any] = {}  # src rank -> staged device buffer
        edges = [(s, d) for s, d, _ in topology.edges()]
        # Multi-controller: a process can only device_put between devices
        # IT addresses, so with the default transfer each process keeps
        # only the edges whose BOTH endpoints are process-local. That
        # partitions intra-host edges exactly once across the fleet (an
        # edge's devices share one owning process); cross-host edges are
        # counted on consensusml_link_edges_remote rather than probed —
        # measuring them needs a collective-phased probe, not a sideband
        # device_put (future auto-tuner work). Injected transfers see
        # every edge: they define their own reachability.
        self.remote_edges = 0
        if transfer is None and devices is not None:
            import jax

            pid = jax.process_index()
            local = [
                e
                for e in edges
                if devices[e[0] % len(devices)].process_index == pid
                and devices[e[1] % len(devices)].process_index == pid
            ]
            self.remote_edges = len(edges) - len(local)
            edges = local
        self.skipped_edges = max(0, len(edges) - max_edges)
        self.edges = edges[: max_edges]
        self._stats: dict[tuple[int, int], tuple[int, float]] = {
            e: (0, 0.0) for e in self.edges
        }
        self._warmed = False
        r = self.registry
        r.gauge(
            "consensusml_link_edges",
            "directed gossip edges the link prober covers",
        ).set(len(self.edges))
        r.gauge(
            "consensusml_link_edges_skipped",
            "edges past the prober's max_edges cap (0 = full coverage)",
        ).set(self.skipped_edges)
        r.gauge(
            "consensusml_link_edges_remote",
            "cross-process edges this rank cannot probe with a local "
            "device_put (multi-controller; 0 on single-process runs)",
        ).set(self.remote_edges)
        r.gauge(
            "consensusml_link_probe_payload_bytes",
            "payload size of one link probe transfer",
        ).set(self.payload_bytes)
        self._m_rounds = r.counter(
            "consensusml_link_probe_rounds_total",
            "completed link-probe sweeps (one timing per edge each)",
        )
        self._m_spent = r.counter(
            "consensusml_link_probe_seconds_total",
            "wall time spent probing links (the probe's total overhead)",
        )

    # -- the default device-to-device transfer -----------------------------
    def _device(self, rank: int):
        import jax

        if self._devices is not None:
            return self._devices[rank % len(self._devices)]
        return jax.devices()[0]

    def _buf(self, rank: int):
        buf = self._bufs.get(rank)
        if buf is None:
            import jax
            import jax.numpy as jnp

            buf = jax.device_put(
                jnp.zeros((self.payload_bytes // 4,), jnp.float32),
                self._device(rank),
            )
            buf.block_until_ready()
            self._bufs[rank] = buf
        return buf

    def _default_transfer(self, src: int, dst: int) -> None:
        import jax

        # device-resident source buffer -> destination device, fenced:
        # the one-hop transfer cost the gossip wire pays per payload.
        # Host syncs by design (telemetry sideband, never inside jit).
        jax.device_put(self._buf(src), self._device(dst)).block_until_ready()

    # -- probing -----------------------------------------------------------
    def probe_round(self) -> dict[tuple[int, int], float]:
        """One timed transfer per edge; returns {edge: seconds} and
        feeds the labeled histograms/gauges."""
        transfer = self._transfer or self._default_transfer
        if not self._warmed:
            # throwaway sweep: first-touch allocation and dispatch-path
            # warmup must not pollute the recorded latencies
            for src, dst in self.edges:
                transfer(src, dst)
            self._warmed = True
        t_sweep = time.perf_counter()
        out: dict[tuple[int, int], float] = {}
        for src, dst in self.edges:
            t0 = time.perf_counter()
            transfer(src, dst)
            dt = time.perf_counter() - t0
            out[(src, dst)] = dt
            n, tot = self._stats[(src, dst)]
            self._stats[(src, dst)] = (n + 1, tot + dt)
            labels = {"src": src, "dst": dst}
            self.registry.histogram(
                "consensusml_link_latency_seconds",
                "one-hop probe transfer time per directed gossip edge",
                buckets=DEFAULT_LINK_LATENCY_BUCKETS,
                labels=labels,
            ).observe(dt)
            self.registry.gauge(
                "consensusml_link_bandwidth_bytes_per_sec",
                "probe payload / latest probe latency per edge",
                labels=labels,
            ).set(self.payload_bytes / dt if dt > 0 else 0.0)
        self._m_rounds.inc()
        self._m_spent.inc(time.perf_counter() - t_sweep)
        return out

    def slowest(self, k: int | None = None) -> list[dict[str, Any]]:
        """Edges ranked by mean probed latency, slowest first — the
        ordering the cluster report and the future topology auto-tuner
        consume."""
        rows = [
            {
                "src": s,
                "dst": d,
                "probes": n,
                "mean_latency_s": tot / n,
            }
            for (s, d), (n, tot) in self._stats.items()
            if n > 0
        ]
        rows.sort(key=lambda r: -r["mean_latency_s"])
        return rows if k is None else rows[:k]

    # -- steady-state wire rates ------------------------------------------
    def record_wire_rates(self, engine, params: Any) -> None:
        """Set the per-edge steady-state wire gauges from the engine's
        accounting (host-side, once at startup — shape structs fine)."""
        for (src, dst), nbytes in link_wire_bytes(engine, params).items():
            self.registry.gauge(
                "consensusml_link_wire_bytes_per_round",
                "steady-state gossip bytes per round on each directed "
                "edge (engine wire accounting)",
                labels={"src": src, "dst": dst},
            ).set(nbytes)
