"""Cost ledger: per-executable compiled cost/memory accounting.

Every gauge and span in the stack so far answers "how long did it take";
nothing answers "how long SHOULD it have taken". This module closes that
gap at the place XLA already knows the answer: ``lower().compile()``.
For every executable the stack owns (the train step, the gossip round
under its active bucket plan, the paged prefill/decode serving stages,
the hot-swap staging transfer) the :class:`CostLedger` records

- **compiled cost**: FLOPs and bytes-accessed from
  ``Compiled.cost_analysis()`` — the roofline inputs;
- **compiled memory**: argument/output/temp/generated-code bytes from
  ``Compiled.memory_analysis()`` plus the live-footprint combination
  (arguments + temps + outputs − aliases) the HBM reconciliation uses
  (docs/memory.md "Reconciliation");
- **compile wall time**: measured around the ledger's own
  ``lower().compile()`` call.

Rows land in labeled ``consensusml_cost_*`` gauge families (one child
per ``executable=`` label) and the monotonic ``consensusml_compile_*``
counters, so the cluster snapshot / ``tools/obs_report.py`` carry the
full per-executable table (docs/observability.md "Cost attribution").

Registration is ANALYSIS-ONLY: the ledger lowers with shape structs (or
concrete arrays — nothing executes either way) through JAX's AOT path,
which never touches the jit dispatch cache — the zero-recompile
contract's ``compile_counts()`` stays byte-identical after wiring (the
``pytest -m profiling`` tier pins it). The price is one DUPLICATE
compile per registered executable, paid once at registration — which is
why ``train.py --cost-ledger`` is opt-in while the run-time side
(:meth:`CostLedger.observe_measured`, a few gauge stores) is cheap
enough for every telemetry tick.

Expected-vs-measured attribution: :meth:`observe_measured` pairs a
measured span time (the PR 10 round timeline, engine SLO stats) with
the executable's roofline floors —

    compute floor = flops / peak_flops_per_s
    memory  floor = bytes_accessed / peak_bytes_per_s
    expected      = max(compute floor, memory floor)

— and reports which bound binds plus the measured/floor ratio ("this
round is 1.7x its bytes-bound floor; the gap is the fence"). Peaks come
from one table keyed by ``device_kind`` (overridable per ledger), and a
device that is not in it is an error rather than a borrowed roofline.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, Callable

from consensusml_tpu.analysis import guarded_by
from consensusml_tpu.obs.metrics import MetricsRegistry, get_registry

__all__ = [
    "DEVICE_PEAKS",
    "device_peaks",
    "ExecutableCost",
    "CostLedger",
    "get_cost_ledger",
]

# Roofline anchors by ``device_kind`` as JAX reports it: (peak FLOP/s,
# peak HBM bytes/s, host<->device staging bytes/s). The third is its own
# column because transfer rows (hot-swap artifact stage, prefetch
# windows) cross PCIe/host links, NOT the HBM bus — flooring them
# against the HBM figure would understate the floor ~30x and read every
# healthy transfer as an anomaly. A device that is not in the table is
# an error (:func:`device_peaks`), never another device's peaks.
DEVICE_PEAKS: dict[str, tuple[float, float, float]] = {
    # TPU v5e, one chip: 197 TFLOP/s bf16, 819 GB/s HBM2e (Google Cloud
    # documentation, "TPU v5e"); staging figure is a PCIe-class anchor
    "TPU v5 lite": (197e12, 819e9, 30e9),
    # the CPU test mesh: order-of-magnitude host anchors, there so the
    # attribution plumbing runs in tier-1 — never a device metric
    "cpu": (5e10, 2e10, 10e9),
}


def device_peaks(device_kind: str) -> tuple[float, float, float]:
    """``DEVICE_PEAKS[device_kind]``, or a ``KeyError`` that says what to
    do about a device nobody has written down yet."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no roofline peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}): add its published peaks to "
            "obs.costs.DEVICE_PEAKS with their source, or pass "
            "peak_flops_per_s / peak_bytes_per_s / "
            "peak_transfer_bytes_per_s to CostLedger"
        ) from None


def _tree_device_bytes(tree: Any) -> int:
    """Total leaf bytes of an array tree (shape structs count too)."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
            continue
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            continue
        n = int(np.prod(getattr(leaf, "shape", ()) or (1,)))
        try:
            itemsize = np.dtype(dtype).itemsize
        except TypeError:  # extended dtype (typed PRNG key): 4B words
            itemsize = 4
        total += n * itemsize
    return total


@dataclasses.dataclass(frozen=True)
class ExecutableCost:
    """One ledger row: what XLA compiled for one executable."""

    name: str
    platform: str
    flops: float
    bytes_accessed: float
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    alias_bytes: int
    generated_code_bytes: int
    compile_s: float
    kind: str = "compiled"  # "compiled" | "transfer"
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def peak_bytes(self) -> int:
        """XLA's live device footprint for one invocation: arguments +
        temps + outputs − aliases (donated inputs alias their outputs) —
        the number the three-way HBM reconciliation compares against
        the analytic model and the live runtime (docs/memory.md)."""
        return (
            self.argument_bytes
            + self.temp_bytes
            + self.output_bytes
            - self.alias_bytes
        )

    def floors_s(
        self,
        peak_flops_per_s: float,
        peak_bytes_per_s: float,
        peak_transfer_bytes_per_s: float | None = None,
    ) -> tuple[float, float]:
        """(compute floor, memory floor) in seconds. Transfer rows have
        no FLOPs: their floor is bytes over the host<->device staging
        bandwidth (``peak_transfer_bytes_per_s``), not the HBM bus."""
        compute = self.flops / peak_flops_per_s if peak_flops_per_s else 0.0
        if self.kind == "compiled":
            moved, bw = self.bytes_accessed, peak_bytes_per_s
        else:
            moved = float(self.argument_bytes)
            bw = peak_transfer_bytes_per_s or peak_bytes_per_s
        memory = moved / bw if bw else 0.0
        return compute, memory

    def as_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["peak_bytes"] = self.peak_bytes
        return d


@guarded_by("_lock", "_rows", "_measured")
class CostLedger:
    """Get-or-create per-executable cost table + metric exporter.

    One process-wide instance (:func:`get_cost_ledger`) feeds the global
    registry; tests build private instances over private
    registries. Thread-safe: serving registers from the client thread
    while the engine thread serves, and observe_measured may come from a
    telemetry tick.
    """

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        device_kind: str | None = None,
        peak_flops_per_s: float | None = None,
        peak_bytes_per_s: float | None = None,
        peak_transfer_bytes_per_s: float | None = None,
    ):
        import jax

        self.registry = registry if registry is not None else get_registry()
        self.platform = jax.default_backend()
        self.device_kind = device_kind or jax.devices()[0].device_kind
        given = (peak_flops_per_s, peak_bytes_per_s, peak_transfer_bytes_per_s)
        # the table is only consulted for what the caller left out
        table = given if all(given) else device_peaks(self.device_kind)
        self.peak_flops_per_s = peak_flops_per_s or table[0]
        self.peak_bytes_per_s = peak_bytes_per_s or table[1]
        self.peak_transfer_bytes_per_s = peak_transfer_bytes_per_s or table[2]
        self._rows: dict[str, ExecutableCost] = {}
        self._measured: dict[str, float] = {}
        self._lock = threading.RLock()
        reg = self.registry
        # monotonic compile-side counters (the "is something recompiling
        # behind my back" signal reads these, so they must only go up)
        self._m_compiles = reg.counter(
            "consensusml_compile_total",
            "executables lowered+compiled into the cost ledger",
        )
        self._m_compile_s = reg.counter(
            "consensusml_compile_seconds_total",
            "cumulative ledger compile wall time",
        )

    # -- registration -----------------------------------------------------

    def register(
        self,
        name: str,
        fn: Callable,
        *args: Any,
        meta: dict[str, Any] | None = None,
        **kwargs: Any,
    ) -> ExecutableCost:
        """Lower + compile ``fn(*args, **kwargs)`` and record its row.

        ``fn`` must be jit-wrapped (``hasattr(fn, "lower")``); bare
        callables are wrapped on the fly. ``args`` may be concrete
        arrays or ``jax.ShapeDtypeStruct``s — NOTHING executes, no
        device memory is touched beyond XLA's compile arena, and the
        jit dispatch cache (``_cache_size`` / ``compile_counts()``) is
        not populated (AOT path). Re-registering a name overwrites its
        row (a re-lowered executable after a world/shape change).
        """
        import jax

        if not hasattr(fn, "lower"):
            fn = jax.jit(fn)
        t0 = time.perf_counter()
        compiled = fn.lower(*args, **kwargs).compile()
        compile_s = time.perf_counter() - t0

        try:
            ca = compiled.cost_analysis()
        except Exception:  # backend without cost analysis
            ca = None
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        ca = ca or {}
        flops = float(ca.get("flops", 0.0) or 0.0)
        bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)

        try:
            ma = compiled.memory_analysis()
        except Exception:
            ma = None
        row = ExecutableCost(
            name=name,
            platform=self.platform,
            flops=flops,
            bytes_accessed=bytes_accessed,
            argument_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
            output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
            temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
            alias_bytes=int(getattr(ma, "alias_size_in_bytes", 0)),
            generated_code_bytes=int(
                getattr(ma, "generated_code_size_in_bytes", 0)
            ),
            compile_s=compile_s,
            meta=dict(meta or {}),
        )
        self._record(row)
        return row

    def register_transfer(
        self, name: str, tree: Any, meta: dict[str, Any] | None = None
    ) -> ExecutableCost:
        """Record a host↔device staging transfer (the hot-swap artifact
        stage, a prefetch window) as a bytes-only row: no FLOPs, no
        compile — its floor is pure bandwidth."""
        nbytes = _tree_device_bytes(tree)
        row = ExecutableCost(
            name=name,
            platform=self.platform,
            flops=0.0,
            bytes_accessed=float(nbytes),
            argument_bytes=nbytes,
            output_bytes=nbytes,
            temp_bytes=0,
            alias_bytes=nbytes,  # staged in place: not double-resident
            generated_code_bytes=0,
            compile_s=0.0,
            kind="transfer",
            meta=dict(meta or {}),
        )
        self._record(row)
        return row

    def _record(self, row: ExecutableCost) -> None:
        reg = self.registry
        labels = {"executable": row.name}
        reg.gauge(
            "consensusml_cost_flops",
            "compiled FLOPs per invocation (XLA cost analysis)",
            labels=labels,
        ).set(row.flops)
        reg.gauge(
            "consensusml_cost_bytes_accessed",
            "compiled bytes accessed per invocation (XLA cost analysis)",
            labels=labels,
        ).set(row.bytes_accessed)
        reg.gauge(
            "consensusml_cost_argument_bytes",
            "compiled argument buffer bytes",
            labels=labels,
        ).set(row.argument_bytes)
        reg.gauge(
            "consensusml_cost_output_bytes",
            "compiled output buffer bytes",
            labels=labels,
        ).set(row.output_bytes)
        reg.gauge(
            "consensusml_cost_temp_bytes",
            "compiled temp buffer bytes (XLA scratch)",
            labels=labels,
        ).set(row.temp_bytes)
        reg.gauge(
            "consensusml_cost_generated_code_bytes",
            "compiled program code size",
            labels=labels,
        ).set(row.generated_code_bytes)
        reg.gauge(
            "consensusml_cost_peak_bytes",
            "compiled live footprint: arguments + temps + outputs - aliases",
            labels=labels,
        ).set(row.peak_bytes)
        reg.gauge(
            "consensusml_compile_seconds",
            "ledger-measured lower+compile wall time for this executable",
            labels=labels,
        ).set(row.compile_s)
        if row.kind == "compiled":
            self._m_compiles.inc()
            self._m_compile_s.inc(row.compile_s)
        with self._lock:
            self._rows[row.name] = row

    # -- queries ----------------------------------------------------------

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._rows)

    def row(self, name: str) -> ExecutableCost | None:
        with self._lock:
            return self._rows.get(name)

    def rows(self) -> list[ExecutableCost]:
        with self._lock:
            return [self._rows[k] for k in sorted(self._rows)]

    # -- run-time attribution --------------------------------------------

    def observe_measured(self, name: str, seconds: float) -> dict[str, Any]:
        """Pair a measured wall time with ``name``'s modeled cost.

        Feeds the ``consensusml_cost_{measured,expected}_seconds`` and
        ``consensusml_cost_floor_ratio`` gauges and returns the
        attribution row. Raises ``KeyError`` for an unregistered name —
        a silent typo here would report an executable as free."""
        row = self.row(name)
        if row is None:
            raise KeyError(
                f"executable {name!r} is not in the cost ledger "
                f"(registered: {self.names()})"
            )
        with self._lock:
            self._measured[name] = float(seconds)
        attr = self.attribution(name)
        labels = {"executable": name}
        reg = self.registry
        reg.gauge(
            "consensusml_cost_measured_seconds",
            "measured wall time paired with this executable's cost row",
            labels=labels,
        ).set(seconds)
        reg.gauge(
            "consensusml_cost_expected_seconds",
            "roofline floor: max(flops/peak_flops, bytes/peak_bw)",
            labels=labels,
        ).set(attr["expected_s"])
        reg.gauge(
            "consensusml_cost_floor_ratio",
            "measured / roofline floor (1.0 = at the hardware bound)",
            labels=labels,
        ).set(attr["ratio_to_floor"])
        return attr

    def attribution(self, name: str) -> dict[str, Any]:
        """Expected-vs-measured row for one executable (measured fields
        are NaN until :meth:`observe_measured` pairs a wall time)."""
        row = self.row(name)
        if row is None:
            raise KeyError(f"executable {name!r} is not in the cost ledger")
        compute_s, memory_s = row.floors_s(
            self.peak_flops_per_s,
            self.peak_bytes_per_s,
            self.peak_transfer_bytes_per_s,
        )
        expected = max(compute_s, memory_s)
        if row.kind == "transfer":
            bound = "transfer"
        else:
            bound = "compute" if compute_s >= memory_s else "memory"
        with self._lock:
            measured = self._measured.get(name, math.nan)
        ratio = measured / expected if expected > 0 else math.nan
        return {
            "executable": name,
            "kind": row.kind,
            "bound": bound,
            "compute_floor_s": compute_s,
            "memory_floor_s": memory_s,
            "expected_s": expected,
            "measured_s": measured,
            "ratio_to_floor": ratio,
            "unattributed_s": (
                max(0.0, measured - expected)
                if not math.isnan(measured)
                else math.nan
            ),
        }

    def snapshot(self) -> dict[str, Any]:
        """The full table as one JSON-able doc (cluster snapshots,
        obs_report)."""
        out = []
        for row in self.rows():
            d = row.as_dict()
            d.update(self.attribution(row.name))
            out.append(d)
        return {
            "platform": self.platform,
            "device_kind": self.device_kind,
            "peak_flops_per_s": self.peak_flops_per_s,
            "peak_bytes_per_s": self.peak_bytes_per_s,
            "peak_transfer_bytes_per_s": self.peak_transfer_bytes_per_s,
            "executables": out,
        }


_GLOBAL: CostLedger | None = None
_GLOBAL_LOCK = threading.Lock()


def get_cost_ledger() -> CostLedger:
    """The process-wide ledger over the global metrics registry (built
    lazily so importing obs never touches the jax backend)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = CostLedger()
        return _GLOBAL
