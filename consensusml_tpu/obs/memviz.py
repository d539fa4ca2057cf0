"""Live HBM accounting + three-way memory reconciliation.

``tools/hbm_model.py`` PREDICTS per-device bytes from first principles;
the cost ledger (:mod:`consensusml_tpu.obs.costs`) records what XLA
COMPILED; the runtime knows what is actually LIVE. Until now only the
first existed as a number anyone could alert on — this module makes all
three first-class gauges and reconciles them:

- **analytic** — ``hbm_model.predict()``'s peak model (state + batch +
  max(activations, codec transients) + payloads). Authoritative BEFORE a
  run exists: capacity planning, "does llama_lora fit a v5e".
- **compiled** — the ledger's ``memory_analysis()`` live footprint
  (arguments + temps + outputs − aliases). Authoritative for ONE
  executable: what XLA will reserve when that program runs.
- **live** — what the runtime's ``device.memory_stats()`` read WHILE A
  ROUND RAN (:func:`record_hbm` on the feed's thread, ``where="feed.stage"``),
  where one was sampled; else its ``peak_bytes_in_use``, the PROCESS's
  lifetime high-water mark, which set-up programs, evaluation and
  checkpoint staging raise too and nothing resets; either with its
  ``bytes_reserved``, where the TPU runtime keeps its programs'
  temporaries (``bytes_in_use`` counts arrays alone); else (the CPU backend
  has no ``memory_stats()``) the ``jax.live_arrays()`` total, a FLOOR
  that cannot see XLA temps, and the compiled number is the peak
  authority. Authoritative for the PROCESS: leaks, fragmentation,
  serving headroom.

Pairwise drift lands on ``consensusml_hbm_drift_pct{pair=...}`` so a
model that stops matching reality pages someone instead of rotting in a
doc table (docs/memory.md "Reconciliation"). The serving engine
additionally tags its big resident consumers — block-pool pages
(``consensusml_pool_hbm_bytes`` / ``consensusml_pool_hbm_free_bytes``)
and the params tree (``consensusml_serve_params_bytes``) — so per-engine
KV headroom is a gauge the fleet router can place traffic on, and the
prefetcher reports its staged window (``consensusml_feed_staged_bytes``).
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, NamedTuple

from consensusml_tpu.obs.metrics import MetricsRegistry, get_registry

__all__ = [
    "live_array_bytes",
    "device_memory_stats",
    "HbmSample",
    "hbm_sample",
    "record_hbm",
    "IN_ROUND",
    "compiled_footprint",
    "load_tool",
    "HbmAccountant",
    "reconcile_config",
]


def live_array_bytes() -> dict[str, Any]:
    """Sum of all live jax array buffers in this process.

    Walks ``jax.live_arrays()`` — host-side bookkeeping, no device sync,
    cheap enough for a telemetry tick. Deleted-but-unreleased buffers
    (donated inputs mid-dispatch) may still count for one tick; that
    jitter is why the reconciliation tolerance is a band, not equality.
    """
    import jax

    total = 0
    count = 0
    for a in jax.live_arrays():
        try:
            total += int(a.nbytes)
        except Exception:  # deleted under us mid-walk
            continue
        count += 1
    return {"bytes": total, "arrays": count}


def device_memory_stats(device: Any = None) -> dict[str, float] | None:
    """The runtime's own accounting (``peak_bytes_in_use`` etc.), or
    None where the backend has none (the CPU backend)."""
    import jax

    dev = device if device is not None else jax.local_devices()[0]
    try:
        stats = dev.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: float(v) for k, v in stats.items()}


class HbmSample(NamedTuple):
    """One reading of a device's allocator (``memory_stats()``), in bytes.

    ``in_use`` and ``peak`` (the PROCESS's lifetime high-water mark of
    ``in_use``; nothing resets it) count ARRAYS: on the TPU runtime a
    running program's temporaries are in neither. They are in
    ``reserved`` (``bytes_reserved``; 0 where the backend has no such
    key): ONE workspace for the programs that have run, as large as the
    largest's temporaries, taken at a program's first run, kept between
    runs and given up when arrays need the room or the program is dropped
    (proved on the chip, ``tests/kernels_tpu_child.py hbm_sampler``,
    PERF.md section 6 PR 35). What the chip holds is ``in_use + reserved``.
    """

    in_use: int
    peak: int
    limit: int
    reserved: int


def hbm_sample(devices: Any = None) -> HbmSample | None:
    """The allocator's reading on the fullest local device (the most bytes
    in use now), or None where the backend has no ``memory_stats()`` (the
    CPU). One runtime call a device, tens of microseconds: cheap enough
    for the feed's thread once a batch."""
    if devices is None:
        import jax

        devices = jax.local_devices()
    best = None
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            continue
        if not stats or stats.get("bytes_in_use") is None:
            continue
        sample = HbmSample(
            int(stats["bytes_in_use"]),
            int(stats.get("peak_bytes_in_use") or 0),
            int(stats.get("bytes_limit") or 0),
            int(stats.get("bytes_reserved") or 0),
        )
        if best is None or sample.in_use > best.in_use:
            best = sample
    return best


# the ``where`` of the samples taken while a round runs: the prefetcher's
# producer, after it staged the next batch (data/prefetch.py)
IN_ROUND = "feed.stage"


def record_hbm(
    where: str, registry: MetricsRegistry | None = None, devices: Any = None
) -> HbmSample | None:
    """One :func:`hbm_sample` into ``consensusml_hbm_in_use_bytes{where=}``
    and its running maximum ``consensusml_hbm_in_use_max_bytes{where=}``;
    returns the sample (None, and nothing recorded, off a backend with
    ``memory_stats()``). ``where`` says which boundary sampled:
    :data:`IN_ROUND`, ``compile`` (a compile-log record closing),
    ``tick`` (:meth:`HbmAccountant.tick`)."""
    sample = hbm_sample(devices)
    if sample is None:
        return None
    reg = registry if registry is not None else get_registry()
    labels = {"where": where}
    reg.gauge(
        "consensusml_hbm_in_use_bytes",
        "runtime bytes_in_use of the fullest local device, by the "
        "boundary that sampled it (feed.stage = while a round runs)",
        labels=labels,
    ).set(sample.in_use)
    reg.gauge(
        "consensusml_hbm_reserved_bytes",
        "runtime bytes_reserved of the same device at the same boundary: "
        "what the TPU runtime holds for its programs' workspaces, which "
        "bytes_in_use and its peak leave out",
        labels=labels,
    ).set(sample.reserved)
    reg.gauge(
        "consensusml_hbm_in_use_max_bytes",
        "running maximum of consensusml_hbm_in_use_bytes per boundary",
        labels=labels,
    ).set_max(sample.in_use)
    return sample


def compiled_footprint(ma: Any) -> int:
    """XLA's live device footprint from a ``memory_analysis()`` result:
    arguments + temps + outputs − aliases (donated state aliases its
    outputs, so this is what the device actually holds at once). The
    ONE definition shared by the cost ledger, ``tools/hbm_model.py
    --measure`` and the reconciliation below."""
    return int(
        ma.argument_size_in_bytes
        + ma.temp_size_in_bytes
        + ma.output_size_in_bytes
        - ma.alias_size_in_bytes
    )


def _drift_pct(a: float, b: float) -> float:
    """Signed drift of ``a`` relative to ``b`` in percent."""
    if not b:
        return math.nan
    return 100.0 * (a - b) / b


class HbmAccountant:
    """Live HBM gauges + the three-way reconciliation writer."""

    def __init__(
        self, registry: MetricsRegistry | None = None, device: Any = None
    ):
        self.registry = registry if registry is not None else get_registry()
        self.device = device
        reg = self.registry
        self._g_live = reg.gauge(
            "consensusml_hbm_live_bytes",
            "bytes held by live jax arrays in this process (floor on "
            "runtimes without memory_stats: XLA temps are invisible)",
        )
        self._g_limit = reg.gauge(
            "consensusml_hbm_limit_bytes",
            "runtime bytes_limit (NaN when unavailable)",
        )
        self._live_peak = 0.0  # high-water mark of our own live samples

    def tick(self) -> dict[str, Any]:
        """One sample: refresh the live gauges (telemetry-tick cadence)."""
        live = live_array_bytes()
        self._live_peak = max(self._live_peak, float(live["bytes"]))
        self._g_live.set(live["bytes"])
        devices = None if self.device is None else [self.device]
        in_use, peak, limit, reserved = record_hbm("tick", self.registry, devices) or (math.nan,) * 4
        self._g_limit.set(limit)
        return {
            "time_s": time.time(),
            "live_bytes": live["bytes"],
            "live_arrays": live["arrays"],
            "runtime_in_use_bytes": in_use,
            "runtime_reserved_bytes": reserved,
            "runtime_peak_bytes": peak,
            "runtime_limit_bytes": limit,
        }

    @property
    def live_peak_bytes(self) -> float:
        """Best live peak this accountant knows (docs/memory.md
        "Reconciliation"): the largest ``bytes_in_use`` sampled while a
        round ran (:data:`IN_ROUND`, in this accountant's registry) plus
        the ``bytes_reserved`` read there, where a round was sampled;
        else the runtime's lifetime ``peak_bytes_in_use``, set-up
        included, plus what it reserves now; else the high-water mark
        of the live-array samples taken so far."""
        where = {"where": IN_ROUND}
        in_round = self.registry.gauge("consensusml_hbm_in_use_max_bytes", labels=where).value
        if in_round > 0:
            return in_round + self.registry.gauge("consensusml_hbm_reserved_bytes", labels=where).value
        stats = device_memory_stats(self.device)
        if stats and stats.get("peak_bytes_in_use"):
            return float(stats["peak_bytes_in_use"]) + float(stats.get("bytes_reserved") or 0.0)
        return self._live_peak

    def reconcile(
        self,
        analytic_bytes: float | None,
        compiled_bytes: float | None,
        live_peak_bytes: float | None = None,
    ) -> dict[str, Any]:
        """Set the three absolute gauges + pairwise drift gauges and
        return the reconciliation doc. ``None`` sides render as NaN and
        drop out of the drift pairs rather than faking a zero."""
        if live_peak_bytes is None:
            live_peak_bytes = self.live_peak_bytes
        reg = self.registry
        vals = {
            "analytic": analytic_bytes,
            "compiled": compiled_bytes,
            "live": live_peak_bytes,
        }
        reg.gauge(
            "consensusml_hbm_analytic_bytes",
            "tools/hbm_model.py predicted per-device peak",
        ).set(math.nan if analytic_bytes is None else analytic_bytes)
        reg.gauge(
            "consensusml_hbm_compiled_bytes",
            "XLA memory_analysis live footprint (args+temps+outputs-aliases)",
        ).set(math.nan if compiled_bytes is None else compiled_bytes)
        reg.gauge(
            "consensusml_hbm_live_peak_bytes",
            "observed live peak (the in-round maximum of bytes_in_use "
            "where sampled, else the runtime's lifetime peak_bytes_in_use, "
            "either with the runtime's bytes_reserved; else the live-array "
            "high-water mark)",
        ).set(math.nan if live_peak_bytes is None else live_peak_bytes)
        drift: dict[str, float] = {}
        for a, b in (
            ("analytic", "compiled"),
            ("compiled", "live"),
            ("analytic", "live"),
        ):
            if vals[a] is None or vals[b] is None:
                continue
            pct = _drift_pct(float(vals[a]), float(vals[b]))
            drift[f"{a}_vs_{b}"] = pct
            reg.gauge(
                "consensusml_hbm_drift_pct",
                "signed drift between two HBM accountings "
                "(100*(first-second)/second per pair label)",
                labels={"pair": f"{a}_vs_{b}"},
            ).set(pct)
        return {
            "analytic_bytes": analytic_bytes,
            "compiled_bytes": compiled_bytes,
            "live_peak_bytes": live_peak_bytes,
            "drift_pct": drift,
        }


def load_tool(name: str):
    """Import a ``tools/<name>.py`` script by path (tools/ is a script
    dir next to the package, not a package itself — the repo layout
    pins it two levels up from obs/). None when absent (installed
    package without the repo checkout). The ONE loader every obs
    module shares — the /profile endpoint and the reconciliation both
    use it, so a tools/ relocation breaks in exactly one place."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
        "tools",
        f"{name}.py",
    )
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(f"_cml_tool_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_hbm_model():
    return load_tool("hbm_model")


def reconcile_config(
    name: str,
    scale: str = "smoke",
    rounds: int = 2,
    registry: MetricsRegistry | None = None,
    ledger: Any = None,
) -> dict[str, Any]:
    """The full three-way for one config at world=1 (the per-device
    layout the analytic model predicts): analytic ``predict()`` vs the
    compiled train step's ``memory_analysis()`` (through the cost
    ledger, so the row lands in ``consensusml_cost_*`` too) vs the live
    peak after actually running ``rounds`` rounds.

    CPU note (the ``pytest -m profiling`` tier runs this): the runtime
    hides memory_stats, so "live" is the live-array high-water mark — a
    floor missing XLA temps — and the analytic model's activation
    coefficients were fit against TPU scheduling; the drift assertion
    is correspondingly a loose band, not a tight tolerance.
    """
    import jax

    from consensusml_tpu import configs
    from consensusml_tpu.obs.costs import get_cost_ledger
    from consensusml_tpu.train import (
        init_stacked_state,
        make_simulated_train_step,
    )

    hbm_model = _load_hbm_model()
    if hbm_model is None:
        raise RuntimeError(
            "tools/hbm_model.py not found next to the package — the "
            "three-way reconciliation needs the analytic side"
        )
    analytic = hbm_model.predict(name, scale, world=1)

    if ledger is None:
        ledger = get_cost_ledger()
    acct = HbmAccountant(registry=registry)
    bundle = configs.build(name, scale, world=1)
    step = make_simulated_train_step(bundle.cfg, bundle.loss_fn)
    state = init_stacked_state(
        bundle.cfg, bundle.init_params, jax.random.key(0), 1
    )
    batch = next(iter(bundle.batches(1, 0)))
    row = ledger.register(
        f"train.step.{name}", step, state, batch,
        meta={"config": name, "scale": scale, "world": 1},
    )
    acct.tick()
    metrics = None
    for b in bundle.batches(rounds, 0):
        state, metrics = step(state, b)
        acct.tick()
    if metrics is not None:  # execute for real; fence on the loss
        float(metrics["loss"])
    acct.tick()
    doc = acct.reconcile(
        analytic_bytes=float(analytic["predicted_peak_bytes"]),
        compiled_bytes=float(row.peak_bytes),
    )
    doc.update(
        {
            "config": name,
            "scale": scale,
            "executable": row.name,
            "compile_s": row.compile_s,
            "analytic_detail": analytic["per_device"],
        }
    )
    return doc
