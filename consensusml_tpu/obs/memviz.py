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
- **live** — ``jax.live_arrays()`` totals plus the runtime's
  ``device.memory_stats()`` peak where the backend exposes one (libtpu
  does; the CPU backend does not: there the live-array total is a FLOOR
  — it cannot see XLA temps — and the compiled number is the peak
  authority). Authoritative for the PROCESS: leaks, fragmentation,
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
from typing import Any

from consensusml_tpu.obs.metrics import MetricsRegistry, get_registry

__all__ = [
    "live_array_bytes",
    "device_memory_stats",
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
        self._g_arrays = reg.gauge(
            "consensusml_hbm_live_arrays", "live jax array count"
        )
        self._g_peak = reg.gauge(
            "consensusml_hbm_peak_bytes",
            "runtime peak_bytes_in_use (NaN when the backend hides "
            "memory_stats)",
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
        self._g_arrays.set(live["arrays"])
        stats = device_memory_stats(self.device)
        peak = (stats or {}).get("peak_bytes_in_use", math.nan)
        limit = (stats or {}).get("bytes_limit", math.nan)
        self._g_peak.set(peak)
        self._g_limit.set(limit)
        return {
            "time_s": time.time(),
            "live_bytes": live["bytes"],
            "live_arrays": live["arrays"],
            "runtime_peak_bytes": peak,
            "runtime_limit_bytes": limit,
        }

    @property
    def live_peak_bytes(self) -> float:
        """Best live peak this accountant knows: the runtime's
        ``peak_bytes_in_use`` when exposed, else the high-water mark of
        the live-array samples taken so far."""
        stats = device_memory_stats(self.device)
        if stats and stats.get("peak_bytes_in_use"):
            return float(stats["peak_bytes_in_use"])
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
            "observed live peak (runtime peak_bytes_in_use, or the "
            "live-array high-water mark where the runtime hides stats)",
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
