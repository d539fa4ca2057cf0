#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Holds no cell, configuration or metric in its code. Everything is found by the
names in ``BENCHMARK.json`` (in the directory above this one):

    configs/<config>.json          sizes, source, guarantees, ``driver``
    traffic/<workload>.json        the traffic mix's parameters
    layer_metrics/<metric>.json    ``reader`` and its arguments
    drivers/<driver>.py            builds the system, runs the window, checks it
    readers/<reader>.py            takes one per-layer metric from what a run left

A driver module has ``class Driver`` with ``__init__(cell)``, ``setup()``,
``window(seconds) -> dict``, ``release()``, ``check() -> list`` and
``close()``. Files are looked for beside ``BENCHMARK.json`` first and beside
this file second, so a later PR (or a test, from a temporary directory) adds a
cell, a configuration, a driver or a metric by adding files and entries.

Exit codes: 0 a result line was printed; 2 no TPU, too few chips, or a device
that ``peaks.json`` does not know (no result line); 3 the program is not in
this checkout (no result line); anything else is a crash.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.monotonic()  # set-up is counted from here

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def _find(bench_dirs: list, *rel: str) -> str:
    for base in bench_dirs:
        path = os.path.join(base, *rel)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"{os.path.join(*rel)} not found under {bench_dirs}")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(kind: str, name: str, bench_dirs: list):
    path = _find(bench_dirs, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(bench_file: str, workload: str) -> dict:
    """Everything ``BENCHMARK.json`` and the files it names say of one cell."""
    bench = _load_json(bench_file)
    root = os.path.dirname(os.path.abspath(bench_file))
    bench_dirs = [os.path.join(root, p) for p in bench["paths"]]
    if HERE not in bench_dirs:
        bench_dirs.append(HERE)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    entry = cells[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = _load_json(os.path.join(root, config_entry["file"]))
    traffic = _load_json(_find(bench_dirs, "traffic", f"{workload}.json"))

    def in_cell(metric: dict) -> bool:
        return workload in metric["workloads"] if "workloads" in metric else True

    end_to_end = [m for m in bench["end_to_end"] if in_cell(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return {
        "name": workload,
        "chips": int(entry["chips"]),
        "config_name": entry["config"],
        "config": config,
        "traffic": traffic,
        "end_to_end": end_to_end,
        "per_layer": per_layer,
        "bench_dirs": bench_dirs,
        "root": root,
        "peaks_table": _load_json(_find(bench_dirs, "peaks.json")),
    }


def find_device(cell: dict) -> dict:
    """The device as JAX reports it, or exit 2: a measurement path that finds
    no chip fails, it does not fall back."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if jax.default_backend() != "tpu":
        log(f"default backend is {jax.default_backend()!r}, not 'tpu': no result")
        raise SystemExit(2)
    if device["kind"] not in cell["peaks_table"]:
        log(f"device kind {device['kind']!r} is not in peaks.json: no result")
        raise SystemExit(2)
    if len(devices) < cell["chips"]:
        log(f"cell needs {cell['chips']} chips, JAX found {len(devices)}: no result")
        raise SystemExit(2)
    return device


def enable_cache(root: str) -> str:
    """The persistent compile cache at the checkout's fixed ``.jax_cache``
    (the program's own helper picks the same directory), every program kept."""
    import jax

    try:
        from consensusml_tpu.compile_cache import enable_compile_cache
    except ImportError:
        log("the program (consensusml_tpu) is not in this checkout: no result")
        raise SystemExit(3)
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CacheCounter:
    """Persistent-cache hits and misses, from JAX's own events."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_peak(chips: int) -> tuple:
    """(peak bytes on the fullest chip used, its limit), None off a chip."""
    import jax

    peak = limit = None
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use") is not None and (
            peak is None or stats["peak_bytes_in_use"] > peak
        ):
            peak, limit = stats["peak_bytes_in_use"], stats.get("bytes_limit")
    return peak, limit


def read_layer_metrics(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of this cell through its own reader; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for metric in cell["per_layer"]:
        spec = _load_json(_find(cell["bench_dirs"], "layer_metrics", f"{metric['name']}.json"))
        reader = _load_module("readers", spec["reader"], cell["bench_dirs"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is None:
            log(f"per-layer {metric['name']}: nothing to read")
            continue
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, device: dict) -> dict:
    import jax

    cache = CacheCounter()
    driver_mod = _load_module("drivers", cell["config"]["driver"], cell["bench_dirs"])
    cell = dict(
        cell, seed=int(seed), device=device, process_t0=PROCESS_T0,
        peaks=cell["peaks_table"].get(device["kind"]),
    )
    driver = driver_mod.Driver(cell)
    trace_dir = None
    try:
        driver.setup(seconds)
        setup_misses = cache.misses
        log(f"set-up done: cache hits {cache.hits}, misses {cache.misses}")
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(trace_dir)
        t_trace0 = time.monotonic()
        result = driver.window(seconds, PROCESS_T0)
        traced_s = time.monotonic() - t_trace0
        if trace:
            jax.profiler.stop_trace()
        window_misses = cache.misses - setup_misses
        peak, limit = memory_peak(cell["chips"])
        driver.release()
        checks = list(driver.check())
    finally:
        driver.close()
    checks.append({"name": "compiles_in_window", "value": window_misses, "limit": 0, "ok": window_misses == 0})
    device_out = dict(device, memory_peak_bytes=peak)
    line = {
        "correct": all(c["ok"] for c in checks),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
    }
    if not trace:
        wanted = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        line["metrics"] = {
            name: {"value": float(result["end_to_end"][name]), "unit": unit}
            for name, unit in wanted.items()
        }
    else:
        import xtrace as trace_mod  # benchmarks/xtrace.py

        try:
            reduced = trace_mod.load_xplane(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        busy = trace_mod.busy_seconds(reduced)
        device_out.update(busy_s=busy, window_s=traced_s)
        ctx = {
            "cell": cell, "trace": reduced, "trace_mod": trace_mod,
            "stats": result["stats"], "window_s": traced_s, "busy_s": busy,
            "peaks": cell["peaks"], "chips": cell["chips"],
            "memory_peak_bytes": peak, "memory_limit_bytes": limit,
        }
        line["metrics"] = read_layer_metrics(cell, ctx)
        line["breakdown"] = {
            "device_ops": trace_mod.top_ops(reduced),
            "idle_gaps": trace_mod.idle_gaps(reduced),
        }
    line["device"] = device_out
    line["checks"] = [
        {"name": c["name"], "value": c["value"], "limit": c["limit"]} for c in checks
    ]
    return line


def main(argv=None, *, bench_file: str | None = None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(bench_file or os.path.join(ROOT, "BENCHMARK.json"), args.workload)
    for d in [ROOT, cell["root"], *reversed(cell["bench_dirs"])]:
        if d not in sys.path:  # the program (from its checkout) and the benchmark's modules
            sys.path.insert(0, d)
    if require_chip:
        device = find_device(cell)
    else:  # the tests' rehearsal: everything but the look for a chip
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    enable_cache(cell["root"])
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    for c in line["checks"]:
        log(f"check {c['name']}: {c['value']} (limit {c['limit']})")
    log(f"correct: {line['correct']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
