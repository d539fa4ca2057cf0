#!/usr/bin/env python3
"""The builder's tools: what defines a cell, measured once on the chip.

    python3 benchmarks/probe.py seeds --workload W --seeds 1,2,3 --seconds 2 --precisions fp8,int8 --faults half_batch
    python3 benchmarks/probe.py trace --workload W --seconds 5 --seed 1

``seeds``: a full set-up, short window and check per seed, in one process (one
compile). Prints every compared number of the program and of each control (the
reference in a lower precision, or with a fault planted, put in the program's
place), and what a run's comparison makes of each side: ``correct`` has to be
true for the program and false for every control and fault.
``trace``: a short traced window; writes the names the profiler gives planes,
lines and the longest events, and a trimmed recorded trace for the tests.
Results go to stdout and to ``chiprun_out/probe_<command>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

import run as harness


def _out(name: str, obj) -> None:
    print(json.dumps(obj), flush=True)
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"probe_{name}.json"), "a") as f:
        f.write(json.dumps(obj) + "\n")


def _driver(cell, seed, device):
    mod = harness._load_module("drivers", cell["config"]["driver"], cell["bench_dirs"])
    return mod.Driver(dict(cell, seed=int(seed), device=device, peaks=cell["peaks_table"].get(device["kind"])))


def seeds(args, cell, device) -> None:
    precisions = tuple(p for p in args.precisions.split(",") if p)
    faults = tuple(f for f in args.faults.split(",") if f)
    step = None  # a training driver's compiled round does not depend on the seed: built once
    for seed in (int(s) for s in args.seeds.split(",")):
        driver = _driver(cell, seed, device)
        if step is not None:
            driver.build_step = lambda cfg, loss_fn: step
        t0 = time.monotonic()
        try:
            driver.setup(args.seconds)
            step = getattr(driver, "step", None)
            res = driver.window(args.seconds, harness.PROCESS_T0)
            peak, _ = harness.memory_peak(cell["chips"])
            driver.release()
            t1 = time.monotonic()
            readings = driver.readings(precisions, faults)
            # every side through the comparison a run makes: the program has to
            # come out correct, a control or a fault in its place not correct
            judged = {side: driver.judge(read) for side, read in readings.items()}
        finally:
            driver.close()
        _out("seeds", {
            "seed": seed, "seconds": args.seconds, "readings": readings,
            "correct": {side: all(c["ok"] for c in checks) for side, checks in judged.items()},
            "failed_checks": {side: [c["name"] for c in checks if not c["ok"]] for side, checks in judged.items()},
            "failed": res["failed"], "attempted": res["attempted"],
            "end_to_end": res["end_to_end"], "memory_peak_bytes": peak,
            "run_s": t1 - t0, "check_s": time.monotonic() - t1,
        })


def trace(args, cell, device) -> None:
    import jax

    import xtrace

    driver = _driver(cell, args.seed, device)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        driver.setup(args.seconds)
        jax.profiler.start_trace(trace_dir)
        t0 = time.monotonic()
        driver.window(args.seconds, harness.PROCESS_T0)
        window_s = time.monotonic() - t0
        jax.profiler.stop_trace()
    finally:
        driver.close()
    from jax.profiler import ProfileData
    import glob

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    data = ProfileData.from_file(path)
    names = {
        plane.name: {line.name: sum(1 for _ in line.events) for line in plane.lines}
        for plane in data.planes
    }
    reduced = xtrace.load_xplane(trace_dir)
    trimmed = {"planes": {
        p: {ln: evs[:400] for ln, evs in lines.items()} for p, lines in reduced["planes"].items()
    }}
    examples, seconds = {}, {}
    for lines in reduced["planes"].values():
        for name, _, d in lines.get(xtrace.OPS_LINE, []):
            key = re.sub(r"[.\d]+$", "", xtrace._short(name))
            seconds[key] = seconds.get(key, 0.0) + d / 1e9
            examples.setdefault(key, name[:400])
    named = sorted(seconds, key=lambda k: -seconds[k])[:40]
    modules = {}
    for lines in reduced["planes"].values():
        for name, _, d in lines.get(xtrace.MODULES_LINE, []):
            modules[name] = modules.get(name, 0.0) + d / 1e9
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"recorded_trace_{cell['name']}.json"), "w") as f:
        json.dump(trimmed, f)
    _out("trace", {
        "xplane_bytes": os.path.getsize(path), "window_s": window_s,
        "busy_s": xtrace.busy_seconds(reduced), "planes": names,
        "top_ops": xtrace.top_ops(reduced, 25), "modules": sorted(modules.items(), key=lambda kv: -kv[1])[:25],
        "idle_gaps": xtrace.idle_gaps(reduced),
        "examples": [[k, seconds[k], examples[k]] for k in named],
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("command", choices=("seeds", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--precisions", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(os.path.join(harness.ROOT, "BENCHMARK.json"), args.workload)
    for d in [harness.ROOT, *reversed(cell["bench_dirs"])]:
        if d not in sys.path:
            sys.path.insert(0, d)
    device = harness.find_device(cell)
    harness.enable_cache(cell["root"])
    {"seeds": seeds, "trace": trace}[args.command](args, cell, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
