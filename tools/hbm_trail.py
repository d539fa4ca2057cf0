#!/usr/bin/env python3
"""Which program set a cell's lifetime HBM peak, and what the chip holds while
a round runs: the tables of PERF.md section 5 (PR 35).

    chiprun -- python3 tools/hbm_trail.py --workload xing4_ep8.solo_4k --seed 1 --seconds 30

from the root of a checkout. It runs ``benchmarks/run.py --trace 1`` in this
process (the cell's result line is printed as always) and then reads what the
program itself recorded: the compile log's ``hbm_trail`` (each program of
set-up with the lifetime peak before and after its first run; the last row's
"after" is the window's first ``feed.stage`` reading), the ``hbm_in_use`` /
``hbm_peak`` arguments of the window's ``feed.stage`` spans with how far into
its round each was taken (the pop that woke the producer to the next pop),
the ``host.gc`` spans and the collector's seconds by generation, and the
traced window's own tokens/s (which a traced result line leaves out). One JSON
object, to ``chiprun_out/hbm_trail_<workload>.json`` (``HBM_TRAIL_OUT`` names
another file) and, without the per-span list, to stderr.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.getcwd()


def in_round_offsets(stages: list, pops: list) -> list:
    """For each ``feed.stage`` span (end, ns) the share of its round that had
    passed: the round runs from the consumer's pop before it to the next pop."""
    out = []
    for end in stages:
        before = [p for p in pops if p <= end]
        after = [p for p in pops if p > end]
        if before and after:
            out.append((end - before[-1]) / (after[0] - before[-1]))
    return out


def report(events: list, trail_of, gc_seconds: dict) -> dict:
    stages = sorted((e for e in events if e["name"] == "feed.stage"), key=lambda e: e["start_ns"])
    read = [e for e in stages if "hbm_in_use" in e.get("args", {})]
    pops = sorted(e["start_ns"] + e["dur_ns"] for e in events if e["name"] == "feed.wait")
    offsets = in_round_offsets([e["start_ns"] + e["dur_ns"] for e in read], pops)
    gcs = [e for e in events if e["name"] == "host.gc"]
    first_peak = read[0]["args"]["hbm_peak"] if read else None
    out = {
        "feed_stage_spans": len(stages),
        "with_a_reading": len(read),
        "setup_peak_bytes": first_peak,
        "round_in_use_max_bytes": max((e["args"]["hbm_in_use"] for e in read), default=None),
        "round_in_use_min_bytes": min((e["args"]["hbm_in_use"] for e in read), default=None),
        "window_peak_last_bytes": read[-1]["args"]["hbm_peak"] if read else None,
        "round_reserved_bytes": sorted({e["args"].get("hbm_reserved") for e in read}, key=str),
        "share_of_round_passed_at_the_reading": {
            "median": statistics.median(offsets), "min": min(offsets), "max": max(offsets),
        } if offsets else None,
        "host_gc_spans": [
            {"ms": e["dur_ns"] / 1e6, **{k: e["args"].get(k) for k in ("gen", "collected")}} for e in gcs
        ],
        "gc_pause_seconds_total": gc_seconds,
        "setup_programs": trail_of(first_peak, min((e["start_ns"] for e in events), default=None)),
        "readings": [
            [e["args"]["hbm_in_use"], e["args"]["hbm_peak"], e["args"].get("hbm_reserved")] for e in read
        ],
    }
    return out


def main(argv=None, **harness_kw) -> int:
    """``harness_kw``: the rehearsal's ``bench_file`` and ``require_chip``."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    sys.path.insert(0, ROOT)
    import run as harness  # benchmarks/run.py

    args = list(sys.argv[1:] if argv is None else argv)
    workload = args[args.index("--workload") + 1]
    # a traced line leaves the end-to-end metrics out: keep what the driver's window
    # returned, to hold the traced window's tokens/s against an untraced run's
    window_read, load = {}, harness._load_module

    def load_and_keep(kind, name, bench_dirs):
        module = load(kind, name, bench_dirs)
        if kind == "drivers":
            window = module.Driver.window

            def kept(self, *a, **k):
                result = window(self, *a, **k)
                window_read.update(result["end_to_end"], rounds=result["attempted"])
                return result

            module.Driver.window = kept
        return module

    harness._load_module = load_and_keep
    try:
        rc = harness.main(args + ["--trace", "1"], **harness_kw)
    finally:
        harness._load_module = load
    from consensusml_tpu.obs import get_registry, get_tracer
    from consensusml_tpu.obs.compile_log import get_compile_log

    reg = get_registry()
    gc_seconds = {
        str(gen): reg.counter("consensusml_gc_pause_seconds_total", labels={"gen": gen}).value
        for gen in range(3)
    }
    out = report(get_tracer().events(), get_compile_log().hbm_trail, gc_seconds)
    out["workload"] = workload
    out["traced_window"] = window_read
    path = os.environ.get("HBM_TRAIL_OUT") or os.path.join(ROOT, "chiprun_out", f"hbm_trail_{workload}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f)
    raised = [p for p in out["setup_programs"] if p["raised_bytes"]]
    brief = dict(out, readings=len(out["readings"]), setup_programs=len(out["setup_programs"]), raised_the_peak=raised)
    print("hbm_trail:", json.dumps(brief), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
