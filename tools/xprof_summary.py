"""Summarize an xprof trace directory from the command line.

The profiling subsystem (`utils.profiling.trace`, `train.py
--profile-dir`) dumps xplane/trace files that normally need TensorBoard;
this tool prints the device-op time breakdown directly:

    python train.py --config cifar_resnet50 --profile-dir /tmp/prof ...
    python tools/xprof_summary.py /tmp/prof

Groups device ops by fused-op family and reports total/share. Family
grouping strips XLA's duplicate-instruction suffix (``fusion`` /
``fusion.1`` / ``fusion.2`` merge) but ONLY when the bare base name also
appears in the trace — a pallas kernel whose family name itself ends in
``.N`` (two fused-wire codecs differing only by a numeric width suffix)
has no bare sibling and stays its own row instead of silently merging
with its neighbor.

The program's own spans (``obs/tracer.py``: ``train.round``,
``feed.wait``, ``round.fence``, ...) are ``jax.profiler.TraceAnnotation``s
whenever a session is open, so they are in the capture itself, on the
host process's threads beside JAX's ``PjitFunction(<name>)`` dispatch
events and on the device planes' clock: the report lists them from
there, grouped by name (``host_spans``). The Python tracer's per-call
events (``$file:line function``) are left out.

``--json`` emits the whole report as one machine-readable document
(op-family table, totals, host spans) so the cost ledger's ``/profile``
endpoint and scripts can consume captures programmatically instead of
scraping the text table.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys
from collections import Counter


def find_trace_json(root: str) -> str | None:
    hits = sorted(
        glob.glob(os.path.join(root, "**", "*.trace.json.gz"), recursive=True)
    )
    return hits[-1] if hits else None


def op_family(name: str, raw_names: set[str]) -> str:
    """Family an op name groups under.

    XLA uniquifies duplicated instructions as ``base.1``, ``base.2``, …
    ALONGSIDE the bare ``base`` — so a trailing ``.N`` is stripped only
    when that bare base is itself present in the trace. A name whose
    family genuinely ends in a number after a dot (distinct pallas
    kernels differing only by a numeric suffix, e.g. a ``.4``/``.8``
    bit-width pair) has no bare sibling and keeps its full name — the
    old unconditional strip merged such pairs into one bogus row.
    """
    m = re.match(r"^(.*)\.(\d+)$", name)
    if m and m.group(1) in raw_names:
        return m.group(1)
    return name


def summarize(path: str, top: int = 25) -> dict:
    with gzip.open(path) as f:
        data = json.load(f)
    ev = data.get("traceEvents", [])
    names = {
        e["pid"]: e["args"]["name"]
        for e in ev
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    device_pids = {p for p, n in names.items() if "TPU" in n or "GPU" in n}
    is_wrapper = lambda n: (
        n in ("0",) or n.startswith("jit_") or n.startswith("while")
    )
    host_pids = {p for p, n in names.items() if n.startswith("/host:")}
    raw: Counter = Counter()
    host_us: Counter = Counter()
    host_count: Counter = Counter()
    event_count = 0
    for e in ev:
        if e.get("ph") == "X":
            event_count += 1
        if (
            e.get("ph") == "X"
            and e.get("pid") in host_pids
            and not e["name"].startswith("$")
        ):
            host_us[e["name"]] += e.get("dur", 0)
            host_count[e["name"]] += 1
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        if is_wrapper(e["name"]):
            continue
        raw[e["name"]] += e.get("dur", 0)
    raw_names = set(raw)
    cat: Counter = Counter()
    for name, d in raw.items():
        cat[op_family(name, raw_names)] += d
    total = sum(cat.values())
    return {
        "trace": path,
        "device_total_ms": round(total / 1000, 2),
        "event_count": event_count,
        "processes": {str(p): n for p, n in sorted(names.items())},
        "ops": [
            {
                "op": name,
                "ms": round(d / 1000, 2),
                "share": round(d / total, 4) if total else 0.0,
            }
            for name, d in cat.most_common(top)
        ],
        "host_spans": [
            {
                "span": name,
                "count": host_count[name],
                "total_ms": round(us / 1000, 3),
                "mean_ms": round(us / 1000 / host_count[name], 3),
            }
            for name, us in host_us.most_common(top)
        ],
    }


def main() -> int:
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    p.add_argument("trace_dir", help="xprof trace directory (or a "
                   "*.trace.json.gz file) from train.py --profile-dir")
    p.add_argument("--json", action="store_true",
                   help="emit ONE machine-readable JSON document (op "
                        "table + totals + host spans) instead of the "
                        "text report — what the cost ledger and the "
                        "/profile endpoint consume")
    args = p.parse_args()

    root = args.trace_dir
    if not os.path.exists(root):
        print(
            f"error: trace path {root!r} does not exist — run "
            "`python train.py ... --profile-dir DIR` first (it dumps the "
            "xprof trace this tool summarizes)",
            file=sys.stderr,
        )
        return 1
    path = root if root.endswith(".gz") else find_trace_json(root)
    if path is None:
        print(
            f"error: no *.trace.json.gz under {root!r} — the directory "
            "exists but holds no completed xprof dump (a run killed "
            "mid-trace leaves none; re-run with --profile-dir)",
            file=sys.stderr,
        )
        return 1
    out = summarize(path)
    if args.json:
        print(json.dumps(out, indent=2))
        return 0

    print(f"trace: {out['trace']}")
    print(f"device op total: {out['device_total_ms']} ms")
    for o in out["ops"]:
        print(f"{o['ms']:10.2f} ms  {100 * o['share']:5.1f}%  {o['op']}")
    if out["host_spans"]:
        print("\nhost spans (program spans and JAX's dispatch events):")
        for s in out["host_spans"]:
            print(
                f"{s['total_ms']:10.2f} ms  x{s['count']:<5d} "
                f"mean {s['mean_ms']:8.3f} ms  {s['span']}"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
