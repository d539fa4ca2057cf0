"""From a profiler trace to numbers: busy union, idle share, kernel time.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small plain structure, and every reduction below works on that structure, so
the arithmetic is tested on a recorded trace without the profiler:

    {"planes": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns], ...],
                                   "XLA Modules": [...]}}}

Only device planes are kept. On a TPU the line ``XLA Ops`` holds one event per
executed HLO operation (fusions, custom calls = Pallas kernels, collectives)
and ``XLA Modules`` one per executed program (``jit_<function>(<id>)``). Busy
time is the union of the op intervals, never their sum: operations overlap
(async copies, collectives beside compute).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
NAME_CHARS = 300


def load_xplane(trace_dir: str) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir``, device planes only."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines[line.name] = [
                [_event_name(ev), float(ev.start_ns), float(ev.duration_ns)] for ev in line.events
            ]
        planes[plane.name] = lines
    return {"planes": planes}


def _event_name(ev) -> str:
    """The instruction's own text, cut short. A Pallas kernel shows as an
    instruction whose opcode is ``custom-call`` (`` custom-call(``, with the
    space: ``%custom-call.12`` inside another instruction is an operand). The
    program gives its kernels no names of their own (``kernel_metadata={}``),
    so a kernel is found by the instruction's name, which follows the scope
    it was traced in: ``%h_3.21`` for attention in block 3,
    ``%vmap_jit_chunked_topk__.26`` for the codec's selection."""
    return ev.name[:NAME_CHARS]


def merged(intervals) -> list:
    """Sorted, non-overlapping [start, end] covering the same time."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def union_seconds(intervals) -> float:
    """Length of the union of (start_ns, end_ns) intervals, in seconds."""
    return sum(end - start for start, end in merged(intervals)) / 1e9


def _events(trace: dict, line: str):
    for plane, lines in sorted(trace["planes"].items()):
        yield plane, lines.get(line, [])


def device_planes(trace: dict) -> list:
    """Planes on which at least one operation ran."""
    return [p for p, evs in _events(trace, OPS_LINE) if evs]


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices used."""
    per = [
        union_seconds((s, s + d) for _, s, d in evs)
        for _, evs in _events(trace, OPS_LINE)
        if evs
    ]
    return sum(per) / len(per) if per else 0.0


def matching_seconds(trace: dict, pattern: str, line: str = OPS_LINE) -> tuple:
    """(seconds, calls) of the events whose name matches ``pattern``: the
    union per device (a kernel does not overlap itself, but its pieces
    may), averaged over the devices on which it ran."""
    rx = re.compile(pattern)
    per, calls = [], 0
    for _, evs in _events(trace, line):
        hit = [(s, s + d) for name, s, d in evs if rx.search(name)]
        if hit:
            per.append(union_seconds(hit))
            calls += len(hit)
    return (sum(per) / len(per) if per else 0.0), calls


def exposed_seconds(trace: dict, pattern: str) -> float:
    """Seconds, per device, in which an event matching ``pattern`` ran and
    no other operation did on that device (collective time not hidden
    under compute), averaged over the devices on which it ran."""
    rx = re.compile(pattern)
    per = []
    for _, evs in _events(trace, OPS_LINE):
        mine = merged((s, s + d) for name, s, d in evs if rx.search(name))
        if not mine:
            continue
        rest = merged((s, s + d) for name, s, d in evs if not rx.search(name))
        exposed, j = 0.0, 0
        for start, end in mine:
            cover = 0.0
            while j < len(rest) and rest[j][1] <= start:
                j += 1
            k = j
            while k < len(rest) and rest[k][0] < end:
                cover += min(end, rest[k][1]) - max(start, rest[k][0])
                k += 1
            exposed += (end - start) - cover
        per.append(exposed / 1e9)
    return sum(per) / len(per) if per else 0.0


def _short(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion.12``; keep names comparable."""
    name = name.split(" = ")[0].strip().lstrip("%")
    return name[:80]


def top_ops(trace: dict, n: int = 10) -> list:
    """[name, seconds] of the operations that took most device time, summed
    over calls and devices, with the run-number suffix folded away."""
    totals = {}
    for _, evs in _events(trace, OPS_LINE):
        for name, _, d in evs:
            key = re.sub(r"[.\d]+$", "", _short(name)) or _short(name)
            totals[key] = totals.get(key, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[name, seconds]: idle time on the first device grouped by the program
    that ran next (the host was getting that program ready), largest
    first. Without host spans on the profiler's clock this is as far as a
    gap can be attributed."""
    for _, lines in sorted(trace["planes"].items()):
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        busy = merged((s, s + d) for _, s, d in ops)
        mods = sorted(
            (s, s + d, re.sub(r"\(\d+\)$", "", name))
            for name, s, d in lines.get(MODULES_LINE, [])
        )
        starts = [m[0] for m in mods]
        totals = {}
        for (_, end), (start, _) in zip(busy, busy[1:]):
            # the program whose span holds the first operation after the gap
            j = bisect.bisect_right(starts, start) - 1
            nxt = mods[j][2] if j >= 0 and mods[j][1] >= start else "unknown"
            key = f"before:{nxt}"
            totals[key] = totals.get(key, 0.0) + (start - end) / 1e9
        return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]
    return []
