#!/usr/bin/env python3
"""A cell's device time by the SCOPE its operations were traced in: the table
of PERF.md section 5, which the benchmark's ``device_ops`` (grouped by the
instruction's name) cannot give (PERF.md section 7).

    chiprun -- python3 tools/scope_split.py --workload nemotron3_nano_ep16.solo_8k --seconds 10 --seed 1

from the root of a checkout (the parent's copy runs the same file: ``cd
<parent> && python3 <this file> ...``). It sets the cell up as
``benchmarks/run.py`` does, traces one short window, and reads the capture's
``.xplane.pb`` with ``tensorflow.tsl.profiler.protobuf.xplane_pb2``: the scope
is the ``tf_op`` stat of an event's METADATA, which ``jax.profiler.ProfileData``
does not surface. Leaf operations only (a ``while`` holds its body); an
operation counts under the first of ``SCOPES`` that its scope names, else
under its block (``block_other``), ``train.grad_other``, ``other`` or
``no_scope``; ``bwd`` is what runs under a transpose, the recomputed forward
pass included. Prints one JSON object and writes it to
``chiprun_out/scope_<tag>.json`` (``SCOPE_OUT`` names another file).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.getcwd()
SCOPES = [
    "moe_rows_gather", "moe_rows_combine", "moe_gmm_dlhs", "moe_gmm_drhs", "moe_gmm",
    "moe.route", "moe.sort", "moe.experts", "moe.shared", "moe.combine",
    "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj",
    "gdn.in_proj", "gdn.conv", "gdn.scan", "gdn.gate_norm", "gdn.out_proj",
    "attn.qk_norm_rope", "attn.gate", "attn.flash",
    "mhc_read_fwd", "mhc_read_bwd", "mhc_write_fwd", "mhc_write_bwd",  # the residual path's kernels, before the spans they run in
    "mhc.maps", "mhc.sinkhorn", "mhc.pre", "mhc.post", "mla.q_lora", "mla.kv_lora", "mla.rope", "mla.out_proj",
    "mlp.dense", "mtp.embed_proj", "mtp.loss", "mtp.block",  # the module's block last: what it nests counts under its own scope
    "train.optimizer", "train.consensus_error", "gossip.round",
]


def split(path: str, out: dict) -> dict:
    """``split_space`` of the capture at ``path``."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return split_space(space, out)


def split_space(space, out: dict, planes: str = r"^/device:TPU:\d+$", ops_line: str = "XLA Ops") -> dict:
    """Adds ``ms_a_round_by_scope`` (and ``_dir``: forward or backward, and
    ``top_ops``: scope|instruction) of the ``XSpace`` to ``out``."""
    for plane in space.planes:
        if not re.match(planes, plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        named = {}
        for mid, md in plane.event_metadata.items():
            scope = ""
            for st in md.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    scope = st.str_value or stat_names.get(st.ref_value, "")
            named[mid] = (md.name, scope)
        for line in plane.lines:
            if line.name == "XLA Modules":
                rounds = sorted(
                    ev.duration_ps / 1e9 for ev in line.events if "train_step" in named[ev.metadata_id][0])
                out["rounds"] = len(rounds)
                out["round_ms_device"] = rounds[len(rounds) // 2] if rounds else None
            if line.name != ops_line:
                continue
            events = sorted((ev.offset_ps, ev.offset_ps + ev.duration_ps, ev.metadata_id) for ev in line.events)
            by_scope, by_dir, by_op = {}, {}, {}
            for i, (start, end, mid) in enumerate(events):
                if i + 1 < len(events) and events[i + 1][0] < end:
                    continue  # a container (while, call): its body follows
                name, scope = named[mid]
                key = next((k for k in SCOPES if k in scope), None)
                if key is None and re.search(r"/h_\d+/", scope):
                    key = "block_other"
                elif key is None:
                    key = "train.grad_other" if "train.grad" in scope else "other" if scope else "no_scope"
                ms = (end - start) / 1e9
                short = re.sub(r"[.\d]+$", "", name.split(" = ")[0].lstrip("%"))
                for table, k in ((by_scope, key), (by_dir, f"{key}|{'bwd' if 'transpose(' in scope else 'fwd'}"),
                                 (by_op, f"{key}|{short}")):
                    table[k] = table.get(k, 0.0) + ms
            n = max(out.get("rounds") or 1, 1)
            ranked = lambda t, top=None: {
                k: round(v / n, 3) for k, v in sorted(t.items(), key=lambda kv: -kv[1])[:top]}
            out.update(ms_a_round_by_scope=ranked(by_scope), ms_a_round_by_scope_dir=ranked(by_dir),
                       ms_a_round_top_ops=ranked(by_op, 60))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tag", default="change")
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]
    import run as harness  # benchmarks/run.py: the cell's files, the device, the compile cache

    cell = harness.load_cell(os.path.join(ROOT, "BENCHMARK.json"), args.workload)
    for d in reversed(cell["bench_dirs"]):
        if d not in sys.path:
            sys.path.insert(0, d)
    device = harness.find_device(cell)
    harness.enable_cache(cell["root"])
    import jax

    mod = harness._load_module("drivers", cell["config"]["driver"], cell["bench_dirs"])
    driver = mod.Driver(dict(cell, seed=args.seed, device=device, peaks=cell["peaks_table"].get(device["kind"])))
    trace_dir = tempfile.mkdtemp(prefix="scope_trace_")
    driver.setup(args.seconds)
    jax.profiler.start_trace(trace_dir)
    t0 = time.monotonic()
    result = driver.window(args.seconds, harness.PROCESS_T0)
    window_s = time.monotonic() - t0
    jax.profiler.stop_trace()
    driver.close()
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    out = split(path, {"tag": args.tag, "window_s": window_s, "end_to_end": result.get("end_to_end")})
    dest = os.environ.get("SCOPE_OUT", os.path.join(ROOT, "chiprun_out", f"scope_{args.tag}.json"))
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
