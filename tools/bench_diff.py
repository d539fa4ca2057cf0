#!/usr/bin/env python
"""Bench regression sentinel: diff a fresh bench JSON vs the trajectory.

A directory of ``BENCH_r0*.json`` files is a perf trajectory (one
compact record per bench round: ``parsed.metric/value/vs_baseline``) and
``BENCH_DETAIL.json`` beside them the latest round's full section
detail. None is checked in: the rounds-1-5 records were deleted in
PR 21 (older code, another installation) and ROADMAP A0/C1 replace this
gate with the benchmark's own bounds — point ``--repo-root`` at
wherever records are kept. This tool turns such an archive into a GATE: compare a fresh bench result
against the trajectory under a per-metric **direction + tolerance
spec** and exit non-zero on regression, so a PR that slows the headline
or blows an overhead budget fails loudly instead of shipping a slower
number into the archive.

Spec semantics (``--spec FILE`` overrides the built-in ``DEFAULT_SPEC``;
one entry per metric):

- ``direction: "up"``   — higher is better; regression when
  ``fresh < ref * (1 - tol_pct/100)`` (e.g. ``value`` = imgs/s/chip);
- ``direction: "down"`` — lower is better; regression when
  ``fresh > ref * (1 + tol_pct/100)`` (e.g. a ttft_p99_ms);
- ``direction: "max"``  — absolute budget, no reference needed;
  regression when ``fresh > bound`` (e.g. the observability plane's
  overhead_pct must stay under 1%);
- ``direction: "min"``  — absolute floor, no reference needed;
  regression when ``fresh < bound`` (e.g. the speculative serving
  block's tokens/s gain and acceptance rate, and boolean gates like
  ``zero_recompiles_after_warmup`` where ``true`` must stay ``true``).

``key`` is a dotted path: top-level keys (``value``, ``vs_baseline``)
resolve in the compact record, dotted keys (``observability.
link_probe_overhead_pct``) in the section detail. Metrics missing on
either side are reported as ``skipped`` — a spec can stay ahead of the
sections the bench grows — and ``--strict`` turns skips into failures.

    python tools/bench_diff.py BENCH_fresh.json            # text report
    python tools/bench_diff.py BENCH_fresh.json --json -   # machine-readable
    python tools/bench_diff.py DIR/BENCH_r05.json --repo-root DIR
                                                           # self-check: the
                                                           # archive is clean
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Direction + tolerance per metric. Tolerances are deliberately loose on
# wall-clock-noisy section metrics (shared CI hosts) and tight on the
# budget bounds the docs promise.
DEFAULT_SPEC = [
    {"key": "value", "direction": "up", "tol_pct": 15.0,
     "label": "headline imgs/s/chip"},
    {"key": "vs_baseline", "direction": "up", "tol_pct": 15.0},
    {"key": "serving.ttft_p99_ms", "direction": "down", "tol_pct": 50.0},
    {"key": "serving.decode_tokens_per_sec", "direction": "up",
     "tol_pct": 50.0},
    {"key": "gossip_round.gossip_round_ms", "direction": "down",
     "tol_pct": 50.0},
    {"key": "gpt2.tokens_sec", "direction": "up", "tol_pct": 30.0},
    {"key": "fed_input.native_loader_u8.imgs_sec", "direction": "up",
     "tol_pct": 30.0},
    # budgets documented in docs/observability.md — absolute, always on
    {"key": "observability.link_probe_overhead_pct", "direction": "max",
     "bound": 1.0},
    {"key": "observability.request_tracing_overhead_pct",
     "direction": "max", "bound": 1.0},
    # alerting & history plane (ISSUE 15, docs/observability.md
    # "Alerting & history"): the amortized history-record + default-
    # ruleset evaluation tick stays under 1% of a gossip round, and the
    # default ruleset fires ZERO alerts on a healthy bench run — a
    # posture that pages on a healthy fleet is a broken posture
    {"key": "observability.alerting_overhead_pct", "direction": "max",
     "bound": 1.0},
    {"key": "observability.alerts_fired_on_healthy_run",
     "direction": "max", "bound": 0.0},
    # wide-event accounting plane (ISSUE 17, docs/observability.md
    # "Wide events & tenant accounting"): the per-terminal emit +
    # amortized /tenants rollup stays under 1% of a decode step, and
    # the per-tenant rollup must re-derive the engine's own
    # request/token totals EXACTLY — a cost join that doesn't balance
    # is worse than no join
    {"key": "observability.wide_event_overhead_pct", "direction": "max",
     "bound": 1.0},
    {"key": "observability.tenant_rollup_mismatch", "direction": "max",
     "bound": 0.0},
    # cost-attribution plane (docs/observability.md "Cost attribution"):
    # the run-time side must stay under 1% of a round, the ledger's
    # per-executable compile budgets are ABSOLUTE walls (CPU-tier tiny
    # models; a blowup here means a program family regressed its
    # lowering, not that the box was busy), and every bench workload
    # must carry an expected-vs-measured pairing — zero missing
    {"key": "attribution.attribution_overhead_pct", "direction": "max",
     "bound": 1.0},
    {"key": "attribution.expected_vs_measured_missing", "direction": "max",
     "bound": 0.0},
    {"key": "attribution.compile_ms.train_step", "direction": "max",
     "bound": 60000.0},
    {"key": "attribution.compile_ms.gossip_round", "direction": "max",
     "bound": 60000.0},
    {"key": "attribution.compile_ms.serve_decode", "direction": "max",
     "bound": 60000.0},
    {"key": "attribution.compile_ms.serve_prefill_max", "direction": "max",
     "bound": 60000.0},
    # speculative serving block (ISSUE 13, docs/serving.md "Speculative
    # decode"): the greedy CPU proxy's decode-tokens/s gain must hold
    # (trajectory-relative once archived, absolute floor always), the
    # proxy's acceptance rate is ~1.0 by construction (a drop means the
    # draft/verify key schedule or acceptance math regressed, not the
    # box), both engines must stay zero-recompile after warmup, and the
    # two new spec executables get the same absolute compile walls as
    # the other serving programs
    {"key": "serving.spec.spec_tokens_per_sec_gain", "direction": "min",
     "bound": 1.5},
    {"key": "serving.spec.spec_tokens_per_sec_gain", "direction": "up",
     "tol_pct": 30.0},
    {"key": "serving.spec.spec.acceptance_rate", "direction": "min",
     "bound": 0.95},
    {"key": "serving.spec.spec.zero_recompiles_after_warmup",
     "direction": "min", "bound": 1.0},
    {"key": "serving.spec.baseline.zero_recompiles_after_warmup",
     "direction": "min", "bound": 1.0},
    {"key": "attribution.compile_ms.spec_propose", "direction": "max",
     "bound": 60000.0},
    {"key": "attribution.compile_ms.spec_verify", "direction": "max",
     "bound": 60000.0},
    # concurrency-correctness plane (ISSUE 14, docs/static_analysis.md):
    # the cml-check AST passes hold ABSOLUTE wall budgets (<2 s each on
    # CPU — a pass suddenly 10x slower is a regression even when its
    # findings stay clean), the lockdep sanitizer fuzz smoke stays
    # under its 30 s CPU budget, and the passes report ZERO active
    # (un-baselined) findings
    {"key": "analysis.pass_seconds.host_sync", "direction": "max",
     "bound": 2.0},
    {"key": "analysis.pass_seconds.locks", "direction": "max",
     "bound": 2.0},
    {"key": "analysis.pass_seconds.threads", "direction": "max",
     "bound": 2.0},
    {"key": "analysis.pass_seconds.lockorder", "direction": "max",
     "bound": 2.0},
    {"key": "analysis.pass_seconds.docs_drift", "direction": "max",
     "bound": 2.0},
    # ISSUE 19: the lifecycle escape lint is one more AST pass (<2 s);
    # the protocol model checker exhausts whole state spaces, so its
    # budget is 30 s — today it runs in well under 2 s (≈12k states
    # across the six models), the headroom is for added actors/actions
    {"key": "analysis.pass_seconds.lifecycle", "direction": "max",
     "bound": 2.0},
    {"key": "analysis.pass_seconds.model", "direction": "max",
     "bound": 30.0},
    {"key": "analysis.active_findings", "direction": "max", "bound": 0.0},
    {"key": "analysis.lockdep_smoke_seconds", "direction": "max",
     "bound": 30.0},
    # fused paged-attention kernel tier (ISSUE 16, docs/perf.md
    # "Roofline workflow"): the fused decode must stay bit-exact vs the
    # two-step gather path and must touch NO MORE HBM bytes than it
    # (the whole point of fusing is the gathered view never landing in
    # HBM — the ledger's compiled bytes_accessed is the witness), and
    # the floor-ratio gates are the self-driving part: each serving hot-
    # path stage's measured-over-roofline ratio ratchets DOWN with the
    # archive trajectory and holds an absolute order-of-magnitude
    # ceiling (CPU-tier programs are dispatch-bound at ~5-8x floor; a
    # three-digit ratio means a stage's lowering or measurement broke,
    # whatever the archive says)
    {"key": "serving.fused_attention.bit_exact", "direction": "min",
     "bound": 1.0},
    {"key": "serving.fused_attention.hbm_bytes_ratio", "direction": "max",
     "bound": 1.0},
    {"key": "attribution.floor_ratio.serve_decode", "direction": "down",
     "tol_pct": 60.0},
    {"key": "attribution.floor_ratio.serve_decode", "direction": "max",
     "bound": 100.0},
    {"key": "attribution.floor_ratio.serve_decode_fused",
     "direction": "down", "tol_pct": 60.0},
    {"key": "attribution.floor_ratio.serve_decode_fused",
     "direction": "max", "bound": 100.0},
    {"key": "attribution.floor_ratio.serve_prefill", "direction": "down",
     "tol_pct": 60.0},
    {"key": "attribution.floor_ratio.serve_prefill", "direction": "max",
     "bound": 100.0},
    {"key": "attribution.floor_ratio.spec_verify", "direction": "down",
     "tol_pct": 60.0},
    {"key": "attribution.floor_ratio.spec_verify", "direction": "max",
     "bound": 100.0},
    {"key": "attribution.floor_ratio.spec_verify_fused",
     "direction": "down", "tol_pct": 60.0},
    {"key": "attribution.floor_ratio.spec_verify_fused",
     "direction": "max", "bound": 100.0},
    {"key": "attribution.compile_ms.serve_decode_fused",
     "direction": "max", "bound": 60000.0},
    {"key": "attribution.compile_ms.spec_verify_fused",
     "direction": "max", "bound": 60000.0},
    # prefix-cache block (ISSUE 18, docs/serving.md "Prefix sharing"):
    # under the 90%-shared system-prompt mix the admission hit rate must
    # clear its floor and hit admissions must actually skip prefill work
    # (tokens-saved fraction vs the index-off twin at the same seed);
    # TTFT p50 must never be SLOWER with the cache on (floor 1.0 — the
    # measured speedup rides the archive trajectory); the engine stays
    # zero-recompile after warmup with the prefix_prefill family
    # compiled (one executable per SUFFIX bucket, whatever the hit
    # pattern), and a workload that never hits pays under 1% of a p50
    # request for the hash-and-miss
    {"key": "serving.prefix_cache.shared.hit_rate", "direction": "min",
     "bound": 0.5},
    {"key": "serving.prefix_cache.prefill_tokens_saved_frac",
     "direction": "min", "bound": 0.3},
    {"key": "serving.prefix_cache.ttft_p50_speedup", "direction": "min",
     "bound": 1.0},
    {"key": "serving.prefix_cache.ttft_p50_speedup", "direction": "up",
     "tol_pct": 30.0},
    {"key": "serving.prefix_cache.shared.zero_recompiles_after_warmup",
     "direction": "min", "bound": 1.0},
    {"key": "serving.prefix_cache.zero_hit.hits", "direction": "max",
     "bound": 0.0},
    {"key": "serving.prefix_cache.zero_hit.overhead_pct",
     "direction": "max", "bound": 1.0},
    # fleet block (ISSUE 20, docs/fleet.md): the 3-replica zipf run with
    # a mid-run replica kill and a canary generation rollout must lose
    # ZERO accepted streams (a dead replica's in-flight streams
    # re-dispatch as continuations, never drop), the router's
    # placement-decision overhead stays under 1% of p50 request latency,
    # headroom-aware placement beats round-robin TTFT p99 on the same
    # trace (ratio <= 1.0 under the imbalanced pool mix), every replica
    # stays zero-recompile after warmup, and the canary rollout promotes
    # within its soak wall budget
    {"key": "fleet.lost_streams", "direction": "max", "bound": 0.0},
    {"key": "fleet.router_overhead_pct", "direction": "max",
     "bound": 1.0},
    {"key": "fleet.ttft_p99_ms", "direction": "down",
     "tol_pct": 50.0},
    {"key": "fleet.latency_p99_ms", "direction": "down",
     "tol_pct": 50.0},
    {"key": "fleet.placement_ttft_ratio", "direction": "max",
     "bound": 1.0},
    {"key": "fleet.zero_recompiles_after_warmup",
     "direction": "min", "bound": 1.0},
    {"key": "fleet.canary_promoted", "direction": "min",
     "bound": 1.0},
    {"key": "fleet.canary_soak_wall_s", "direction": "max",
     "bound": 120.0},
]


def _get_path(doc: dict, dotted: str):
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) else None


def _flatten(doc: dict) -> dict:
    """Normalize either record shape to one lookup dict: a trajectory
    point (``{parsed: {...}}``) exposes its ``parsed`` keys at top
    level; a detail doc (``BENCH_DETAIL.json`` / a fresh bench emit)
    already carries sections + headline keys together."""
    if isinstance(doc.get("parsed"), dict):
        merged = dict(doc)
        merged.update(doc["parsed"])
        return merged
    return doc


def load_trajectory(repo_root: str, patterns: list[str] | None = None):
    """(reference_doc, provenance): the newest trajectory point's compact
    record merged UNDER the section detail, so dotted keys resolve when
    the detail file carries them."""
    pats = patterns or ["BENCH_r0*.json"]
    points = []
    for pat in pats:
        for path in sorted(glob.glob(os.path.join(repo_root, pat))):
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            points.append((doc.get("n", 0), path, _flatten(doc)))
    if not points:
        return None, []
    points.sort(key=lambda t: t[0])
    _n, latest_path, ref = points[-1]
    provenance = [p for _, p, _ in points]
    detail_path = os.path.join(repo_root, "BENCH_DETAIL.json")
    if os.path.exists(detail_path):
        try:
            with open(detail_path) as f:
                detail = json.load(f)
            merged = dict(detail)
            merged.update({k: v for k, v in ref.items() if k not in merged})
            ref = merged
            provenance.append(detail_path)
        except (OSError, ValueError):
            pass
    return ref, provenance


def diff(fresh: dict, ref: dict | None, spec: list[dict]) -> dict:
    fresh = _flatten(fresh)
    rows = []
    for entry in spec:
        key = entry["key"]
        direction = entry["direction"]
        fv = _get_path(fresh, key)
        row = {
            "key": key,
            "direction": direction,
            "fresh": fv,
            "ref": None,
            "status": "ok",
        }
        if direction in ("max", "min"):
            bound = float(entry["bound"])
            row["bound"] = bound
            if fv is None:
                row["status"] = "skipped"
                row["why"] = "metric absent from fresh result"
            elif direction == "max" and fv > bound:
                row["status"] = "regression"
                row["why"] = f"{fv:g} exceeds the absolute budget {bound:g}"
            elif direction == "min" and fv < bound:
                row["status"] = "regression"
                row["why"] = f"{fv:g} is below the absolute floor {bound:g}"
        else:
            tol = float(entry.get("tol_pct", 0.0))
            rv = _get_path(ref, key) if ref else None
            row["ref"] = rv
            row["tol_pct"] = tol
            if fv is None or rv is None:
                row["status"] = "skipped"
                row["why"] = (
                    "metric absent from fresh result"
                    if fv is None
                    else "metric absent from trajectory"
                )
            elif direction == "up" and fv < rv * (1 - tol / 100):
                row["status"] = "regression"
                row["why"] = (
                    f"{fv:g} is {100 * (1 - fv / rv):.1f}% below the "
                    f"trajectory's {rv:g} (tolerance {tol:g}%)"
                )
            elif direction == "down" and fv > rv * (1 + tol / 100):
                row["status"] = "regression"
                row["why"] = (
                    f"{fv:g} is {100 * (fv / rv - 1):.1f}% above the "
                    f"trajectory's {rv:g} (tolerance {tol:g}%)"
                )
        rows.append(row)
    regressions = [r for r in rows if r["status"] == "regression"]
    skipped = [r for r in rows if r["status"] == "skipped"]
    return {
        "ok": not regressions,
        "rows": rows,
        "counts": {
            "checked": len(rows) - len(skipped),
            "regressions": len(regressions),
            "skipped": len(skipped),
        },
    }


def render_text(report: dict, provenance: list[str]) -> str:
    lines = []
    for r in report["rows"]:
        mark = {"ok": "ok  ", "skipped": "skip", "regression": "FAIL"}[
            r["status"]
        ]
        if r.get("ref") is not None:
            ref = f" vs {r['ref']:g} ±{r.get('tol_pct', 0):g}%"
        elif "bound" in r:
            op = ">=" if r["direction"] == "min" else "<="
            ref = f" {op} {r['bound']:g}"
        else:
            ref = ""
        fresh = "-" if r["fresh"] is None else f"{r['fresh']:g}"
        lines.append(
            f"[{mark}] {r['key']:<42} {r['direction']:>4}  {fresh}{ref}"
            + (f"  ({r['why']})" if "why" in r else "")
        )
    c = report["counts"]
    verdict = "PASSED" if report["ok"] else "FAILED"
    lines.append(
        f"bench-diff {verdict}: {c['checked']} checked, "
        f"{c['regressions']} regression(s), {c['skipped']} skipped "
        f"(trajectory: {len(provenance)} file(s))"
    )
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("fresh", help="fresh bench JSON (a BENCH_DETAIL-style "
                                 "doc or a compact trajectory record)")
    p.add_argument("--repo-root", default=_REPO_ROOT,
                   help="where the BENCH_r0*.json trajectory lives")
    p.add_argument("--trajectory", nargs="*", default=None, metavar="GLOB",
                   help="trajectory file patterns relative to --repo-root "
                        "(default: BENCH_r0*.json + BENCH_DETAIL.json)")
    p.add_argument("--spec", default=None,
                   help="JSON spec file overriding the built-in "
                        "direction+tolerance table")
    p.add_argument("--strict", action="store_true",
                   help="treat skipped metrics as failures")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write the machine-readable report ('-' = stdout)")
    args = p.parse_args(argv)

    try:
        with open(args.fresh) as f:
            fresh = json.load(f)
    except (OSError, ValueError) as e:
        print(f"error: cannot read fresh bench JSON {args.fresh}: {e}",
              file=sys.stderr)
        return 2
    spec = DEFAULT_SPEC
    if args.spec:
        try:
            with open(args.spec) as f:
                spec = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read spec {args.spec}: {e}",
                  file=sys.stderr)
            return 2
    ref, provenance = load_trajectory(args.repo_root, args.trajectory)
    if ref is None:
        print(
            f"error: no trajectory files under {args.repo_root} "
            "(expected BENCH_r0*.json)",
            file=sys.stderr,
        )
        return 2
    report = diff(fresh, ref, spec)
    if args.strict and report["counts"]["skipped"]:
        report["ok"] = False
    out = render_text(report, provenance)
    if args.json:
        doc = json.dumps(report, indent=2)
        if args.json == "-":
            print(doc)
        else:
            with open(args.json, "w") as f:
                f.write(doc + "\n")
            print(out)
    else:
        print(out)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
