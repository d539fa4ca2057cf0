"""Finding model + suppression baseline for the cml-check passes.

Every pass reports :class:`Finding`s. A finding's ``id`` is built from
WHAT was found and WHERE (pass, rule, file, enclosing symbol, detail
token) but deliberately excludes the line number, so a baseline entry
survives unrelated edits to the same file. Two findings in the same
function that trip the same rule on the same callee share an id — a
suppression therefore covers both, which is the right granularity for
"this function intentionally syncs" style allowlisting.

The baseline file (``.cml-check-baseline`` at the repo root) is one
finding id per line; ``#`` starts a comment (inline or whole-line).
Workflow: a NEW finding either gets fixed or — when the sync/access is
intentional — its id is appended to the baseline with a comment saying
why. ``tools/cml_check.py --write-baseline`` regenerates the file from
the current findings; stale entries (baselined ids that no longer fire)
are reported so the allowlist never rots silently.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Iterable

__all__ = [
    "Finding",
    "Baseline",
    "load_baseline",
    "split_suppressed",
    "render_report",
    "to_json",
]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One static-analysis finding.

    ``symbol`` is the dotted path of the enclosing scope inside the file
    (``Class.method`` / ``function.<locals>.inner``); ``detail`` is the
    rule-specific token that makes the id precise (the callee name for a
    host-sync call, the attribute for a lock violation, the topology name
    for a schedule fault).
    """

    pass_name: str  # host-sync | locks | schedule | jaxpr
    rule: str  # e.g. sync-in-traced, unguarded-write, deadlock-op-mismatch
    path: str  # repo-relative file (or a symbolic source for non-file passes)
    symbol: str  # enclosing scope ("" for module level)
    detail: str  # rule-specific token
    message: str  # human sentence
    line: int = 0  # 1-based; 0 when not tied to a source line
    # model-pass findings carry the minimal action trace that reaches the
    # violation, so CI logs hold the repro without rerunning the checker
    counterexample: tuple[str, ...] = ()

    @property
    def id(self) -> str:
        return ":".join(
            (self.pass_name, self.rule, self.path, self.symbol or "<module>",
             self.detail)
        )

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["id"] = self.id
        if not self.counterexample:
            d.pop("counterexample", None)
        else:
            d["counterexample"] = list(self.counterexample)
        return d

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{loc}{sym}: {self.rule}: {self.message}\n    id: {self.id}"


@dataclasses.dataclass
class Baseline:
    """Parsed suppression file: ids plus provenance for stale reporting."""

    path: str | None
    ids: frozenset[str]

    def __contains__(self, finding_id: str) -> bool:
        return finding_id in self.ids


def load_baseline(path: str | None) -> Baseline:
    """Read a baseline file; a missing file is an empty baseline (the
    passes then report everything, which is what a fresh checkout of a
    new project wants)."""
    ids: set[str] = set()
    if path and os.path.exists(path):
        with open(path) as f:
            for raw in f:
                line = raw.split("#", 1)[0].strip()
                if line:
                    ids.add(line)
    return Baseline(path=path, ids=frozenset(ids))


def split_suppressed(
    findings: Iterable[Finding], baseline: Baseline
) -> tuple[list[Finding], list[Finding], list[str]]:
    """``(active, suppressed, stale_baseline_ids)``.

    ``active`` are the findings the run fails on; ``stale`` are baseline
    entries no current finding matches — reported (not fatal) so dead
    suppressions get pruned instead of hiding future regressions under
    an id that happens to match."""
    findings = list(findings)
    active = [f for f in findings if f.id not in baseline]
    suppressed = [f for f in findings if f.id in baseline]
    seen = {f.id for f in findings}
    stale = sorted(i for i in baseline.ids if i not in seen)
    return active, suppressed, stale


def render_report(
    active: list[Finding],
    suppressed: list[Finding],
    stale: list[str],
    *,
    passes_run: list[str],
) -> str:
    lines: list[str] = []
    by_pass: dict[str, list[Finding]] = {}
    for f in active:
        by_pass.setdefault(f.pass_name, []).append(f)
    for name in passes_run:
        fs = by_pass.get(name, [])
        status = "FAIL" if fs else "ok"
        lines.append(f"[{status}] {name}: {len(fs)} finding(s)")
        for f in sorted(fs, key=lambda f: (f.path, f.line, f.id)):
            lines.append("  " + f.render().replace("\n", "\n  "))
    if suppressed:
        lines.append(f"(suppressed by baseline: {len(suppressed)})")
    for sid in stale:
        lines.append(f"(stale baseline entry — prune it: {sid})")
    verdict = "FAILED" if active else "PASSED"
    lines.append(
        f"cml-check {verdict}: {len(active)} active finding(s), "
        f"{len(suppressed)} suppressed, {len(stale)} stale baseline entries"
    )
    return "\n".join(lines)


def to_json(
    active: list[Finding],
    suppressed: list[Finding],
    stale: list[str],
    *,
    passes_run: list[str],
    timings: dict[str, float] | None = None,
) -> str:
    doc = {
        "ok": not active,
        "passes": passes_run,
        "findings": [f.to_dict() for f in active],
        "suppressed": [f.to_dict() for f in suppressed],
        "stale_baseline": stale,
        "counts": {
            "active": len(active),
            "suppressed": len(suppressed),
            "stale": len(stale),
        },
    }
    if timings is not None:
        # per-pass wall seconds (test_cml_check.py asserts the AST-pass budgets)
        doc["pass_seconds"] = {k: round(v, 4) for k, v in timings.items()}
    return json.dumps(doc, indent=2)
