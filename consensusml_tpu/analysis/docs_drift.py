"""Docs-drift pass: the metric schema in code and in docs must agree.

``docs/observability.md`` is the contract for every ``consensusml_*``
Prometheus family the stack emits — dashboards, alerts, and the cluster
aggregator are written against it. Families drift in two directions and
both rot silently:

- ``undocumented-metric`` — a family registered in code
  (``registry.counter/gauge/histogram("consensusml_...")``) that the doc
  never mentions: invisible to anyone reading the schema;
- ``stale-doc-metric`` — a family the doc lists that no code emits any
  more: an alert written against it will simply never fire.

Detection is static: one AST walk over the package + the CLI entry
points collects every string literal passed as the metric name to a
``counter``/``gauge``/``histogram`` call (f-strings record their literal
PREFIX — ``f"consensusml_{k}"`` marks the whole prefix as dynamically
emitted, so doc entries under it are exempt from the stale rule only
when the prefix is more specific than the bare ``consensusml_``
namespace); the doc side is every ``consensusml_\\w+`` token in
``docs/observability.md``.

Same baseline mechanics as the host-sync lint: a deliberate exception
(a family documented as a wildcard row, e.g. the ``MetricsLogger``'s
per-field gauges) is suppressed by its finding id in
``.cml-check-baseline`` with a comment saying why, and stale baseline
entries are reported when the drift gets fixed for real.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterable

from consensusml_tpu.analysis.findings import Finding

__all__ = ["emitted_families", "documented_families", "run", "check_repo"]

PASS = "docs-drift"
DOC_RELPATH = os.path.join("docs", "observability.md")
_METRIC_CALLS = {"counter", "gauge", "histogram"}
_NAME_RE = re.compile(r"consensusml_[a-z0-9_]*[a-z0-9]")


def _symbol_of(stack: list[str]) -> str:
    return ".".join(stack)


def emitted_families(
    py_files: Iterable[str], repo_root: str
) -> tuple[dict[str, tuple[str, str, int]], set[str]]:
    """Scan sources for metric registrations.

    Returns ``(families, dynamic_prefixes)``: ``families`` maps each
    literal family name to its first (repo-relative path, symbol, line)
    emission site; ``dynamic_prefixes`` holds the literal prefixes of
    f-string metric names (dynamically composed families the stale rule
    must not flag).
    """
    families: dict[str, tuple[str, str, int]] = {}
    dynamic: set[str] = set()
    for path in sorted(py_files):
        rel = os.path.relpath(os.path.abspath(path), repo_root)
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
        except (OSError, SyntaxError):
            continue

        stack: list[str] = []

        def visit(node: ast.AST) -> None:
            scoped = isinstance(
                node,
                (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
            )
            if scoped:
                stack.append(node.name)
            if isinstance(node, ast.Call):
                fn = node.func
                attr = (
                    fn.attr
                    if isinstance(fn, ast.Attribute)
                    else fn.id if isinstance(fn, ast.Name) else None
                )
                if attr in _METRIC_CALLS and node.args:
                    arg = node.args[0]
                    if (
                        isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)
                        and arg.value.startswith("consensusml_")
                    ):
                        families.setdefault(
                            arg.value,
                            (rel, _symbol_of(stack), node.lineno),
                        )
                    elif isinstance(arg, ast.JoinedStr) and arg.values:
                        head = arg.values[0]
                        if (
                            isinstance(head, ast.Constant)
                            and isinstance(head.value, str)
                            and head.value.startswith("consensusml_")
                            # a bare f"consensusml_{k}" must not exempt
                            # the whole namespace from the stale rule
                            and len(head.value) > len("consensusml_")
                        ):
                            dynamic.add(head.value)
            # any f-string in the module whose head is a consensusml_
            # prefix marks dynamic composition even when the call passes
            # it through a variable (utils/logging.py's _PROM_SAFE path)
            if isinstance(node, ast.JoinedStr) and node.values:
                head = node.values[0]
                if (
                    isinstance(head, ast.Constant)
                    and isinstance(head.value, str)
                    and head.value.startswith("consensusml_")
                    and len(head.value) > len("consensusml_")
                ):
                    dynamic.add(head.value)
            for child in ast.iter_child_nodes(node):
                visit(child)
            if scoped:
                stack.pop()

        visit(tree)
    return families, dynamic


def documented_families(doc_path: str) -> set[str]:
    """Family names the doc commits to. Wildcard/namespace references —
    ``consensusml_serve_*`` prose, ``consensusml_tpu/obs`` module paths,
    ``consensusml_tpu.obs`` imports — are not family names and are
    skipped (the trailing ``*``/``/``/``.`` gives them away)."""
    try:
        with open(doc_path) as f:
            text = f.read()
    except OSError:
        return set()
    out: set[str] = set()
    for m in _NAME_RE.finditer(text):
        tail = text[m.end():m.end() + 2]
        if tail[:1] in ("*", "/", ".") or tail == "_*":
            continue
        out.add(m.group(0))
    return out


def default_sources(repo_root: str) -> list[str]:
    """The emitting surface: the package plus the CLI entry points that
    register families directly (train/worker/loadgen)."""
    out: list[str] = []
    pkg = os.path.join(repo_root, "consensusml_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        out.extend(
            os.path.join(dirpath, f)
            for f in filenames
            if f.endswith(".py")
        )
    for extra in ("train.py", "worker.py"):
        p = os.path.join(repo_root, extra)
        if os.path.exists(p):
            out.append(p)
    tools = os.path.join(repo_root, "tools")
    if os.path.isdir(tools):
        out.extend(
            os.path.join(tools, f)
            for f in os.listdir(tools)
            if f.endswith(".py")
        )
    return out


def run(
    repo_root: str,
    py_files: Iterable[str] | None = None,
    doc_path: str | None = None,
) -> list[Finding]:
    files = (
        list(py_files) if py_files is not None else default_sources(repo_root)
    )
    doc = (
        doc_path
        if doc_path is not None
        else os.path.join(repo_root, DOC_RELPATH)
    )
    emitted, dynamic = emitted_families(files, repo_root)
    documented = documented_families(doc)
    doc_rel = os.path.relpath(os.path.abspath(doc), repo_root)

    findings: list[Finding] = []
    for name in sorted(set(emitted) - documented):
        rel, symbol, line = emitted[name]
        findings.append(
            Finding(
                PASS,
                "undocumented-metric",
                rel,
                symbol,
                name,
                f"metric family {name!r} is emitted here but missing from "
                f"{doc_rel} — document it (kind + meaning)",
                line,
            )
        )
    # doc names with no literal emission: stale, unless a dynamic
    # f-string prefix covers them (e.g. consensusml_swarm_* composed at
    # runtime would be exempt under the "consensusml_swarm_" prefix)
    for name in sorted(documented - set(emitted)):
        if any(name.startswith(p) for p in dynamic):
            continue
        findings.append(
            Finding(
                PASS,
                "stale-doc-metric",
                doc_rel,
                "<doc>",
                name,
                f"{doc_rel} documents {name!r} but no code emits it — "
                "remove the entry or restore the metric",
                0,
            )
        )
    return findings


def check_repo(repo_root: str) -> list[Finding]:
    """CLI entry (tools/cml_check.py --docs)."""
    return run(repo_root)
