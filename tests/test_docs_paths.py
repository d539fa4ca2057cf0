"""The documents that describe the system as it is cite only files that
exist, and README's quick start only commands that parse.

History (CHANGES.md, ROADMAP.md, PERF.md, SURVEY.md) is not checked: it
may name what was deleted.
"""

import glob
import os
import re
import shlex

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md",
    "BASELINE.md",
    "benchmarks/README.md",
    ".claude/skills/verify/SKILL.md",
    "docs/studies/README.md",
    *sorted(
        os.path.relpath(p, REPO).replace(os.sep, "/")
        for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
    ),
]

# a path is a run of name characters that ends in one of the four
# extensions; a placeholder (`drivers/<driver>.py`) or a glob (`*.py`)
# breaks the run and is not a citation
_CITED = re.compile(r"(?<![\w./<>*-])[\w./-]*\w\.(?:py|md|cc|h)\b(?![\w/])")


@pytest.fixture(scope="module")
def files() -> list[str]:
    """Every file of the checkout, bar what building, testing and running
    leave behind (the dot-directories but `.claude`, and the caches)."""
    found = []
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [
            d for d in dirnames
            if d == ".claude" or not (d.startswith(".") or d in ("__pycache__", "chiprun_out", "build"))
        ]
        rel = os.path.relpath(dirpath, REPO).replace(os.sep, "/")
        found += [f if rel == "." else f"{rel}/{f}" for f in filenames]
    return found


def _exists(cited: str, files: list[str]) -> bool:
    if "/" not in cited:
        return any(os.path.basename(f) == cited for f in files)
    return any(f == cited or f.endswith("/" + cited) for f in files)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_cited_paths_exist(document, files):
    with open(os.path.join(REPO, document), encoding="utf-8") as fh:
        text = fh.read()
    cited = sorted(set(_CITED.findall(text)))
    assert cited, "the pattern found no path"
    stale = [c for c in cited if not _exists(c, files)]
    assert not stale, f"{document} cites files that do not exist: {stale}"


def _readme() -> str:
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as fh:
        return fh.read()


def _quick_start_commands() -> list[list[str]]:
    """The `python train.py ...` commands of README's quick start, with
    continuation lines joined and trailing comments dropped."""
    text = _readme()
    start = text.index("## Quick start")
    block = text[start : text.index("\n## ", start + 1)].replace("\\\n", " ")
    return [
        shlex.split(line, comments=True)
        for line in block.splitlines()
        if line.startswith("python train.py ")
    ]


def test_readme_commands_parse():
    import train
    from consensusml_tpu import configs

    commands = _quick_start_commands()
    assert len(commands) >= 10, commands
    named = set()
    for argv in commands:
        try:
            args = train.parse_args(argv[2:])
        except SystemExit as e:  # argparse's way of refusing
            pytest.fail(f"README: {' '.join(argv)!r} does not parse ({e.code})")
        if args.list:
            continue
        assert args.config in configs.names(), argv
        named.add(args.config)
    # the quick start shows every packaged recipe, and says how many there are
    assert named == set(configs.names())
    assert len(named) == 8 and "# list the eight packaged workloads" in _readme()
