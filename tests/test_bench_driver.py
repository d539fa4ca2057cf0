"""bench.py's driver contract: the one final JSON line always lands, is
capped, and the exit code says whether every section ran."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_module():
    """Import bench.py (repo root, not a package) as a module."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_env(tmp_path, **extra):
    """Env for bench.py subprocess tests: BENCH_DETAIL_PATH is redirected
    so a suite run never writes a record into the repo."""
    return {
        **os.environ,
        "BENCH_DETAIL_PATH": str(tmp_path / "detail.json"),
        **extra,
    }


def _final_and_detail(stdout: str):
    """Split bench.py stdout into (final compact record, full detail).

    The driver parses the LAST line; section detail rides an earlier
    ``BENCH_DETAIL`` line (see bench.py FINAL_LINE_LIMIT rationale)."""
    limit = _bench_module().FINAL_LINE_LIMIT
    final_line = [l for l in stdout.splitlines() if l.startswith("{")][-1]
    assert len(final_line.encode()) <= limit, len(final_line.encode())
    detail_line = [
        l for l in stdout.splitlines() if l.startswith("BENCH_DETAIL ")
    ][-1]
    return json.loads(final_line), json.loads(detail_line[len("BENCH_DETAIL "):])


@pytest.mark.slow
def test_bench_emits_headline_json_when_budget_exhausted(tmp_path):
    """The one driver-parsed JSON line must land even when the global
    budget leaves no room for any section: every section is skipped (a
    skip is not a failure: rc 0), value is 0, and the note says why."""
    r = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
        env=_bench_env(
            tmp_path,
            BENCH_DEVICE="cpu",
            BENCH_TOTAL_BUDGET="10",  # below the per-section floor
        ),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out, detail = _final_and_detail(r.stdout)
    assert out["unit"] == "imgs/sec/chip" and out["value"] == 0.0
    assert out["vs_baseline"] == 0.0
    assert "budget exhausted" in json.dumps(detail)
    assert "preflight" not in detail


@pytest.mark.slow
def test_bench_sigterm_lands_partial_json(tmp_path):
    """The driver's timeout delivers SIGTERM before SIGKILL; bench.py
    must use that window to print the partial headline line, and exit
    non-zero: a cut-short run is not a complete record."""
    import signal
    import time

    proc = subprocess.Popen(
        [sys.executable, "bench.py"],
        cwd=REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=_bench_env(
            tmp_path,
            BENCH_DEVICE="cpu",
            BENCH_TOTAL_BUDGET="3000",  # roomy: sections would run
        ),
    )
    time.sleep(5)  # inside the first (slow) section's child
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=60)
    assert proc.returncode == 1, err[-2000:]
    parsed, _ = _final_and_detail(out)
    assert parsed["unit"] == "imgs/sec/chip"
    assert "signal 15" in parsed["note"]


def test_bench_final_line_capped_worst_case():
    """The driver's tail window is ~2000 bytes; round 4's record died when
    the one JSON line outgrew it. build_final_line must cap the line at
    800 bytes for ANY note — including one bigger than the window itself —
    while never dropping the numeric fields."""
    bench = _bench_module()

    worst_note = (
        'a "quoted" note with escapes \\ and unicode é ' * 200
    )  # ~9 KB pre-escaping, expands further when JSON-escaped
    payload = {
        "metric": "imgs/sec/chip (ResNet-50 consensus-SGD, bf16 224px)",
        "value": 2536.13,
        "unit": "imgs/sec/chip",
        "vs_baseline": 1.0144,
        "elapsed_s": 2512.7,
        "note": worst_note,
    }
    line = bench.build_final_line(payload)
    assert len(line.encode("utf-8")) <= bench.FINAL_LINE_LIMIT, len(line.encode("utf-8"))
    out = json.loads(line)
    assert out["value"] == 2536.13 and out["vs_baseline"] == 1.0144
    assert out["unit"] == "imgs/sec/chip" and out["elapsed_s"] == 2512.7
    assert out["note"].endswith("...") and len(out["note"]) > 0

    # empty and short notes pass through untouched
    for note in ("", "short note"):
        line = bench.build_final_line({**payload, "note": note})
        assert json.loads(line)["note"] == note
        assert len(line.encode()) <= bench.FINAL_LINE_LIMIT


def test_bench_final_line_capped_even_without_note_to_trim():
    """With the note exhausted, optional fields drop (in declared order)
    until the line fits; "value" survives every cut. A pathological
    payload that STILL overflows is byte-truncated — an over-window line
    is lost entirely, a clipped one at least lands its head."""
    bench = _bench_module()

    huge_metric = "m" * 2000  # no note to trim: the metric itself overflows
    payload = {
        "metric": huge_metric,
        "value": 2536.13,
        "unit": "imgs/sec/chip",
        "vs_baseline": 1.0144,
        "elapsed_s": 2512.7,
        "note": "",
    }
    line = bench.build_final_line(payload)
    assert len(line.encode("utf-8")) <= bench.FINAL_LINE_LIMIT
    out = json.loads(line)  # still valid JSON: the overflow field dropped
    assert out["value"] == 2536.13
    assert "metric" not in out

    # un-droppable overflow (value itself too wide for a 16-byte limit):
    # byte-truncation is the last resort — never a >limit line
    line = bench.build_final_line({"value": 10.0 / 3.0, "x": "y" * 900}, limit=16)
    assert len(line.encode("utf-8")) <= 16
