"""chip_smoke.py's phases rehearsed at ``smoke`` scale on the CPU mesh —
the same functions, the same checks, the same entry points (train.main,
load_engine, ServeServer, run_loadgen) — and the script's own refusal to
run anywhere but on the accelerator."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_phases_rehearsed_at_smoke_scale(tmp_path):
    import chip_smoke

    train = chip_smoke.train_phase(
        "smoke", "cpu", workers=4, rounds=2, workdir=str(tmp_path)
    )
    # the four-chip shape: collective ring, one shard of the state per
    # device, replicas that disagree
    assert train["backend"] == "collective" and train["state_devices"] == 4
    assert len(train["losses"]) == 2 and min(train["consensus_errors"]) > 0
    serve = chip_smoke.serve_phase(
        train["artifact"], num_slots=4, max_len=0, max_new_tokens=4,
        n_requests=3, prompt_lens=(4, 20),
    )
    assert serve["attn_impl"] == "gather"
    assert serve["compile_counts"]["decode"] == 1
    assert serve["requests"]["greedy"]["completed"] == 3
    assert serve["requests"]["sampled"]["tokens_out"] == 12
    assert len(serve["greedy_replay"]) == 4


def test_a_failed_check_ends_the_run(tmp_path):
    """No try/except that logs and carries on: a phase that cannot meet
    its checks raises."""
    import chip_smoke

    with pytest.raises(AssertionError, match="chip_smoke"):
        chip_smoke._check(False, "nothing was served")
    with pytest.raises(Exception):
        chip_smoke.serve_phase(
            str(tmp_path / "no_artifact_here"), num_slots=2, max_len=0,
            max_new_tokens=2, n_requests=1, prompt_lens=(4, 8),
        )


def test_final_line_is_ok_and_device_only():
    """The driver reads the last stdout line and refuses anything but
    exactly ``ok`` + ``device{platform, kind, count}`` — the phase records
    go on the lines above it."""
    import json

    import chip_smoke

    line = chip_smoke.final_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_script_exits_nonzero_off_the_accelerator():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 2, (proc.stdout, proc.stderr)
    assert "not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line without a chip
