"""The driver rehearsed on the CPU at smoke sizes, from a temporary directory.

The directory holds its own ``BENCHMARK.json``, a configuration, a traffic
file, per-layer metric files and one reader of its own: the harness finds all
of them by name and falls back to the committed drivers and readers. That is
how a later PR adds a cell, a configuration or a metric without editing a file.
The look for a chip is the one thing skipped (``require_chip=False``).
"""

import json
import os

import pytest

import run as harness

TRAIN_CFG = {
    "driver": "train", "vocab_size": 64, "n_positions": 32, "n_embd": 32, "n_layer": 2,
    "n_head": 2, "n_inner": 128, "layer_norm_epsilon": 1e-6, "resid_pdrop": 0.0, "embd_pdrop": 0.0, "attn_pdrop": 0.0,
    "compute_dtype": "bfloat16",
    "train": {"recipe": "gpt2_topk", "scale": "smoke", "batch": 8, "seq": 16, "h": 2,
              "learning_rate": 3e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8,
              "codec_chunk": 128, "codec_k": 13,
              "codec_warmup_rounds": 0, "codec_refresh_every": 0},
}
MOMENT_DIFF_LIMIT = 0.05
SOLO = {
    "kind": "train_rounds", "workers": 1, "backend": "simulated",
    "check": {"rows_per_block": 4, "loss_gap_round1": 0.01, "loss_gap_round2": 0.01,
              "loss_gap_round3": 0.01, "moment_diff": MOMENT_DIFF_LIMIT, "moment_norm_gap": 0.05,
              "change_norm_gap": 0.3, "track_norm_gap": 0.3},
}
NEW_READER = '''"""A reader that exists only in the temporary directory."""


def read(ctx, key):
    return ctx["stats"].get(key)
'''


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    d = os.path.join(root, "benchmarks")
    _write(os.path.join(d, "configs", "tiny_choco.json"), TRAIN_CFG)
    _write(os.path.join(d, "traffic", "tiny_choco.solo.json"), SOLO)
    _write(os.path.join(d, "peaks.json"), {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "rehearsal"}})
    _write(os.path.join(d, "readers", "plain.py"), NEW_READER)
    layer = {
        "final_loss.train": ("plain", {"key": "final_loss"}),
        "mfu.train": ("mfu", {}),
        "flash_attn_roofline.train": ("roofline", {"pattern": "custom-call", "flops_key": "flash_flops"}),
        "round_p50_ms.train": ("stat", {"key": "round_p50_ms"}),
    }
    for name, (reader, args) in layer.items():
        _write(os.path.join(d, "layer_metrics", f"{name}.json"), {"reader": reader, "args": args})
    bench = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"], "run_seconds": 3,
        "configs": [
            {"name": "tiny_choco", "source": "test", "file": "benchmarks/configs/tiny_choco.json", "reduced": [], "why": "t"},
        ],
        "workloads": [
            {"name": "tiny_choco.solo", "config": "tiny_choco", "traffic": "solo", "chips": 1, "why": "t"},
        ],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.01, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"},
        ],
        "per_layer": [
            {"name": n, "unit": "x", "better": "higher", "source": "host_clock", "layer": "t", "moves": "train_tokens_per_s"}
            for n in layer
        ],
    }
    path = os.path.join(root, "BENCHMARK.json")
    _write(path, bench)
    return path


def run_cell(bench_file, capsys, trace=0, seed=3_000_000_001):
    rc = harness.main(
        ["--workload", "tiny_choco.solo", "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        bench_file=bench_file, require_chip=False,
    )
    captured = capsys.readouterr()
    assert rc == 0
    line = json.loads(captured.out.strip().splitlines()[-1])
    return line, captured.err


def test_the_cell_prints_the_result_line(bench_file, capsys):
    line, err = run_cell(bench_file, capsys)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # each number compared stands beside its limit, in the line and as the last lines of stderr
    names = [c["name"] for c in line["checks"]]
    assert names == ["change_norm_gap", "loss_gap_round1", "loss_gap_round2", "loss_gap_round3", "moment_diff",
                     "moment_norm_gap", "track_norm_gap", "nonfinite_or_collapsed_rounds", "compiles_in_window"]
    tail = err.strip().splitlines()[-(len(names) + 1):]
    assert tail[-1] == "bench: correct: True"
    assert all(f"check {n}:" in l and "limit" in l for n, l in zip(names, tail))


def test_a_traced_run_reads_per_layer_metrics_through_readers_found_by_name(bench_file, capsys):
    line, _ = run_cell(bench_file, capsys, trace=1)
    assert list(line)[-1] == "checks" and "breakdown" in line
    assert {"busy_s", "window_s"} <= set(line["device"])
    # the temporary directory's own reader was found; the committed ones too
    assert line["metrics"]["final_loss.train"]["value"] > 0
    assert line["metrics"]["mfu.train"]["value"] > 0
    assert line["metrics"]["round_p50_ms.train"]["value"] > 0
    # no device plane on the CPU: a roofline share is left out, never reported as 0
    assert "flash_attn_roofline.train" not in line["metrics"]


def _train_driver():
    return harness._load_module("drivers", "train", [harness.HERE])


_real_load = harness._load_module


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_training_step_reads_not_correct(bench_file, capsys, monkeypatch, fault):
    import jax

    mod = _train_driver()
    real = mod.Driver.build_step

    def broken(self, cfg, loss_fn):
        if fault == "half_batch":  # half of the batch left out, the mean over the rest
            def half(params, model_state, batch, rng):
                return loss_fn(params, model_state, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch), rng)

            return real(self, cfg, half)
        step = real(self, cfg, loss_fn)

        def unchanged(state, batch):  # a step that returns its state unchanged
            held = jax.tree.map(lambda x: x.copy(), state)
            _, metrics = step(state, batch)
            return held, metrics

        return unchanged

    monkeypatch.setattr(mod.Driver, "build_step", broken)
    monkeypatch.setattr(harness, "_load_module", lambda kind, name, dirs: mod if kind == "drivers" else _real_load(kind, name, dirs))
    line, _ = run_cell(bench_file, capsys, seed=6)
    failed = [c["name"] for c in line["checks"] if c["value"] > c["limit"]]
    assert line["correct"] is False and failed


def test_lower_precision_in_the_programs_place_reads_not_correct(bench_file):
    """The control at a size a test can hold (the program's smoke recipe,
    hidden 32), through the comparison a run makes: the reference with fp8
    operands put in the program's place fails ``moment_diff``, the difference
    of the first moments themselves, and so does every planted fault. int8
    with a scale per row keeps 7 bits of magnitude where bfloat16 keeps 8 of
    significand: it is no lower precision and reads like the program. The
    readings at the cell's own size are in PERF.md."""
    cell = harness.load_cell(bench_file, "tiny_choco.solo")
    driver = _train_driver().Driver(dict(cell, seed=7, device={}, peaks=None))
    try:
        driver.setup(1.0)
        driver.window(0.2, 0.0)
        driver.release()
        read = driver.readings(("fp8",), faults=("half_batch", "codec_int4"))
    finally:
        driver.close()
    failed = {side: [c["name"] for c in driver.judge(numbers) if not c["ok"]] for side, numbers in read.items()}
    assert failed["program"] == []
    assert "moment_diff" in failed["fp8"]
    assert read["fp8"]["moment_diff"] > 2.5 * read["program"]["moment_diff"]
    assert read["half_batch"]["loss_gap_round1"] > 10 * read["program"]["loss_gap_round1"]
    assert read["half_batch"]["moment_norm_gap"] > 10 * read["program"]["moment_norm_gap"]
    assert {"moment_diff", "moment_norm_gap"} <= set(failed["half_batch"])
    assert read["codec_int4"]["track_norm_gap"] > 10 * read["program"]["track_norm_gap"]
    assert driver.left_out_leaves == 2  # the two layers' key biases: no gradient under softmax


def test_off_the_chip_there_is_no_result(bench_file, capsys, monkeypatch):
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "tiny_choco.solo", "--seed", "1", "--seconds", "1"], bench_file=bench_file)
    assert e.value.code == 2 and capsys.readouterr().out == ""
    # a TPU whose kind the table of peaks does not know is an error, never a default
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = harness.load_cell(bench_file, "tiny_choco.solo")
    with pytest.raises(SystemExit) as e:
        harness.find_device(dict(cell, peaks_table={"TPU v5 lite": {}}))
    assert e.value.code == 2 and capsys.readouterr().out == ""
