"""The hybrid (``nemotron_h``) cell rehearsed on the CPU at smoke sizes, from a
temporary directory: its driver, reference, rounds and metric files found by
name beside the committed harness, which is not edited. The look for a chip is
the one thing skipped (``require_chip=False``); off a TPU the expert products
are ``lax.ragged_dot`` and attention is dense, so the kernels' rooflines are
left out (never 0)."""

import json
import os

import pytest

import run as harness

TINY = {
    "driver": "train_nemotron_h", "vocab_size": 64, "hidden_size": 32,
    "hybrid_override_pattern": "MEMEM*EME", "num_hidden_layers": 9, "num_hidden_layers_published": 52,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
    "chunk_size": 8, "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "n_routed_experts": 4, "n_routed_experts_published": 8, "held_experts_start": 0,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "norm_eps": 1e-5, "compute_dtype": "bfloat16",
    "e_score_correction_bias": "centred",
    "train": {"recipe": "nemotron_h_ep16", "scale": "smoke", "batch": 2, "seq": 32, "h": 2,
              "learning_rate": 3e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8, "gossip": "exact"},
}
SOLO = {
    "kind": "train_rounds", "workers": 1, "backend": "simulated",
    # at hidden 32 bfloat16 is loud: the program reads moment_diff 0.30-0.35 where fp8 reads
    # 0.45-0.59; the mean gap of the change (0.005-0.006 against 0.012-0.015), the routing
    # (0-0.003 against 0.010) and the scan's output (0.001 against 0.007-0.011) part them
    "check": {"loss_gap_round1": 0.01, "loss_gap_round3": 0.02, "moment_diff": 0.42,
              "moment_norm_gap": 0.07, "change_norm_gap": 0.06, "change_norm_gap_mean": 0.009,
              "routing_disagreement": 0.006, "scan_rms_gap": 0.004},
}
CELL = "tiny_hybrid.solo"
LAYER = {
    "mfu.train": ("mfu", {}),
    "round_p50_ms.train": ("stat", {"key": "round_p50_ms"}),
    "moe_gmm_roofline.train": ("roofline", {"pattern": "^%?moe_gmm", "flops_key": "moe_gmm_flops", "bytes_key": "moe_gmm_bytes"}),
    "moe_load_max_over_mean.train": ("stat", {"key": "moe_load_max_over_mean"}),
    "moe_rows_per_step.train": ("stat", {"key": "moe_rows_per_step"}),
}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_hybrid"))
    d = os.path.join(root, "benchmarks")
    _write(os.path.join(d, "configs", "tiny_hybrid.json"), TINY)
    _write(os.path.join(d, "traffic", f"{CELL}.json"), SOLO)
    _write(os.path.join(d, "peaks.json"), {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "rehearsal"}})
    for name, (reader, args) in LAYER.items():
        _write(os.path.join(d, "layer_metrics", f"{name}.json"), {"reader": reader, "args": args})
    bench = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"], "run_seconds": 3,
        "configs": [{"name": "tiny_hybrid", "source": "test", "file": "benchmarks/configs/tiny_hybrid.json",
                     "reduced": [], "why": "t"}],
        "workloads": [{"name": CELL, "config": "tiny_hybrid", "traffic": "solo", "chips": 1, "why": "t"}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.01, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"},
        ],
        "per_layer": [
            {"name": n, "unit": "x", "better": "higher", "source": "host_clock", "layer": "t", "moves": "train_tokens_per_s"}
            for n in LAYER
        ],
    }
    path = os.path.join(root, "BENCHMARK.json")
    _write(path, bench)
    return path


def run_cell(bench_file, capsys, trace=0, seed=3_000_000_017):
    rc = harness.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        bench_file=bench_file, require_chip=False,
    )
    captured = capsys.readouterr()
    assert rc == 0
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_the_hybrid_cell_prints_the_result_line(bench_file, capsys):
    line, err = run_cell(bench_file, capsys)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    names = [c["name"] for c in line["checks"]]
    assert names == ["change_norm_gap", "change_norm_gap_mean", "loss_gap_round1", "loss_gap_round3",
                     "moment_diff", "moment_norm_gap", "routing_disagreement", "scan_rms_gap",
                     "nonfinite_or_collapsed_rounds", "compiles_in_window"]
    assert err.strip().splitlines()[-1] == "bench: correct: True"


def test_a_traced_run_reads_the_expert_layers_counters(bench_file, capsys):
    line, _ = run_cell(bench_file, capsys, trace=1, seed=3_000_000_018)
    metrics = line["metrics"]
    assert metrics["mfu.train"]["value"] > 0 and metrics["round_p50_ms.train"]["value"] > 0
    # 2 steps x 2 rows x 32 tokens x 3 choices, about half of them for the 4 of 8 experts held
    per_step = metrics["moe_rows_per_step.train"]["value"]
    assert 0.2 * 4 * 2 * 32 * 3 < per_step < 0.8 * 4 * 2 * 32 * 3
    assert metrics["moe_load_max_over_mean.train"]["value"] >= 1.0
    assert "moe_gmm_roofline.train" not in metrics  # no kernel off a TPU: left out, never 0


def test_controls_and_faults_in_the_programs_place_read_not_correct(bench_file):
    cell = harness.load_cell(bench_file, CELL)
    mod = harness._load_module("drivers", "train_nemotron_h", cell["bench_dirs"])
    driver = mod.Driver(dict(cell, seed=11, device={}, peaks=None))
    faults = ("half_batch", "top5", "renorm_over_held", "no_state_carry")
    try:
        driver.setup(1.0)
        driver.window(0.2, 0.0)
        driver.release()
        read = driver.readings(("fp8",), faults=faults)
    finally:
        driver.close()
    failed = {side: [c["name"] for c in driver.judge(numbers) if not c["ok"]] for side, numbers in read.items()}
    assert failed["program"] == []
    for side in ("fp8", "half_batch", "top5", "renorm_over_held"):
        assert failed[side], side
    assert read["top5"]["routing_disagreement"] >= 1 / 3
    assert "routing_disagreement" in failed["top5"] and "moment_diff" in failed["renorm_over_held"]
    assert read["fp8"]["change_norm_gap_mean"] > 1.8 * read["program"]["change_norm_gap_mean"]
    # at these widths (state 16, 32 tokens) the scan's output IS the skip term D x to 2e-5,
    # so a state lost between chunks cannot show here; tests/test_nemotron_h.py plants it in
    # the program against the recurrence, and PERF.md has the reading at the cell's size
    assert read["no_state_carry"]["scan_rms_gap"] < read["fp8"]["scan_rms_gap"]


def test_a_fault_in_the_timed_round_fails_the_numbers_read_from_it(bench_file, monkeypatch):
    """``routing_disagreement`` and ``scan_rms_gap`` come out of the compiled round that
    the window drives (its metrics, ``LossAux.first_step``), not out of a second program:
    a router that the ROUND traces with one choice in three wrong is seen."""
    from consensusml_tpu.models import moe

    sound = moe.route_top_k

    def one_choice_wrong(scores, k, scale, bias=None):
        idx, weights = sound(scores, k, scale, bias)
        return idx.at[:, -1].set((idx[:, -1] + 1) % scores.shape[-1]), weights

    monkeypatch.setattr(moe, "route_top_k", one_choice_wrong)
    cell = harness.load_cell(bench_file, CELL)
    mod = harness._load_module("drivers", "train_nemotron_h", cell["bench_dirs"])
    driver = mod.Driver(dict(cell, seed=12, device={}, peaks=None))
    try:
        driver.setup(1.0)
        driver.window(0.2, 0.0)
        driver.release()
        read = driver.readings()["program"]
    finally:
        driver.close()
    assert read["routing_disagreement"] > 0.15
    assert "routing_disagreement" in [c["name"] for c in driver.judge(read) if not c["ok"]]


def test_a_tree_without_the_hybrid_decoder_ends_at_once_with_exit_3(bench_file, monkeypatch):
    import builtins

    real = builtins.__import__

    def no_hybrid(name, *args, **kwargs):
        if name == "consensusml_tpu.models.nemotron_h":
            raise ImportError("No module named 'consensusml_tpu.models.nemotron_h'")
        return real(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", no_hybrid)
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1"], bench_file=bench_file, require_chip=False)
    assert e.value.code == 3
