"""The ``qwen3_next`` cell rehearsed on the CPU at smoke sizes, from a
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
    "driver": "train_qwen3_next", "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 4,
    "num_hidden_layers_published": 48, "full_attention_interval": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "linear_chunk_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000, "num_experts": 4, "num_experts_published": 16,
    "held_experts_start": 0, "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "rms_norm_eps": 1e-6, "compute_dtype": "bfloat16",
    "score_correction": "centred",
    "train": {"recipe": "qwen3_next_ep16", "scale": "smoke", "batch": 2, "seq": 32, "h": 2,
              "learning_rate": 3e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8, "gossip": "exact",
              "gmm_row_tile": 256},
}
SOLO = {
    "kind": "train_rounds", "workers": 1, "backend": "simulated",
    # at hidden 32 bfloat16 is loud: the program reads moment_diff 0.21-0.30 where fp8 reads
    # 0.38-0.56; the mean gap of the change (0.004-0.005 against 0.008-0.010), the routing
    # (0.004-0.005 against 0.022-0.027) and the delta rule's output (0.002-0.005 against
    # 0.015-0.031) part them
    "check": {"loss_gap_round1": 0.01, "loss_gap_round3": 0.02, "moment_diff": 0.42,
              "moment_norm_gap": 0.08, "change_norm_gap": 0.06, "change_norm_gap_mean": 0.0065,
              "routing_disagreement": 0.012, "gdn_rms_gap": 0.009},
}
CELL = "tiny_delta.solo"
LAYER = {
    "mfu.train": ("mfu", {}),
    "round_p50_ms.train": ("stat", {"key": "round_p50_ms"}),
    "moe_gmm_roofline.train": ("roofline", {"pattern": "^%?moe_gmm", "flops_key": "moe_gmm_flops", "bytes_key": "moe_gmm_bytes"}),
    "moe_load_max_over_mean.train": ("stat", {"key": "moe_load_max_over_mean"}),
    "moe_rows_per_step.train": ("stat", {"key": "moe_rows_per_step"}),
    "moe_gmm_tile_fill_pct.train": ("stat", {"key": "moe_gmm_tile_fill_pct"}),
}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_delta"))
    d = os.path.join(root, "benchmarks")
    _write(os.path.join(d, "configs", "tiny_delta.json"), TINY)
    _write(os.path.join(d, "traffic", f"{CELL}.json"), SOLO)
    _write(os.path.join(d, "peaks.json"), {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "rehearsal"}})
    for name, (reader, args) in LAYER.items():
        _write(os.path.join(d, "layer_metrics", f"{name}.json"), {"reader": reader, "args": args})
    bench = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"], "run_seconds": 3,
        "configs": [{"name": "tiny_delta", "source": "test", "file": "benchmarks/configs/tiny_delta.json",
                     "reduced": [], "why": "t"}],
        "workloads": [{"name": CELL, "config": "tiny_delta", "traffic": "solo", "chips": 1, "why": "t"}],
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


def test_the_cell_prints_the_result_line(bench_file, capsys):
    line, err = run_cell(bench_file, capsys)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    names = [c["name"] for c in line["checks"]]
    assert names == ["change_norm_gap", "change_norm_gap_mean", "gdn_rms_gap", "loss_gap_round1",
                     "loss_gap_round3", "moment_diff", "moment_norm_gap", "routing_disagreement",
                     "nonfinite_or_collapsed_rounds", "compiles_in_window"]
    assert err.strip().splitlines()[-1] == "bench: correct: True"


def test_a_traced_run_reads_the_expert_layers_counters(bench_file, capsys):
    line, _ = run_cell(bench_file, capsys, trace=1, seed=3_000_000_018)
    metrics = line["metrics"]
    assert metrics["mfu.train"]["value"] > 0 and metrics["round_p50_ms.train"]["value"] > 0
    # 2 steps x 2 rows x 32 tokens x 3 choices, about a quarter of them for the 4 of 16 experts held
    per_step = metrics["moe_rows_per_step.train"]["value"]
    assert 0.1 * 4 * 2 * 32 * 3 < per_step < 0.5 * 4 * 2 * 32 * 3
    assert metrics["moe_load_max_over_mean.train"]["value"] >= 1.0
    # four small groups share the first tile of 256 rows: four (group, tile) pairs a layer and step
    fill = metrics["moe_gmm_tile_fill_pct.train"]["value"]
    assert fill == pytest.approx(100 * (per_step / 4) / (4 * 256), rel=0.05)
    assert "moe_gmm_roofline.train" not in metrics  # no kernel off a TPU: left out, never 0


FAULTS = {  # each fault and a number that it fails by itself
    "half_batch": "moment_diff", "top9": "routing_disagreement", "renorm_over_held": "moment_diff",
    "no_state_carry": "gdn_rms_gap", "no_delta": "gdn_rms_gap", "no_attn_gate": "moment_diff",
}


@pytest.fixture(scope="module")
def sides(bench_file):
    cell = harness.load_cell(bench_file, CELL)
    mod = harness._load_module("drivers", "train_qwen3_next", cell["bench_dirs"])
    driver = mod.Driver(dict(cell, seed=11, device={}, peaks=None))
    try:
        driver.setup(1.0)
        driver.window(0.2, 0.0)
        driver.release()
        read = driver.readings(("fp8",), faults=tuple(FAULTS))
    finally:
        driver.close()
    failed = {side: [c["name"] for c in driver.judge(numbers) if not c["ok"]] for side, numbers in read.items()}
    return read, failed


def test_the_program_reads_correct_and_fp8_in_its_place_does_not(sides):
    read, failed = sides
    assert failed["program"] == []
    assert {"routing_disagreement", "gdn_rms_gap", "change_norm_gap_mean"} <= set(failed["fp8"])
    assert read["fp8"]["change_norm_gap_mean"] > 1.7 * read["program"]["change_norm_gap_mean"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_programs_place_fails_a_number_by_itself(sides, fault):
    read, failed = sides
    assert FAULTS[fault] in failed[fault], (fault, read[fault])
    if fault == "top9":
        assert read[fault]["routing_disagreement"] >= 1 / 3
    if fault in ("no_state_carry", "no_delta"):  # the heads that remember longest lose whole tenths
        assert read[fault]["gdn_rms_gap"] > 0.1


def test_a_fault_in_the_timed_round_fails_the_numbers_read_from_it(bench_file, monkeypatch):
    """``routing_disagreement`` and ``gdn_rms_gap`` come out of the compiled round that the
    window drives (its metrics, ``LossAux.first_step``), not out of a second program: a
    delta rule that the ROUND traces with its state lost at every chunk boundary is seen."""
    from consensusml_tpu.models import gated_delta

    monkeypatch.setattr(gated_delta, "_carried", lambda state: 0.0 * state)
    cell = harness.load_cell(bench_file, CELL)
    mod = harness._load_module("drivers", "train_qwen3_next", cell["bench_dirs"])
    driver = mod.Driver(dict(cell, seed=12, device={}, peaks=None))
    try:
        driver.setup(1.0)
        driver.window(0.2, 0.0)
        driver.release()
        read = driver.readings()["program"]
    finally:
        driver.close()
    assert read["gdn_rms_gap"] > 0.1
    assert "gdn_rms_gap" in [c["name"] for c in driver.judge(read) if not c["ok"]]


def test_a_tree_without_the_delta_rule_ends_at_once_with_exit_3(bench_file, monkeypatch):
    import builtins

    real = builtins.__import__

    def no_delta_rule(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "consensusml_tpu.models.gated_delta" or (
                name == "consensusml_tpu.models" and "gated_delta" in (fromlist or ())):
            raise ImportError("cannot import name 'gated_delta' from 'consensusml_tpu.models'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_delta_rule)
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1"], bench_file=bench_file, require_chip=False)
    assert e.value.code == 3
