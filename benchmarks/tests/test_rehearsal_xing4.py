"""The ``xing4`` cell rehearsed on the CPU at smoke sizes, from a temporary
directory: its driver, reference, rounds, reader and metric files found by name
beside the committed harness, which is not edited. The look for a chip is the
one thing skipped (``require_chip=False``); off a TPU the expert products are
``lax.ragged_dot`` and attention is dense, so the kernels' rooflines are left out
(never 0), and the CPU's profile has no device plane, so the scope readers find
nothing and say so."""

import json
import os

import pytest

import run as harness

TINY = {
    "driver": "train_xing4", "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 2,
    "num_hidden_layers_published": 40, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 12, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 4, "beta_slow": 1, "factor": 4, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "intermediate_size": 48, "n_routed_experts": 4, "n_routed_experts_published": 8, "held_experts_start": 0,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2, "moe_intermediate_size": 16, "n_shared_experts": 1,
    "rms_norm_eps": 1e-6, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "num_nextn_predict_layers": 1,
    "compute_dtype": "bfloat16", "score_correction": "centred",
    "train": {"recipe": "xing4_ep8", "scale": "smoke", "batch": 2, "seq": 32, "h": 2,
              "learning_rate": 3e-3, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8, "gossip": "exact",
              "gmm_row_tile": 256, "mtp_lambda": 0.3},
}
SOLO = {
    "kind": "train_rounds", "workers": 1, "backend": "simulated",
    # at hidden 32 bfloat16 is loud: the program reads moment_diff 0.05-0.22 where fp8 reads
    # 0.17-0.23; the mean gap of the change (0.0018-0.0020 against 0.0058-0.0064) and the size of
    # latent attention's output (0.002-0.004 against 0.039-0.059) part them
    "check": {"loss_gap_round1": 0.01, "loss_gap_round3": 0.02, "moment_diff": 0.42,
              "moment_norm_gap": 0.08, "change_norm_gap": 0.06, "change_norm_gap_mean": 0.004,
              "routing_disagreement": 0.012, "mla_rms_gap": 0.009, "mhc_stream_rms_gap": 0.009,
              "mtp_loss_gap_round1": 0.01},
}
CELL = "tiny_latent.solo"
LAYER = {
    "mfu.train": ("mfu", {}),
    "round_p50_ms.train": ("stat", {"key": "round_p50_ms"}),
    "moe_gmm_roofline.train": ("roofline", {"pattern": "^%?moe_gmm", "flops_key": "moe_gmm_flops", "bytes_key": "moe_gmm_bytes"}),
    "moe_rows_per_step.train": ("stat", {"key": "moe_rows_per_step"}),
    "mhc_mix_ms.train": ("scope_time", {"scopes": ["mhc."]}),
    "mhc_hbm_roofline.train": ("scope_time", {"scopes": ["mhc."], "bytes_key": "mhc_min_bytes"}),
    "mla_proj_ms.train": ("scope_time", {"scopes": ["mla.q_lora", "mla.kv_lora", "mla.rope", "mla.out_proj"]}),
    "mtp_ms.train": ("scope_time", {"scopes": ["mtp."]}),
}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return make_bench(str(tmp_path_factory.mktemp("bench_latent")))


def make_bench(root):
    d = os.path.join(root, "benchmarks")
    _write(os.path.join(d, "configs", "tiny_latent.json"), TINY)
    _write(os.path.join(d, "traffic", f"{CELL}.json"), SOLO)
    _write(os.path.join(d, "peaks.json"), {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "rehearsal"}})
    for name, (reader, args) in LAYER.items():
        _write(os.path.join(d, "layer_metrics", f"{name}.json"), {"reader": reader, "args": args})
    bench = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"], "run_seconds": 3,
        "configs": [{"name": "tiny_latent", "source": "test", "file": "benchmarks/configs/tiny_latent.json",
                     "reduced": [], "why": "t"}],
        "workloads": [{"name": CELL, "config": "tiny_latent", "traffic": "solo", "chips": 1, "why": "t"}],
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
    assert names == ["change_norm_gap", "change_norm_gap_mean", "loss_gap_round1", "loss_gap_round3",
                     "mhc_stream_rms_gap", "mla_rms_gap", "moment_diff", "moment_norm_gap",
                     "mtp_loss_gap_round1", "routing_disagreement",
                     "nonfinite_or_collapsed_rounds", "compiles_in_window"]
    assert err.strip().splitlines()[-1] == "bench: correct: True"


def test_a_traced_run_reads_the_counters_and_maps_the_scopes(bench_file, capsys):
    line, err = run_cell(bench_file, capsys, trace=1, seed=3_000_000_018)
    metrics = line["metrics"]
    assert metrics["mfu.train"]["value"] > 0 and metrics["round_p50_ms.train"]["value"] > 0
    # 2 steps x 2 rows x 32 tokens x 3 choices, about half of them for the 4 of 8 experts held,
    # in each of the two expert layers (the decoder's and the module's)
    per_step = metrics["moe_rows_per_step.train"]["value"]
    assert 0.2 * 2 * 2 * 32 * 3 < per_step < 0.8 * 2 * 2 * 32 * 3
    assert "moe_gmm_roofline.train" not in metrics  # no kernel off a TPU: left out, never 0
    # the traced run read the compiled round's text: instructions under every scope family ...
    assert "instructions under the program's scopes" in err
    # ... but the CPU's capture has no device plane to time them on: left out, never 0
    assert not {"mhc_mix_ms.train", "mhc_hbm_roofline.train", "mla_proj_ms.train", "mtp_ms.train"} & set(metrics)


def test_the_compiled_rounds_text_names_every_scope_family(bench_file):
    cell = harness.load_cell(bench_file, CELL)
    mod = harness._load_module("drivers", "train_xing4", cell["bench_dirs"])
    driver = mod.Driver(dict(cell, seed=5, device={}, peaks=None))
    try:
        driver.setup(1.0)
        driver.traced = True
        driver.release()
    finally:
        driver.close()
    scopes = set(driver.stats["op_scopes"].values())
    for family in ("mhc.maps", "mhc.sinkhorn", "mhc.pre", "mhc.post", "mla.q_lora", "mla.kv_lora",
                   "mla.rope", "mla.out_proj", "mlp.dense", "mtp.embed_proj", "mtp.block", "mtp.loss"):
        assert any(family in s for s in scopes), family
    assert all(any(f in s for f in mod.SCOPED) for s in scopes)


FAULTS = {  # each fault and a number that it fails by itself
    "half_batch": "moment_diff", "top3": "routing_disagreement", "renorm_over_held": "moment_diff",
    "no_mtp": "loss_gap_round1", "sinkhorn_1": "mhc_stream_rms_gap", "one_stream": "mhc_stream_rms_gap",
    "no_rope_key": "moment_diff", "no_yarn_scale": "moment_norm_gap",
}


@pytest.fixture(scope="module")
def sides(bench_file):
    cell = harness.load_cell(bench_file, CELL)
    mod = harness._load_module("drivers", "train_xing4", cell["bench_dirs"])
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
    assert {"mla_rms_gap", "change_norm_gap_mean"} <= set(failed["fp8"])
    assert read["fp8"]["mla_rms_gap"] > 5 * read["program"]["mla_rms_gap"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_programs_place_fails_a_number_by_itself(sides, fault):
    read, failed = sides
    assert FAULTS[fault] in failed[fault], (fault, read[fault])
    if fault == "top3":
        assert read[fault]["routing_disagreement"] >= 1 / 3
    if fault == "sinkhorn_1":  # one iteration leaves the rows' sums tenths off 1: a stream's size shows it
        assert read[fault]["mhc_stream_rms_gap"] > 0.1
    if fault == "no_mtp":  # the weighted sum loses 0.3 x a loss of about ln 64
        assert read[fault]["loss_gap_round1"] > 1.0


def test_a_fault_in_the_timed_round_fails_the_numbers_read_from_it(bench_file, monkeypatch):
    """``mhc_stream_rms_gap`` comes out of the compiled round that the window drives (its
    metrics, ``LossAux.first_step``), not out of a second program: a round that traces ONE
    Sinkhorn iteration where the configuration states twenty is seen."""
    from consensusml_tpu.models import hyper_connections

    real = hyper_connections.sinkhorn
    monkeypatch.setattr(hyper_connections, "sinkhorn", lambda m, iters, eps: real(m, 1, eps))
    cell = harness.load_cell(bench_file, CELL)
    mod = harness._load_module("drivers", "train_xing4", cell["bench_dirs"])
    driver = mod.Driver(dict(cell, seed=12, device={}, peaks=None))
    try:
        driver.setup(1.0)
        driver.window(0.2, 0.0)
        driver.release()
        read = driver.readings()["program"]
    finally:
        driver.close()
    assert read["mhc_stream_rms_gap"] > 0.1
    assert "mhc_stream_rms_gap" in [c["name"] for c in driver.judge(read) if not c["ok"]]


def test_a_tree_without_latent_attention_ends_at_once_with_exit_3(bench_file, monkeypatch):
    import builtins

    real = builtins.__import__

    def no_latent_attention(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "consensusml_tpu.models.mla" or (
                name == "consensusml_tpu.models" and "mla" in (fromlist or ())):
            raise ImportError("cannot import name 'mla' from 'consensusml_tpu.models'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_latent_attention)
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1"], bench_file=bench_file, require_chip=False)
    assert e.value.code == 3


def test_scope_time_on_the_recorded_trace():
    """The reader on ``recorded_trace.json`` with a hand-made map of its instructions to
    scopes: the union of the matching leaf events per round, and the roofline share."""
    import importlib.util

    import xtrace

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "recorded_trace.json")) as f:
        trace = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "scope_time", os.path.join(os.path.dirname(here), "readers", "scope_time.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    events = trace["planes"]["/device:TPU:0"]["XLA Ops"]
    names = ["fusion.2337", "fusion.2338", "convert_element_type.404"]
    scopes = {"fusion.2337": "jit(f)/h_0/hc/mhc.maps/dot_general", "fusion.2338": "jit(f)/h_0/mhc.post/add",
              "convert_element_type.404": "jit(f)/h_0/mixer/mla.rope/convert"}
    want = {n: sum(d for name, _, d in events if name == n) / 1e9 for n in names}
    assert all(v > 0 for v in want.values())
    ctx = {"trace": trace, "trace_mod": xtrace, "stats": {"op_scopes": scopes, "rounds": 4, "moved": 1e6},
           "peaks": {"hbm_bytes_per_s": 1e9}}
    mhc = reader.read(ctx, scopes=["mhc."])
    assert mhc == pytest.approx(1e3 * (want["fusion.2337"] + want["fusion.2338"]) / 4, rel=1e-6)
    assert reader.read(ctx, scopes=["mla.rope"]) == pytest.approx(1e3 * want["convert_element_type.404"] / 4, rel=1e-6)
    share = reader.read(ctx, scopes=["mhc."], bytes_key="moved")
    assert share == pytest.approx(100 * (1e6 / 1e9) / (want["fusion.2337"] + want["fusion.2338"]), rel=1e-6)
    # nothing to read: no map (the parent), no matching scope, no bytes
    assert reader.read(dict(ctx, stats={"rounds": 4}), scopes=["mhc."]) is None
    assert reader.read(ctx, scopes=["gdn."]) is None
    assert reader.read(ctx, scopes=["mhc."], bytes_key="absent") is None
