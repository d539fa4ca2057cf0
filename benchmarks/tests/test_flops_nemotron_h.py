"""`flops_nemotron_h.py` against counts made by hand for the cut of Nemotron 3
Nano that the benchmark runs, and the configuration file against its contract."""

import json
import os

import flops_nemotron_h as flops
from reference import nemotron_h as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def config():
    with open(os.path.join(BENCH, "configs", "nemotron3_nano_ep16.json")) as f:
        return json.load(f)


def cut():
    return ref.sizes_of(config())


def test_blocks_of_each_kind():
    assert flops.kinds(cut()) == {"M": 4, "E": 4, "*": 1}


def test_dense_params_by_hand():
    mamba = 2688 * (4096 + 4096 + 2 * 8 * 128 + 64) + 4096 * 2688  # in_proj 2688 -> 10,304; out_proj
    assert mamba == 2688 * 10304 + 4096 * 2688 == 38_707_200
    attention = 2688 * (32 * 128 + 2 * 2 * 128) + 32 * 128 * 2688  # q, k, v; o
    assert attention == 23_396_352
    experts = 2688 * 128 + 2 * 2688 * 3712  # router; the shared expert
    assert experts == 20_299_776
    head = 2688 * 16384
    assert flops.dense_params(cut()) == 4 * mamba + attention + 4 * experts + head == 303_464_448


def test_parameter_count_of_the_cut_is_667_million():
    # ISSUE 27's arithmetic: 4 x 38.74 + 23.40 + 4 x 100.13 + 2 x 44.04 = 667.0M
    import jax

    params = jax.eval_shape(lambda s: ref.init_params(s, cut()), jax.numpy.uint32(0))
    total = sum(int(x.size) for x in jax.tree.leaves(params))
    m = 38_707_200 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 2688  # + conv, conv bias, dt_bias/A_log/D, gate norm, norm
    a = 23_396_352 + 2688
    e = 20_299_776 + 8 * 2 * 2688 * 1856 + 2688
    assert total == 4 * m + a + 4 * e + 2 * 2688 * 16384 + 2688 == 666_962_944
    assert round(total / 1e6, 1) == 667.0


def test_scan_and_routed_work_by_hand():
    sizes = cut()
    assert flops.scan_flops_per_token(sizes) == 5 * 64 * 64 * 128 == 2_621_440
    # a step routes about 8192 x 6 x 8/128 = 3072 pairs in each of 4 expert blocks
    rows = 4 * 3072
    assert flops.routed_flops(sizes, rows, backward=False) == 2 * 2 * rows * 2688 * 1856
    assert flops.routed_flops(sizes, rows) == 3 * 2 * 2 * rows * 2688 * 1856  # ISSUE 27's formula
    weights = 8 * 2688 * 1856 * 2
    assert flops.routed_bytes(sizes, rows, 4, 2) == 6 * (4 * weights + rows * (2688 + 1856) * 2)


def test_train_flops_of_one_step():
    sizes = cut()
    pairs = 8192 * 8193 // 2
    forward = (2 * 303_464_448 * 8192 + 4 * 2_621_440 * 8192 + 4 * 1 * 4096 * pairs
               + 2 * 2 * 12288 * 2688 * 1856)
    assert flops.train_flops(sizes, 1, 8192, 12288.0) == 3.0 * forward
    per_token = flops.train_flops(sizes, 1, 8192, 12288.0) / 8192
    assert 2.0e9 < per_token < 2.2e9  # 2.1 GFLOP a token: a dense 350M-parameter model's


def test_attention_work_as_the_kernels_see_it():
    sizes = cut()
    pairs = 8192 * 8193 // 2
    assert flops.attention_flops(sizes, 1, 8192, False) == 4 * 4096 * pairs
    assert flops.attention_flops(sizes, 1, 8192, True) == 2.5 * 4 * 4096 * pairs
    tensor = 8192 * 32 * 128 * 2  # K and V repeated to the 32 query heads
    assert flops.attention_bytes(sizes, 1, 8192, 2, False) == 4 * tensor
    assert flops.attention_bytes(sizes, 1, 8192, 2, True) == 8 * tensor


# -- the configuration's contract ---------------------------------------------


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16":
                return row
    return None


PUBLISHED = {  # the source's config.json, the keys that carry a size or a rule of the layers
    "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
    "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "num_experts_per_tok": 6, "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "norm_eps": 1e-05, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 0.0001, "tie_word_embeddings": False, "rescale_prenorm_residual": True,
    "use_conv_bias": True, "model_type": "nemotron_h",
}


def test_every_published_width_is_unchanged_and_every_cut_is_stated():
    c = config()
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    row = catalog_row()
    if row is not None:  # the catalog beside the guide, where it is installed
        assert c["source"].endswith(row["source_url"])
        for key, value in row["config"].items():
            assert key in c, key
            if key not in c["reduced"]:
                assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
                            "vocab_size", "max_position_embeddings"]
    cuts = {"num_hidden_layers": (9, 52), "n_routed_experts": (8, 128), "vocab_size": (16384, 131072),
            "max_position_embeddings": (8192, 262144)}
    for key, (here, published) in cuts.items():
        assert c[key] == here and c[f"{key}_published"] == published and key in c["reduced_why"]
    assert c["hybrid_override_pattern"] == c["hybrid_override_pattern_published"][:9] == "MEMEM*EME"
    assert len(c["hybrid_override_pattern_published"]) == 52
    assert [c["hybrid_override_pattern_published"].count(k) for k in "ME*"] == [23, 23, 6]
    # the floors of a cut: a whole period, 8 routed experts, an eighth of the vocabulary
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 >= c["vocab_size_published"]
    assert "16 chips share each layer" in c["deployment"] and "rank 0" in c["deployment"]
    assert any("no rotary" in a for a in c["assumed"]) and any("e_score_correction_bias" in a for a in c["assumed"])
    assert c["train"]["warmup_steps"] == 20000 and any("warms up" in a for a in c["assumed"])
    assert any("no routed token is dropped" in g for g in c["guarantees"])
    for key in c["reduced"]:  # never a width: a depth, a pattern, a count held, rows, positions
        assert not any(part in key for part in ("_dim", "_rank", "hidden_size", "state_size", "intermediate", "head", "per_tok"))


def test_the_cell_is_declared_with_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "nemotron3_nano_ep16.solo_8k"
    entry = {w["name"]: w for w in bench["workloads"]}[cell]
    assert entry["chips"] == 1 and entry["config"] == "nemotron3_nano_ep16"
    assert "384 tokens" in entry["why"] and "1/16" in entry["why"]
    listed = {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", [])}
    assert {"moe_gmm_roofline.train", "moe_load_max_over_mean.train", "moe_rows_per_step.train", "mfu.train",
            "flash_attn_roofline.train", "peak_hbm_pct.train", "device_idle_pct.train"} <= listed
    assert "codec_roofline.train" not in listed  # exact gossip: no codec kernel runs
    with open(os.path.join(BENCH, "traffic", f"{cell}.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "train_rounds" and traffic["workers"] == 1 and traffic["backend"] == "simulated"
    # every number the dense cell compares bar the tracking state's, and the two new ones
    assert set(traffic["check"]) == {
        "moment_diff", "moment_norm_gap", "loss_gap_round1", "loss_gap_round3", "change_norm_gap",
        "change_norm_gap_mean", "routing_disagreement", "scan_rms_gap"}
