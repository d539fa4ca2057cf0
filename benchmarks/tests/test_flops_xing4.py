"""`flops_xing4.py` against counts made by hand for the cut of Xing4.0-29B-A4B
that the benchmark runs, the parameter table of ISSUE 33, and the configuration
file against its contract."""

import json
import os

import flops_xing4 as flops
from reference import xing4 as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "xing4_ep8.solo_4k"


def config():
    with open(os.path.join(BENCH, "configs", "xing4_ep8.json")) as f:
        return json.load(f)


def cut():
    return ref.sizes_of(config())


def test_sub_blocks_of_each_kind_and_the_programs_pattern():
    assert flops.kinds(cut()) == {"L": 6, "D": 1, "E": 5}  # the module adds one L and one E
    assert flops.sub_blocks(cut()) == 12
    assert ref.pattern_of(cut()) == "LDLELELELE"


def test_the_issues_parameter_table():
    sizes = cut()
    latent = 3584 * 768 + 768 * 32 * 192 + 3584 * (512 + 64) + 512 * 32 * (128 + 128) + 32 * 128 * 3584 + 768 + 512
    assert flops.latent_params(sizes) == latent == 28_411_136
    assert 3 * 3584 * 9216 == 99_090_432
    assert flops.expert_params(sizes) == 3 * 3584 * 1024 == 11_010_048
    assert flops.hyper_params(sizes) == (4 * 3584) * (4 + 4 + 16) + 24 + 3 == 344_091
    dense_layer = latent + 99_090_432 + 2 * 344_091 + 2 * 3584
    assert dense_layer == 128_196_918  # "128.2M"
    # the router's 64 correction biases of the issue's table are not stored (score_correction 'centred')
    expert_layer = latent + 8 * 11_010_048 + 11_010_048 + 3584 * 64 + 2 * 344_091 + 2 * 3584
    assert expert_layer == 128_426_358 - 64  # "128.4M"
    module = expert_layer + 7168 * 3584 + 3 * 3584
    total = dense_layer + 4 * expert_layer + 2 * 16384 * 3584 + 3584 + module
    assert total == flops.total_params(sizes) == 913_473_668 - 5 * 64 == 913_473_348  # "913.5M"
    assert round(total * 12 / 1e9, 2) == 10.96  # float32 parameters and Adam's two moments, GB


def test_parameter_count_of_the_cut_is_the_references():
    import jax

    params = jax.eval_shape(lambda s: ref.init_params(s, cut()), jax.numpy.uint32(0))
    assert sum(int(x.size) for x in jax.tree.leaves(params)) == flops.total_params(cut())
    assert params["mtp"]["eh_proj"].shape == (7168, 3584)
    assert params["h_0"]["mixer"]["q_b"].shape == (768, 32 * 192)
    assert params["h_0"]["mixer"]["kv_b"].shape == (512, 32 * 256)
    assert params["h_0"]["hc"]["phi"].shape == (4, 3584, 24)
    assert params["h_3"]["mixer"]["w1"].shape == (8, 3584, 1024) and params["h_3"]["mixer"]["router"].shape == (3584, 64)


def test_dense_params_by_hand():
    latent = 28_411_136 - 768 - 512
    experts = 3584 * 64 + 3 * 3584 * 1024
    maps = 4 * 3584 * 24
    want = 6 * latent + 99_090_432 + 5 * experts + 12 * maps + 2 * 3584 * 3584 + 2 * 3584 * 16384
    assert flops.dense_params(cut()) == want == 473_006_080


def test_attention_work_as_the_kernels_see_it():
    sizes = cut()
    pairs = 4096 * 4097 // 2
    assert flops.attention_flops(sizes, 1, 4096, False) == 2 * (192 + 128) * 32 * pairs
    assert flops.attention_flops(sizes, 1, 4096, True) == 2 * (3 * 192 + 2 * 128) * 32 * pairs
    tokens = 4096 * 32 * 2
    assert flops.attention_bytes(sizes, 1, 4096, 2, False) == tokens * (2 * 192 + 2 * 128)
    assert flops.attention_bytes(sizes, 1, 4096, 2, True) == tokens * (4 * 192 + 4 * 128)
    # at equal widths the rule is the other cells': forward 4 d, backward 2.5 x that
    square = dict(sizes, nope_dim=64, rope_dim=64, v_dim=128)
    assert flops.attention_flops(square, 1, 8, True) == 2.5 * flops.attention_flops(square, 1, 8, False)


def test_routed_and_residual_work_by_hand():
    sizes = cut()
    # a step routes about 4096 x 4 x 8/64 = 2048 pairs in each of 5 expert layers
    rows = 5 * 2048
    assert flops.routed_flops(sizes, rows, backward=False) == 3 * 2 * rows * 3584 * 1024
    assert flops.routed_flops(sizes, rows) == 3 * 2 * 3 * rows * 3584 * 1024
    weights = 8 * 3584 * 1024 * 2
    assert flops.routed_bytes(sizes, rows, 5, 2) == 9 * (5 * weights + rows * (3584 + 1024) * 2)
    # 256 rows a group at tiles of 256: every group is exactly one tile
    assert flops.gmm_tile_pairs([256] * 8, 256) == 8
    assert flops.gmm_tile_pairs([255, 257], 256) == 3
    # the streams: X read and X' written forward, dX' read and dX written backward, 12 sub-blocks, bfloat16
    assert flops.residual_bytes(sizes, 4096, 2) == 4 * 12 * 4096 * 4 * 3584 * 2 == 5_637_144_576
    assert flops.mixing_flops(sizes, 1) == 2 * 3584 * (16 + 8)


def test_train_flops_of_one_step():
    sizes = cut()
    pairs = 4096 * 4097 // 2
    forward = (2 * 473_006_080 * 4096 + 2 * 320 * 6 * 32 * pairs + 12 * 2 * 4096 * 3584 * 24
               + 3 * 2 * 10240 * 3584 * 1024)
    assert flops.train_flops(sizes, 1, 4096, 10240.0) == 3.0 * forward
    per_token = forward / 4096
    assert 1.2e9 < per_token < 1.3e9  # ISSUE 33: about 1.24 GFLOP forward a token
    attention = 6 * (2 * (28_411_136 - 1280) + 2 * 320 * 32 * pairs / 4096)
    assert 0.44 < attention / per_token < 0.50  # "47% latent attention"


# -- the configuration's contract ---------------------------------------------


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Xing4.0-29B-A4B":
                return row
    return None


PUBLISHED = {  # the source's config.json, the keys that carry a size or a rule of the layers
    "hidden_size": 3584, "intermediate_size": 9216, "moe_intermediate_size": 1024, "num_attention_heads": 32,
    "num_key_value_heads": 32, "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "norm_topk_prob": True, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "num_nextn_predict_layers": 1, "rope_theta": 10000, "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "tie_word_embeddings": False, "model_type": "xing4_0",
}


def test_every_published_width_is_unchanged_and_every_cut_is_stated():
    c = config()
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    assert c["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                                 "original_max_position_embeddings": 4096, "type": "yarn"}
    row = catalog_row()
    if row is not None:  # the catalog beside the guide, where it is installed
        assert c["source"].endswith(row["source_url"])
        for key, value in row["config"].items():
            assert key in c, key
            if key not in c["reduced"]:
                assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
                            "max_position_embeddings"]
    cuts = {"num_hidden_layers": (5, 40), "first_k_dense_replace": (1, 2), "n_routed_experts": (8, 64),
            "vocab_size": (16384, 131072), "max_position_embeddings": (4096, 262144)}
    for key, (here, published) in cuts.items():
        assert c[key] == here and c[f"{key}_published"] == published and key in c["reduced_why"]
    # the floors of a cut: the leading dense layers once and four expert layers, 8 routed experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4 and c["first_k_dense_replace"] >= 1
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 >= c["vocab_size_published"]
    sizes = ref.sizes_of(c)
    assert sizes["experts"] == 64 and sizes["held"] == 8 and sizes["shared_width"] == 1024
    assert "8 chips share each layer" in c["deployment"] and "rank 0" in c["deployment"]
    for word in ("hyper-connections", "Sinkhorn", "rope_interleave", "multi-token prediction", "B_res N(0, 1) + 2 I"):
        assert any(word in a for a in c["assumed"]), word
    assert c["score_correction"] == "centred" and any("score_correction" in a for a in c["assumed"])
    assert c["train"]["warmup_steps"] == 20000 and any("warms up" in a for a in c["assumed"])
    assert c["train"]["seq"] == c["rope_scaling"]["original_max_position_embeddings"] == 4096
    assert c["train"]["mtp_lambda"] == 0.3 and c["train"]["gossip"] == "exact" and c["train"]["h"] == 2
    with open(os.path.join(BENCH, "configs", "qwen3_next_ep16.json")) as f:
        assert c["guarantees"] == json.load(f)["guarantees"]  # the other expert configurations' three, word for word
    for key in c["reduced"]:  # never a width: depths, a count held, rows, positions
        assert not any(part in key for part in ("_dim", "_rank", "hidden_size", "state_size", "intermediate", "head", "per_tok"))


def test_the_cell_is_declared_with_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert entry["chips"] == 1 and entry["config"] == "xing4_ep8" and entry["traffic"] == "solo_4k"
    assert "256 rows" in entry["why"] and "1/8" in entry["why"] and "above their share" in entry["why"]
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    new = {"mhc_mix_ms.train", "mhc_hbm_roofline.train", "mla_proj_ms.train", "mtp_ms.train"}
    assert {"flash_attn_roofline.train", "moe_gmm_roofline.train", "moe_load_max_over_mean.train",
            "moe_rows_per_step.train", "moe_gmm_tile_fill_pct.train", "mfu.train", "peak_hbm_pct.train",
            "feed_stall_ms.train", "round_p50_ms.train", "device_idle_pct.train", "trace_lower_s.train",
            "backend_compile_s.train", "feed_busy_ms.train", "feed_wait_max_ms.train"} | new == listed
    assert "codec_roofline.train" not in listed  # exact gossip: no codec kernel runs
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s" and m["source"] == "device_trace"
        with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "scope_time" and spec["layer"] == m["layer"]  # data files on ONE new reader
    assert by_name["mhc_mix_ms.train"]["layer"] == by_name["mhc_hbm_roofline.train"]["layer"] == "residual path"
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}["train_tokens_per_s"]["workloads"]
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "train_rounds" and traffic["workers"] == 1 and traffic["backend"] == "simulated"
    compared = {"moment_diff", "moment_norm_gap", "loss_gap_round1", "loss_gap_round3", "change_norm_gap",
                "change_norm_gap_mean", "routing_disagreement", "mla_rms_gap", "mhc_stream_rms_gap",
                "mtp_loss_gap_round1"}
    assert set(traffic["check"]) | set(traffic["not_compared"]) >= compared
    assert {"mla_rms_gap", "mhc_stream_rms_gap", "mtp_loss_gap_round1"} <= set(traffic["check"])


def test_the_row_tile_and_the_shipped_recipe_are_the_programs():
    from consensusml_tpu import configs
    from consensusml_tpu.models import moe
    from drivers.train_xing4 import program_sizes

    c = config()
    assert c["train"]["gmm_row_tile"] == moe._GMM_ROWS
    assert flops.gmm_tile_pairs([256, 0, 300, 7], moe._GMM_ROWS) == moe.gmm_visited_tiles([256, 0, 300, 7])
    bundle = configs.build("xing4_ep8", "full")
    assert program_sizes(bundle.model.config) == cut()  # the catalog's widths, letter for letter
    assert bundle.model.config.loss_vocab_chunk == c["train"]["loss_vocab_chunk"]
