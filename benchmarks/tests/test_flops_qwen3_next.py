"""`flops_qwen3_next.py` against counts made by hand for the cut of Qwen3-Next
that the benchmark runs, and the configuration file against its contract."""

import json
import os

import flops_qwen3_next as flops
from reference import qwen3_next as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "qwen3_next_ep16.solo_8k"


def config():
    with open(os.path.join(BENCH, "configs", "qwen3_next_ep16.json")) as f:
        return json.load(f)


def cut():
    return ref.sizes_of(config())


def test_sub_blocks_of_each_kind_and_the_programs_pattern():
    assert flops.kinds(cut()) == {"G": 3, "A": 1, "E": 4}
    assert ref.pattern_of(cut()) == "GEGEGEAE"


def test_dense_params_by_hand():
    delta = 2048 * (12288 + 64) + 4096 * 2048  # q, k, v, z; b, a; out
    assert delta == 33_685_504
    attention = 2048 * (2 * 4096 + 2 * 512) + 4096 * 2048  # q and its gate, k, v; o
    assert attention == 27_262_976
    experts = 2048 * 512 + 3 * 2048 * 512 + 2048  # router; the shared expert; its gate
    assert experts == 4_196_352
    head = 2048 * 18992
    assert flops.dense_params(cut()) == 3 * delta + attention + 4 * experts + head == 184_000_512


def test_parameter_count_of_the_cut_is_the_issues():
    # ISSUE 31's arithmetic: 3 x 138,582,208 + 132,127,232 + 77,791,232 + 2,048 = 625,667,136
    import jax

    params = jax.eval_shape(lambda s: ref.init_params(s, cut()), jax.numpy.uint32(0))
    total = sum(int(x.size) for x in jax.tree.leaves(params))
    g = 33_685_504 + 8192 * 4 + 32 + 32 + 128  # + convolution, dt_bias, A_log, norm
    a = 27_262_976 + 2 * 256  # + q and k norms
    e = 4_196_352 + 32 * 3 * 2048 * 512
    assert (g, a, e) == (33_718_464, 27_263_488, 104_859_648)
    assert total == 3 * (g + e + 2 * 2048) + (a + e + 2 * 2048) + 2 * 2048 * 18992 + 2048 == 625_667_136


def test_delta_rule_and_routed_work_by_hand():
    sizes = cut()
    assert flops.gdn_scan_flops(sizes, 1) == 7 * 32 * 128 * 128 + 2 * 4 * 8192 == 3_735_552
    assert flops.gdn_scan_bytes(sizes, 8192, 2) == 8192 * ((2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4)
    # a step routes about 8192 x 10 x 32/512 = 5120 pairs in each of 4 expert layers
    rows = 4 * 5120
    assert flops.routed_flops(sizes, rows, backward=False) == 3 * 2 * rows * 2048 * 512
    assert flops.routed_flops(sizes, rows) == 3 * 2 * 3 * rows * 2048 * 512  # ISSUE 31's formula
    weights = 32 * 2048 * 512 * 2
    assert flops.routed_bytes(sizes, rows, 4, 2) == 9 * (4 * weights + rows * (2048 + 512) * 2)


def test_tile_pairs_of_the_grouped_product_by_hand():
    # 160 rows a group at tiles of 256: group 0 lies in tile 0, group 1 (rows 160-319) in tiles 0 and 1, ...
    assert flops.gmm_tile_pairs([160, 160], 256) == 3
    assert flops.gmm_tile_pairs([256, 256], 256) == 2
    assert flops.gmm_tile_pairs([0, 1, 0, 600], 256) == 1 + 3  # rows 1-600 meet tiles 0, 1, 2
    assert flops.gmm_tile_pairs([], 256) == flops.gmm_tile_pairs([0, 0], 256) == 0
    even = flops.gmm_tile_pairs([160] * 32, 256)
    # 8 groups fill 5 tiles exactly; 4 of the 8 straddle a tile's edge
    assert even == 4 * (8 + 4) and round(100 * 5120 / (even * 256), 1) == 41.7  # ISSUE 31: about 40%


def test_train_flops_of_one_step():
    sizes = cut()
    pairs = 8192 * 8193 // 2
    forward = (2 * 184_000_512 * 8192 + 3 * 3_735_552 * 8192 + 4 * 1 * 4096 * pairs
               + 3 * 2 * 20480 * 2048 * 512)
    assert flops.train_flops(sizes, 1, 8192, 20480.0) == 3.0 * forward
    per_token = flops.train_flops(sizes, 1, 8192, 20480.0) / 8192
    assert 1.3e9 < per_token < 1.5e9  # ISSUE 31: about 1.42 GFLOP a token forward + backward


def test_attention_work_as_the_kernels_see_it():
    sizes = cut()
    pairs = 8192 * 8193 // 2
    assert flops.attention_flops(sizes, 1, 8192, False) == 4 * 4096 * pairs
    assert flops.attention_flops(sizes, 1, 8192, True) == 2.5 * 4 * 4096 * pairs
    tensor = 8192 * 16 * 256 * 2  # K and V repeated to the 16 query heads
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
            if row["name"] == "Qwen3-Next-80B-A3B-Instruct":
                return row
    return None


PUBLISHED = {  # the source's config.json, the keys that carry a size or a rule of the layers
    "hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "num_experts_per_tok": 10, "norm_topk_prob": True, "full_attention_interval": 4,
    "decoder_sparse_step": 1, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "model_type": "qwen3_next",
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
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"]
    cuts = {"num_hidden_layers": (4, 48), "num_experts": (32, 512), "vocab_size": (18992, 151936),
            "max_position_embeddings": (8192, 262144)}
    for key, (here, published) in cuts.items():
        assert c[key] == here and c[f"{key}_published"] == published and key in c["reduced_why"]
    # the floors of a cut: a whole period, 8 routed experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] % c["full_attention_interval"] == 0
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= c["vocab_size_published"]
    assert ref.sizes_of(c)["experts"] == 512 and ref.sizes_of(c)["rotary_dim"] == 64
    assert "16 chips share each layer" in c["deployment"] and "rank 0" in c["deployment"]
    assert any("multi-token-prediction" in a for a in c["assumed"])
    assert c["score_correction"] == "centred" and any("score_correction" in a for a in c["assumed"])
    assert c["train"]["warmup_steps"] == 20000 and any("warms up" in a for a in c["assumed"])
    with open(os.path.join(BENCH, "configs", "nemotron3_nano_ep16.json")) as f:
        assert c["guarantees"] == json.load(f)["guarantees"]  # the hybrid configuration's three, word for word
    for key in c["reduced"]:  # never a width: a depth, a count held, rows, positions
        assert not any(part in key for part in ("_dim", "_rank", "hidden_size", "state_size", "intermediate", "head", "per_tok"))


def test_the_cell_is_declared_with_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert entry["chips"] == 1 and entry["config"] == "qwen3_next_ep16" and bench["workloads"][-1] is entry
    assert "160 rows" in entry["why"] and "1/16" in entry["why"] and "above their share" in entry["why"]
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    have_lists = {m["name"] for m in bench["per_layer"] if "workloads" in m}
    assert listed == have_lists - {"codec_roofline.train"}  # exact gossip: no codec kernel runs
    assert {"moe_gmm_tile_fill_pct.train", "moe_gmm_roofline.train", "flash_attn_roofline.train",
            "mfu.train", "peak_hbm_pct.train"} <= listed
    new = bench["per_layer"][-1]
    assert new["name"] == "moe_gmm_tile_fill_pct.train" and new["workloads"] == [CELL]
    assert new["layer"] == "expert layer" and new["moves"] == "train_tokens_per_s"
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}["train_tokens_per_s"]["workloads"]
    with open(os.path.join(BENCH, "layer_metrics", "moe_gmm_tile_fill_pct.train.json")) as f:
        assert json.load(f)["reader"] == "stat"  # a data file on a reader that is there
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "train_rounds" and traffic["workers"] == 1 and traffic["backend"] == "simulated"
    compared = {"moment_diff", "moment_norm_gap", "loss_gap_round1", "loss_gap_round3", "change_norm_gap",
                "change_norm_gap_mean", "routing_disagreement", "gdn_rms_gap"}
    # a number no control fails has no limit: it is listed under not_compared
    assert set(traffic["check"]) | set(traffic["not_compared"]) >= compared
    assert "gdn_rms_gap" in traffic["check"] and "routing_disagreement" in traffic["check"]


def test_the_row_tile_is_the_programs():
    from consensusml_tpu.models import moe

    assert config()["train"]["gmm_row_tile"] == moe._GMM_ROWS
    rows = [160, 0, 300, 7]
    assert flops.gmm_tile_pairs(rows, moe._GMM_ROWS) == moe.gmm_visited_tiles(rows)
