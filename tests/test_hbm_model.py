"""HBM accounting model (tools/hbm_model.py).

The state components are EXACT claims (eval_shape bytes), so they are
pinned against actually-initialized state. The activation term is a
model; its on-chip validation against measured device peak lives in the
slow TPU tier (runs only where a real accelerator is attached).
"""

import math
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import hbm_model  # noqa: E402

from consensusml_tpu.configs import build  # noqa: E402
from consensusml_tpu.train import init_stacked_state  # noqa: E402


def _leaf_bytes(tree) -> int:
    return sum(
        math.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree)
    )


@pytest.mark.parametrize(
    "name",
    [
        "mnist_mlp",
        # the larger smoke states take ~20 s each to initialize: slow tier
        pytest.param("gpt2_topk", marks=pytest.mark.slow),
        pytest.param("cifar_resnet50", marks=pytest.mark.slow),
    ],
)
def test_state_components_match_real_state(name):
    """predict()'s params/opt/gossip bytes equal the bytes of the state a
    run actually allocates (per worker)."""
    pred = hbm_model.predict(name, "smoke")["per_device"]
    bundle = build(name, "smoke")
    state = init_stacked_state(
        bundle.cfg, bundle.init_params, jax.random.key(0), 1
    )
    assert pred["params"] == _leaf_bytes(state.params)
    assert pred["model_state"] == _leaf_bytes(state.model_state)
    assert pred["opt"] == _leaf_bytes(state.opt_state)
    assert pred["gossip"] == _leaf_bytes(state.gossip)


def test_tp_division_shards_matched_leaves_only():
    """With model axes, leaves a sharding rule matches shrink by the axis
    product; unmatched (replicated) leaves do not."""
    base = hbm_model.predict("llama_lora", "smoke", model_axes=())
    tp4 = hbm_model.predict("llama_lora", "smoke", model_axes=(("tp", 4),))
    p0, p4 = base["per_device"]["params"], tp4["per_device"]["params"]
    # matmul weights dominate llama params: tp=4 must cut params to
    # between 1/4 (everything sharded) and 1/2 (half the bytes sharded)
    assert p0 / 4 <= p4 < p0 / 2, (p0, p4)
    # norms/biases are replicated, so it cannot be a clean /4
    assert p4 > p0 / 4, (p0, p4)


def test_codec_terms_present_only_for_compressed_configs():
    gpt2 = hbm_model.predict("gpt2_topk", "smoke")["per_device"]
    mlp = hbm_model.predict("mnist_mlp", "smoke")["per_device"]
    assert gpt2["codec_temp"] > 0 and gpt2["payloads"] > 0
    assert mlp["codec_temp"] == 0 and mlp["payloads"] == 0
    # CHOCO keeps xhat+s per wire bucket: exactly 2x the f32 compress
    # domain with leaf sizes rounded up to the codec chunk (the bucketed
    # state layout — docs/gossip_bucketing.md)
    bundle = build("gpt2_topk", "smoke")
    probe = jax.eval_shape(bundle.init_params, jax.random.key(0))
    plan = bundle.cfg.engine().bucket_plan({"params": probe, "model_state": {}})
    n_params = gpt2["params"]  # f32 leaves
    assert gpt2["gossip"] == 2 * 4 * plan.total_elems >= 2 * n_params


@pytest.mark.slow  # builds all five FULL bundles (llama-7B eval_shape)
def test_full_scale_predictions_fit_claimed_hardware():
    """The doc's pod-fit claims, as assertions: every full-scale config's
    per-device prediction fits a v4 chip's 32 GiB HBM; the single-chip
    workloads fit a v5e's 16 GiB."""
    v4, v5e = 32 * hbm_model.GIB, 16 * hbm_model.GIB
    for name in ("mnist_mlp", "cifar_resnet50", "bert_mlm", "gpt2_topk",
                 "llama_lora"):
        peak = hbm_model.predict(name, "full")["predicted_peak_bytes"]
        assert peak < v4, f"{name}: {peak / hbm_model.GIB:.1f} GiB > v4 HBM"
    for name in ("mnist_mlp", "cifar_resnet50", "bert_mlm"):
        peak = hbm_model.predict(name, "full")["predicted_peak_bytes"]
        assert peak < v5e, f"{name}: {peak / hbm_model.GIB:.1f} GiB > v5e HBM"


@pytest.mark.slow
def test_predicted_vs_measured_on_accelerator():
    """On a real chip: predicted peak within tolerance of the device
    truth (XLA's compiled buffer assignment; runtime memory_stats where
    available) for a runnable full-scale workload, world=1 — exactly the
    per-device layout predict() models."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a real accelerator backend")
    pred = hbm_model.predict("cifar_resnet50", "full", world=1)
    got = hbm_model.measure("cifar_resnet50", "full")
    peak = got.get("measured_peak_bytes") or got["compiled_peak_bytes"]
    ratio = pred["predicted_peak_bytes"] / peak
    # measured on this chip: 1.05 (cifar_resnet50) and 1.03 (gpt2_topk)
    assert 0.85 <= ratio <= 1.15, (pred, got)
