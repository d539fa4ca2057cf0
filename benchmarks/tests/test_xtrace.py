"""The trace reducer on a small recorded trace and on hand-made intervals."""

import json
import os

import numpy as np
import pytest

import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def brute_union_ns(intervals, span):
    on = np.zeros(int(span) + 1, bool)
    for start, end in intervals:
        on[int(start) : int(end)] = True
    return int(on.sum())


def test_busy_is_the_union_not_the_sum_on_the_recorded_trace():
    trace = recorded()
    ops = trace["planes"]["/device:TPU:0"]["XLA Ops"]
    span = max(s + d for _, s, d in ops)
    brute = brute_union_ns([(s, s + d) for _, s, d in ops], span)
    assert abs(xtrace.busy_seconds(trace) * 1e9 - brute) <= len(ops)  # ns rounding
    assert xtrace.busy_seconds(trace) * 1e9 <= sum(d for _, _, d in ops)
    assert xtrace.device_planes(trace) == ["/device:TPU:0"]


def test_idle_share_and_kernel_time_by_hand():
    trace = {"planes": {"/device:TPU:0": {
        "XLA Ops": [["fusion.1", 0, 40], ["copy.2", 30, 30], ["custom-call.3 _topk_kernel", 100, 50],
                    ["custom-call.4 _topk_kernel", 120, 50], ["fusion.5", 300, 100]],
        "XLA Modules": [["jit_a(1)", 0, 170], ["jit_b(2)", 290, 120]],
    }}}
    # busy: [0,60) + [100,170) + [300,400) = 230 ns
    assert xtrace.busy_seconds(trace) == pytest.approx(230e-9)
    seconds, calls = xtrace.matching_seconds(trace, "_topk_kernel")
    assert (seconds, calls) == (pytest.approx(70e-9), 2)  # overlapping pieces are not counted twice
    seconds, calls = xtrace.matching_seconds(trace, "^jit_b", xtrace.MODULES_LINE)
    assert (seconds, calls) == (pytest.approx(120e-9), 1)
    assert xtrace.matching_seconds(trace, "no_such_kernel") == (0.0, 0)
    gaps = dict(xtrace.idle_gaps(trace))
    assert gaps == {"before:jit_b": pytest.approx(130e-9), "before:jit_a": pytest.approx(40e-9)}
    (name, seconds), = xtrace.top_ops(trace, 1)  # fusion.1 + fusion.5, run numbers folded
    assert name == "fusion" and seconds == pytest.approx(140e-9)


def test_exposed_collective_time_counts_only_what_compute_does_not_cover():
    trace = {"planes": {
        "/device:TPU:0": {"XLA Ops": [["collective-permute.1", 0, 100], ["fusion.1", 20, 30], ["fusion.2", 90, 50]]},
        "/device:TPU:1": {"XLA Ops": [["collective-permute.1", 0, 100], ["fusion.1", 0, 100]]},
        "/device:TPU:2": {"XLA Ops": [["fusion.9", 0, 10]]},  # no collective here: not averaged in
    }}
    # chip 0: 100 - 30 - 10 = 60 exposed; chip 1: fully hidden
    assert xtrace.exposed_seconds(trace, "collective-permute") == pytest.approx(30e-9)
    busy = xtrace.busy_seconds(trace)
    assert busy == pytest.approx((140e-9 + 100e-9 + 10e-9) / 3)


# instruction texts as the profiler gave them on the chip (TPU v5 lite, PR 24), cut short
CHIP_NAMES = {
    "flash": "%h_0.21 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[128,1024,128]{2,1,0:T(8,128)}) custom-call(s32[1,1]{1,0:T(1,128)} %get-tuple-element.33320, f32[1,1,128]{2,1,0} %broadcast.71113)",
    "topk": "%vmap_jit_chunked_topk__.26 = (f32[24832,128]{1,0:T(8,128)}, s32[24832,128]{1,0:T(8,128)}) custom-call(f32[24832,512]{1,0:T(8,128)} %pad_bitcast_fusion.25), custom_call_target=\"tpu_custom_call\"",
    "scatter": "%vmap_jit_chunk_scatter__.25 = f32[24832,512]{1,0:T(8,128)} custom-call(f32[24832,128]{1,0:T(8,128)S(1)} %pad_bitcast_fusion.50, s32[24832,128]{1,0} %convert_bitcast_fusion.122)",
    # not kernels: a fusion that reads a custom call's result, and an unnamed custom call
    "head": "%convolution_bitcast_fusion.2.remat = bf16[1,8,1024,50257]{2,3,1,0} fusion(bf16[1,50257,1024]{2,1,0} %convert_element_type.31457, bf16[8,1024,1024]{2,1,0} %custom-call.426)",
    "reader": "%subtract_reduce_fusion.24 = f32[12596224]{0:T(1024)} fusion(f32[1,12596224]{1,0} %constant_dynamic-update-slice_fusion.17, f32[1,12596224]{1,0} %custom-call.515)",
}


@pytest.mark.parametrize("metric, hits", [
    ("flash_attn_roofline.train", {"flash"}),
    ("codec_roofline.train", {"topk", "scatter"}),
])
def test_the_committed_kernel_patterns_find_the_kernels_as_the_chip_names_them(metric, hits):
    with open(os.path.join(os.path.dirname(HERE), "layer_metrics", f"{metric}.json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    trace = {"planes": {"/device:TPU:0": {"XLA Ops": [
        [text, 100 * i, 50] for i, text in enumerate(CHIP_NAMES.values())
    ]}}}
    seconds, calls = xtrace.matching_seconds(trace, pattern)
    assert calls == len(hits) and seconds == pytest.approx(50e-9 * len(hits))
    found = {k for k, text in CHIP_NAMES.items() if xtrace.re.search(pattern, text)}
    assert found == hits
