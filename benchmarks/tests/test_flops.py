"""`flops.py` against counts made by hand for GPT-2-medium."""

import json
import os

import flops
from reference import gpt2

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def medium():
    with open(os.path.join(BENCH, "configs", "gpt2m_choco.json")) as f:
        return gpt2.sizes_of(json.load(f))


def test_matmul_params_of_gpt2_medium():
    # a block: qkv 3*1024^2 + out 1024^2 + mlp 2*1024*4096 = 12 * 1024^2
    assert flops.matmul_params(medium()) == 24 * 12 * 1024**2 + 50257 * 1024


def test_train_flops_per_token_is_about_2_3_gflop():
    per = flops.train_flops_per_token(medium(), 1024)
    dense = 2 * (24 * 12 * 1024**2 + 50257 * 1024)
    attention = 4 * 24 * 1024 * (1024 * 1025 // 2) / 1024
    assert per == 3 * (dense + attention)
    assert 2.2e9 < per < 2.35e9


def test_forward_flops_of_one_short_row():
    # 64 tokens, every one through the head, 2080 causal pairs at 4*h operations a pair per layer
    got = flops.forward_flops(medium(), 64, flops.causal_pairs(64))
    assert got == 2 * (24 * 12 * 1024**2 + 50257 * 1024) * 64 + 4 * 24 * 1024 * 2080


def test_attention_kernel_work():
    s = medium()
    fwd = flops.attention_flops(s, 8, 1024, backward=False)
    assert fwd == 4 * 1024 * 8 * (1024 * 1025 // 2)
    assert flops.attention_flops(s, 8, 1024, backward=True) == 2.5 * fwd
    assert flops.attention_bytes(s, 8, 1024, 2, backward=False) == 4 * 8 * 1024 * 1024 * 2


def test_codec_bytes_by_hand():
    # 512 values, one chunk, 8 kept: encode reads 2048 B, writes 8*(1+4)+4;
    # decode reads the same 44 B and read-modify-writes the tracked copy
    assert flops.codec_bytes(512, 512, 8) == (2048 + 44) + (44 + 2 * 2048)
