"""Parity tests: Pallas kernels (interpreter mode) vs jnp reference math.

The Pallas interpreter executes the actual kernel logic (grid, blocks,
stores) on CPU, so these tests verify the kernels' numerics; the TPU
compile path is exercised by tests/test_kernels_tpu.py on real hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensusml_tpu.compress.kernels import (
    ChunkedTopKCompressor,
    PallasInt8Compressor,
    chunked_topk,
    dequantize_int8,
    quantize_int8,
)
from consensusml_tpu.compress.reference import Int8Compressor


@pytest.mark.parametrize("nchunks,chunk", [(4, 128), (32, 256), (33, 128), (1, 512)])
def test_quantize_kernel_matches_reference(nchunks, chunk):
    rng = np.random.default_rng(0)
    chunks = jnp.asarray(rng.normal(size=(nchunks, chunk)) * 3, jnp.float32)
    q, scales = quantize_int8(chunks, interpret=True)
    ref = Int8Compressor(chunk=chunk).compress(chunks.reshape(-1))
    np.testing.assert_array_equal(np.asarray(q).reshape(-1), np.asarray(ref.data))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(ref.scales), rtol=1e-7)


def test_quantize_kernel_zero_rows():
    chunks = jnp.zeros((8, 128), jnp.float32)
    q, scales = quantize_int8(chunks, interpret=True)
    assert np.all(np.asarray(q) == 0) and np.all(np.asarray(scales) == 0)


def test_dequantize_kernel_roundtrip():
    rng = np.random.default_rng(1)
    chunks = jnp.asarray(rng.normal(size=(16, 256)), jnp.float32)
    q, scales = quantize_int8(chunks, interpret=True)
    out = dequantize_int8(q, scales, interpret=True)
    err = np.abs(np.asarray(out) - np.asarray(chunks))
    bound = np.asarray(scales)[:, None] / 2 + 1e-7
    assert (err <= bound).all()


@pytest.mark.parametrize("nchunks,chunk,k", [(4, 128, 8), (16, 256, 32), (9, 128, 1)])
def test_chunked_topk_kernel_matches_lax(nchunks, chunk, k):
    rng = np.random.default_rng(2)
    chunks = jnp.asarray(rng.normal(size=(nchunks, chunk)), jnp.float32)
    vals, idx = chunked_topk(chunks, k, interpret=True)
    _, ref_idx = jax.lax.top_k(jnp.abs(chunks), k)
    ref_vals = jnp.take_along_axis(chunks, ref_idx, axis=1)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_vals))


def test_chunked_topk_tie_breaking():
    """Equal magnitudes resolve to the lower index, like lax.top_k."""
    row = jnp.zeros((1, 128), jnp.float32).at[0, 5].set(-3.0).at[0, 9].set(3.0)
    vals, idx = chunked_topk(row, 2, interpret=True)
    assert idx.tolist() == [[5, 9]]
    assert vals.tolist() == [[-3.0, 3.0]]


@pytest.mark.parametrize("shape", [(1000,), (37, 53), (8, 128)])
def test_pallas_int8_codec_parity(shape):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=shape) * 5, jnp.float32)
    interp = PallasInt8Compressor(chunk=256, impl="interpret")
    ref = PallasInt8Compressor(chunk=256, impl="jnp")
    pi, pr = interp.compress(x), ref.compress(x)
    np.testing.assert_array_equal(np.asarray(pi.data), np.asarray(pr.data))
    np.testing.assert_allclose(np.asarray(pi.scales), np.asarray(pr.scales), rtol=1e-7)
    np.testing.assert_allclose(
        np.asarray(interp.decompress(pi)), np.asarray(ref.decompress(pr)), rtol=1e-6
    )


@pytest.mark.parametrize("shape", [(1000,), (37, 53), (4, 512)])
def test_chunked_topk_codec_parity(shape):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    interp = ChunkedTopKCompressor(chunk=128, k_per_chunk=8, impl="interpret")
    ref = ChunkedTopKCompressor(chunk=128, k_per_chunk=8, impl="jnp")
    pi, pr = interp.compress(x), ref.compress(x)
    np.testing.assert_array_equal(np.asarray(pi.indices), np.asarray(pr.indices))
    np.testing.assert_allclose(np.asarray(pi.values), np.asarray(pr.values))
    out = interp.decompress(pi)
    assert out.shape == shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.decompress(pr)))


def test_chunked_topk_padding_tail_is_safe():
    """Padded tail beyond n must contribute nothing after decompress."""
    x = jnp.ones((100,), jnp.float32)  # pads to 128 with zeros
    codec = ChunkedTopKCompressor(chunk=128, k_per_chunk=128, impl="interpret")
    out = codec.decompress(codec.compress(x))
    np.testing.assert_allclose(np.asarray(out), np.ones(100))


def test_codec_in_choco_engine():
    """Pallas codecs drop into the consensus engine (simulated backend)."""
    from consensusml_tpu.comm import simulated
    from consensusml_tpu.consensus import ConsensusEngine, GossipConfig
    from consensusml_tpu.topology import RingTopology

    topo = RingTopology(4)
    engine = ConsensusEngine(
        GossipConfig(
            topology=topo,
            compressor=ChunkedTopKCompressor(chunk=128, k_per_chunk=32, impl="jnp"),
            gamma=0.5,
        )
    )
    rng = np.random.default_rng(5)
    x = {"w": jnp.asarray(rng.normal(size=(4, 16, 16)), jnp.float32)}
    err0 = float(engine.consensus_error_simulated(x))
    # stacked params: bucketed/fused CHOCO buffers need the worker count
    state = engine.init_state(x, world_size=4)
    w = simulated.mixing_matrix(topo)
    for _ in range(40):
        x, state = engine.round_simulated(x, state, w)
    assert float(engine.consensus_error_simulated(x)) < 0.2 * err0


def test_invalid_chunk_rejected():
    with pytest.raises(ValueError, match="multiple of 128"):
        PallasInt8Compressor(chunk=100)
    with pytest.raises(ValueError, match="k_per_chunk"):
        ChunkedTopKCompressor(chunk=128, k_per_chunk=0)


def test_chunked_topk_large_k_falls_back_to_sort():
    """k past the kernel's O(k)-pass sweet spot routes to lax.top_k while
    keeping identical chunked payload semantics."""
    from consensusml_tpu.compress import ChunkedTopKCompressor

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(4, 512)), jnp.float32)
    big = ChunkedTopKCompressor(chunk=256, k_per_chunk=128, impl="pallas")
    ref = ChunkedTopKCompressor(chunk=256, k_per_chunk=128, impl="jnp")
    p_big, p_ref = big.compress(x), ref.compress(x)
    np.testing.assert_array_equal(np.asarray(p_big.indices), np.asarray(p_ref.indices))
    np.testing.assert_allclose(np.asarray(p_big.values), np.asarray(p_ref.values))


@pytest.mark.parametrize("nchunks,chunk,k", [(4, 128, 8), (7, 256, 3), (1, 128, 1)])
def test_chunk_scatter_kernel_matches_dense(nchunks, chunk, k):
    """chunk_scatter (the structured scatter that replaces XLA's generic
    .at[].add on the CHOCO receive path) against the obvious dense math."""
    from consensusml_tpu.compress.kernels import chunk_scatter

    rng = np.random.default_rng(10)
    vals = jnp.asarray(rng.normal(size=(nchunks, k)), jnp.float32)
    # distinct in-chunk positions per row, like top-k emits
    idx = jnp.asarray(
        np.stack([
            rng.choice(chunk, size=k, replace=False) for _ in range(nchunks)
        ]),
        jnp.int32,
    )
    want = np.zeros((nchunks, chunk), np.float32)
    for r in range(nchunks):
        for j in range(k):
            want[r, int(idx[r, j])] += 0.3 * float(vals[r, j])
    acc = jnp.asarray(rng.normal(size=(nchunks, chunk)), jnp.float32)
    got = chunk_scatter(vals, idx, chunk, acc, weight=0.3, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(acc) + want, rtol=1e-6, atol=1e-6
    )
    got0 = chunk_scatter(vals, idx, chunk, weight=0.3, interpret=True)
    np.testing.assert_allclose(np.asarray(got0), want, rtol=1e-6, atol=1e-6)


def test_kernel_scatter_payload_parity_with_fallback():
    """ChunkedTopKCompressor's kernel scatter path == the generic
    .at[].add fallback, including a non-chunk-aligned (padded tail) n."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(3, 70)), jnp.float32)  # 210 % 128 != 0
    interp = ChunkedTopKCompressor(chunk=128, k_per_chunk=8, impl="interpret")
    ref = ChunkedTopKCompressor(chunk=128, k_per_chunk=8, impl="jnp")
    p = interp.compress(x)
    assert interp._kernel_scatter(p, None, 1.0) is not None  # kernel engaged
    np.testing.assert_allclose(
        np.asarray(interp.decompress(p)),
        np.asarray(ref.decompress(ref.compress(x))),
        rtol=1e-6, atol=1e-6,
    )
    acc = jnp.asarray(rng.normal(size=(3, 70)), jnp.float32)
    got = interp.decompress_accumulate(p, acc, 0.25)
    want = ref.decompress_accumulate(ref.compress(x), acc, 0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_block_rows_vmem_budget():
    """Wide chunks must shrink the row block so no VMEM buffer exceeds
    the budget (ADVICE r3: a hard 256-row block at chunk=65536 is a
    64 MiB buffer that can never fit)."""
    from consensusml_tpu.compress.kernels import (
        _BLOCK_ELEM_BUDGET,
        _SUBLANE_F32,
        _SUBLANE_I8,
        _block_rows,
    )

    # shipped sizes keep the measured 256-row blocking
    assert _block_rows(100000, 512, _SUBLANE_F32) == 256
    assert _block_rows(100000, 2048, _SUBLANE_F32) == 256
    # wide chunks honor the budget
    for chunk in (4096, 16384, 65536):
        br = _block_rows(100000, chunk, _SUBLANE_F32)
        assert br * chunk <= _BLOCK_ELEM_BUDGET
        assert br % _SUBLANE_F32 == 0 and br >= _SUBLANE_F32
    # the sublane multiple is a hard floor even past the budget
    assert _block_rows(100000, 65536, _SUBLANE_I8) == _SUBLANE_I8
    # small inputs never exceed their row count
    assert _block_rows(8, 512, _SUBLANE_F32) == 8


def test_wide_chunk_kernels_roundtrip():
    """Kernels stay correct when the budget shrinks the block (multi-
    block grid over a 16384-wide chunk)."""
    rng = np.random.default_rng(7)
    chunks = jnp.asarray(rng.normal(size=(100, 16384)), jnp.float32)

    q, s = quantize_int8(chunks, interpret=True)
    ref = Int8Compressor(chunk=16384).compress(chunks.reshape(-1))
    np.testing.assert_array_equal(np.asarray(q).reshape(-1), np.asarray(ref.data))
    # 1-ulp scale slack: the blocked max reduces the 16384-wide row in a
    # different association order than the jnp reference
    np.testing.assert_allclose(np.asarray(s), np.asarray(ref.scales), rtol=1e-6)
    out = dequantize_int8(q, s, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(q, np.float32) * np.asarray(s)[:, None],
        rtol=1e-6,
    )

    k = 4
    vals, idx = chunked_topk(chunks, k, interpret=True)
    _, ref_idx = jax.lax.top_k(jnp.abs(chunks), k)
    ref_vals = jnp.take_along_axis(chunks, ref_idx, axis=1)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_vals))

    from consensusml_tpu.compress.kernels import chunk_scatter

    dense = chunk_scatter(vals, idx, 16384, interpret=True)
    ref_dense = np.zeros((100, 16384), np.float32)
    np.put_along_axis(ref_dense, np.asarray(idx), np.asarray(vals), axis=1)
    np.testing.assert_allclose(np.asarray(dense), ref_dense, rtol=1e-6)
