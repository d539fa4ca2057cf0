"""The flash-attention kernels compiled for a DESCRIBED TPU v5e — no chip.

The interpreter tests cannot see what Mosaic and the TPU compiler refuse:
a slice off the (8, 128) tiling, a block spec the chip cannot hold, a
straight-line schedule whose spills outgrow scoped VMEM (2560 x 128-wide
heads did, unrolled, while every interpreter test passed). The TPU
compiler is installed here and compiles for a topology that is described
and not attached; nothing runs, so these say nothing about results or
times (tests/test_kernels_tpu.py does, on the chip).

The topology is described inside a fixture, never at import: one process
at a time may hold the TPU library, and the suite's workers all import
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from consensusml_tpu.models import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _grads(causal, masked):
    def f(q, k, v, *mask):
        def loss(q, k, v):
            o = fa.flash_attention(
                q, k, v, causal=causal, kv_mask=mask[0] if masked else None
            )
            return jnp.sum(o.astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return f


@pytest.mark.parametrize(
    "shape, dtype, causal, masked",
    [
        # the benchmark cell's call: GPT-2-medium, 8 x 1024, 16 heads of 64
        ((8, 1024, 16, 64), jnp.bfloat16, True, False),
        ((2, 1024, 4, 64), jnp.float32, True, False),
        # padded tail, 128-wide heads (llama), still straight-line code
        ((1, 2000, 8, 128), jnp.bfloat16, True, False),
        # past the straight-line budget: a program per block, looping
        ((1, 2560, 8, 128), jnp.bfloat16, True, False),
        ((1, 8192, 8, 128), jnp.bfloat16, True, False),
        # BERT: not causal, every tile masked by the key row
        ((2, 1024, 4, 64), jnp.bfloat16, False, True),
        ((1, 3000, 4, 64), jnp.bfloat16, False, True),
    ],
)
def test_forward_and_backward_compile_for_v5e(one_chip, shape, dtype, causal, masked):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = [x, x, x]
    if masked:
        args.append(jax.ShapeDtypeStruct(shape[:2], jnp.float32, sharding=one_chip))
    text = jax.jit(_grads(causal, masked)).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("s_real", [1024, 1000])
def test_ring_offset_kernels_compile_for_v5e(one_chip, s_real):
    """The three kernels as ``parallel.ring_attention`` calls them: traced
    offsets in SMEM, the full loop, padded keys in the last tile."""
    blk = jax.ShapeDtypeStruct((16, 1024, 64), jnp.bfloat16, sharding=one_chip)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def step(q, k, v, do, q_off, k_off):
        kw = dict(q_offset=q_off, k_offset=k_off)
        o, lse = fa._fwd(q, k, v, True, s_real, 0.125, False, **kw)
        dq = fa._bwd_dq(q, k, v, do, o, lse, True, s_real, 0.125, False, **kw)
        dk, dv = fa._bwd_dkv(
            q, k, v, do, lse, fa.delta_rows(do, o), True, s_real, 0.125, False, **kw
        )
        return o, dq, dk, dv

    text = jax.jit(step).lower(blk, blk, blk, blk, off, off).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_the_hybrid_cells_call_compiles_stacked_for_v5e(one_chip, monkeypatch):
    """32 heads of 128 over 8,192 tokens under the stacked backend's ``vmap``
    (a worker axis of 1): the dk/dv kernel keeps a head's whole q, do and
    lane-replicated lse, 16 MB double-buffered, and the compiler refused it
    at the default scoped VMEM (17.00M of 16.00M) until the launch asked for
    what it keeps. The benchmark's 1,024-token call asks for nothing."""
    asked = []
    real = fa.pl.pallas_call

    def spy(*args, **kwargs):
        asked.append(getattr(kwargs.get("compiler_params"), "vmem_limit_bytes", None))
        return real(*args, **kwargs)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    monkeypatch.setattr(fa, "_TRACED", {})  # trace anew: the spy sees every launch
    x = jax.ShapeDtypeStruct((1, 1, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.vmap(_grads(True, False))).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert len(asked) == 3 and all(a is not None and a > 16 * 2**20 for a in asked)
    del asked[:]
    short = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16, sharding=one_chip)
    jax.jit(_grads(True, False)).lower(short, short, short)
    assert asked == [None, None, None]
