"""Parity tests for the fused LayerNorm kernel (models/fused_ln.py).

Same protocol as test_fused_bn.py: the jnp path and the Pallas kernels
in interpreter mode are pinned against flax ``nn.LayerNorm`` — values
AND gradients through the row statistics. The compiled-kernel path is
exercised on real hardware by tests/test_kernels_tpu.py; interpreter
mode does not model Mosaic alignment, which is why shapes here mirror
the real configs (hidden a multiple of 128).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensusml_tpu.models.fused_ln import FusedLayerNorm, fused_layer_norm
from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM


def _ref_ln(x, gamma, beta, eps=1e-6):
    mod = nn.LayerNorm(epsilon=eps, dtype=jnp.float32)
    return mod.apply({"params": {"scale": gamma, "bias": beta}}, x)


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
@pytest.mark.parametrize("shape,dtype", [
    ((4, 32, 256), jnp.bfloat16),   # bert-ish
    ((2, 16, 128), jnp.float32),
    ((8, 1024), jnp.bfloat16),      # pre-flattened rows
])
def test_forward_matches_flax(impl, shape, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape) * 3 + 1, dtype)
    h = shape[-1]
    gamma = jnp.asarray(rng.normal(size=(h,)) * 0.5 + 1, jnp.float32)
    beta = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    got = fused_layer_norm(x, gamma, beta, 1e-6, jnp.float32, impl)
    want = _ref_ln(x, gamma, beta)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_gradients_match_flax(impl):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 8, 256)), jnp.float32)
    gamma = jnp.asarray(rng.normal(size=(256,)) * 0.5 + 1, jnp.float32)
    beta = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 8, 256)), jnp.float32)

    def loss_fused(x, g, b):
        return jnp.sum(fused_layer_norm(x, g, b, 1e-6, jnp.float32, impl) * w)

    def loss_ref(x, g, b):
        return jnp.sum(_ref_ln(x, g, b) * w)

    got = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=3e-4, rtol=3e-4
        )


def test_bf16_out_equals_f32_out_then_cast():
    """out_dtype=bf16 must be exactly "f32 LN then cast" — the invariant
    that lets the GPT-2 blocks feed the kernel straight into a bf16
    matmul."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 16, 256)), jnp.bfloat16)
    gamma = jnp.ones((256,), jnp.float32)
    beta = jnp.zeros((256,), jnp.float32)
    a = fused_layer_norm(x, gamma, beta, 1e-6, jnp.bfloat16, "jnp")
    b = fused_layer_norm(x, gamma, beta, 1e-6, jnp.float32, "jnp").astype(
        jnp.bfloat16
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_odd_hidden_falls_back():
    """H not a lane multiple routes to the jnp path (same math), never
    a Pallas error."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(6, 100)), jnp.float32)
    gamma = jnp.ones((100,), jnp.float32)
    beta = jnp.zeros((100,), jnp.float32)
    got = fused_layer_norm(x, gamma, beta, 1e-6, jnp.float32, "pallas")
    want = _ref_ln(x, gamma, beta)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_module_param_names_match_flax():
    """FusedLayerNorm uses flax's scale/bias names so checkpoints and
    gossip path filters are impl-agnostic."""
    mod = FusedLayerNorm(impl="jnp")
    params = mod.init(jax.random.key(0), jnp.zeros((2, 128)))["params"]
    assert set(params) == {"scale", "bias"}


def test_gpt2_norm_impl_parity():
    """A small GPT-2 forward with norm_impl="interpret" matches the
    default flax-LN model on the same params (the kernels are a
    numerics-preserving swap, modulo bf16 rounding at the LN output)."""
    cfg = dict(
        vocab_size=64, hidden=128, layers=2, heads=4, max_len=32, dropout=0.0
    )
    m_flax = GPT2LM(config=GPT2Config(**cfg))
    m_fused = GPT2LM(config=GPT2Config(norm_impl="interpret", **cfg))
    ids = jnp.asarray(
        np.random.default_rng(4).integers(0, 64, size=(2, 16)), jnp.int32
    )
    params = m_flax.init(jax.random.key(0), ids)["params"]
    a = m_flax.apply({"params": params}, ids)
    b = m_fused.apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.05, rtol=0.05)
