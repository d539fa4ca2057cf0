"""Parity tests: chunked-vocab LM loss vs the dense-logits path.

The chunked path (losses.chunked_vocab_lm_loss) must match dense
masked_lm_loss over the tied head to f32 rounding — values AND
gradients (including the DOUBLE use of the embedding: input lookup +
head), across chunk sizes that do and do not divide the vocab.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM, gpt2_loss_fn
from consensusml_tpu.models.losses import (
    chunked_vocab_lm_loss,
    masked_lm_loss,
)


@pytest.mark.parametrize("chunk", [16, 48, 100, 1000])
def test_functional_parity_values_and_grads(chunk):
    """Standalone: chunked == dense over a raw (hidden, embedding)."""
    rng = np.random.default_rng(0)
    n, h, v = 24, 32, 100  # chunk=48 does not divide v; 1000 > v
    hidden = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(v, h)) * 0.3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)
    mask = jnp.asarray(rng.random(n) > 0.3, jnp.float32)

    def dense(hidden, emb):
        return masked_lm_loss(hidden @ emb.T, labels, mask)

    def chunked(hidden, emb):
        return chunked_vocab_lm_loss(hidden, emb, labels, mask, chunk=chunk)

    ld, gd = jax.value_and_grad(dense, argnums=(0, 1))(hidden, emb)
    lc, gc = jax.value_and_grad(chunked, argnums=(0, 1))(hidden, emb)
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-5)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


def test_gpt2_loss_fn_parity():
    """End-to-end through gpt2_loss_fn: loss_vocab_chunk>0 matches the
    dense config on identical params, including the wte gradient that
    flows through BOTH the input lookup and the in-loss head. f32 model
    dtype: in bf16 the two paths accumulate the head matmul in different
    chunk orders, so only f32 isolates the MATH parity (a loose bf16
    loss-value check rides below)."""
    kw = dict(
        vocab_size=96, hidden=64, layers=2, heads=4, max_len=32, dropout=0.0,
        dtype=jnp.float32,
    )
    m_dense = GPT2LM(config=GPT2Config(**kw))
    m_chunk = GPT2LM(config=GPT2Config(loss_vocab_chunk=40, **kw))
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, 96, size=(2, 16)), jnp.int32
    )
    params = m_dense.init(jax.random.key(0), ids)["params"]
    batch = {"input_ids": ids}
    rng = jax.random.key(1)

    def run(model):
        fn = gpt2_loss_fn(model)
        def scalar(p):
            return fn(p, {}, batch, rng)[0]
        # jitted: op-by-op dispatch of a 2-layer fwd+bwd costs ~10 s here
        return jax.jit(jax.value_and_grad(scalar))(params)

    ld, gd = run(m_dense)
    lc, gc = run(m_chunk)
    np.testing.assert_allclose(float(lc), float(ld), rtol=2e-5)
    flat_d = jax.tree_util.tree_leaves_with_path(gd)
    flat_c = dict(
        (jax.tree_util.keystr(k), v)
        for k, v in jax.tree_util.tree_leaves_with_path(gc)
    )
    for k, vd in flat_d:
        vc = flat_c[jax.tree_util.keystr(k)]
        np.testing.assert_allclose(
            np.asarray(vc), np.asarray(vd), atol=2e-4, rtol=2e-3
        )


def test_gpt2_loss_fn_bf16_loss_close():
    """bf16 model dtype (the production config): losses agree to bf16
    rounding even though grad accumulation orders differ."""
    kw = dict(
        vocab_size=96, hidden=64, layers=2, heads=4, max_len=32, dropout=0.0
    )
    m_dense = GPT2LM(config=GPT2Config(**kw))
    m_chunk = GPT2LM(config=GPT2Config(loss_vocab_chunk=40, **kw))
    ids = jnp.asarray(
        np.random.default_rng(3).integers(0, 96, size=(2, 16)), jnp.int32
    )
    params = m_dense.init(jax.random.key(0), ids)["params"]
    batch = {"input_ids": ids}
    rng = jax.random.key(1)
    ld = float(gpt2_loss_fn(m_dense)(params, {}, batch, rng)[0])
    lc = float(gpt2_loss_fn(m_chunk)(params, {}, batch, rng)[0])
    np.testing.assert_allclose(lc, ld, rtol=2e-2)


def test_loss_mask_respected():
    rng = np.random.default_rng(2)
    hidden = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 50, size=(8,)), jnp.int32)
    m1 = jnp.asarray([1, 1, 0, 0, 0, 0, 0, 0], jnp.float32)
    full = chunked_vocab_lm_loss(hidden[:2], emb, labels[:2], m1[:2], chunk=20)
    masked = chunked_vocab_lm_loss(hidden, emb, labels, m1, chunk=20)
    np.testing.assert_allclose(float(full), float(masked), rtol=1e-6)


def test_llama_loss_fn_parity():
    """Llama's UNTIED head (lm_head kernel (H, V), passed transposed)
    matches the dense path on identical params, f32 dtype."""
    from consensusml_tpu.models.llama import LlamaConfig, LlamaLM, llama_loss_fn

    kw = dict(
        vocab_size=90, hidden=48, layers=2, heads=4, kv_heads=2,
        mlp_dim=96, max_len=32, dtype=jnp.float32,
    )
    m_dense = LlamaLM(config=LlamaConfig(**kw))
    m_chunk = LlamaLM(config=LlamaConfig(loss_vocab_chunk=32, **kw))
    ids = jnp.asarray(
        np.random.default_rng(5).integers(0, 90, size=(2, 12)), jnp.int32
    )
    params = m_dense.init(jax.random.key(0), ids)["params"]
    batch = {"input_ids": ids}
    rng = jax.random.key(1)

    def run(model):
        fn = llama_loss_fn(model)
        return jax.value_and_grad(lambda p: fn(p, {}, batch, rng)[0])(params)

    ld, gd = run(m_dense)
    lc, gc = run(m_chunk)
    np.testing.assert_allclose(float(lc), float(ld), rtol=2e-5)
    for (ka, va), (kb, vb) in zip(
        jax.tree_util.tree_leaves_with_path(gc),
        jax.tree_util.tree_leaves_with_path(gd),
    ):
        assert jax.tree_util.keystr(ka) == jax.tree_util.keystr(kb)
        np.testing.assert_allclose(
            np.asarray(va), np.asarray(vb), atol=2e-4, rtol=2e-3,
            err_msg=jax.tree_util.keystr(ka),
        )


def test_inside_a_checked_shard_map():
    """The collective backend takes the loss inside ``shard_map`` with the
    check of varying axes on: the scan's first carry has to vary over the
    axes the rows vary over (a constant carry is a TypeError at trace time)."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.default_rng(1)
    w, n, h, v = 2, 12, 16, 40
    hidden = jnp.asarray(rng.normal(size=(w, n, h)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(w, v, h)) * 0.3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=(w, n)), jnp.int32)
    mask = jnp.ones((n,), jnp.float32)

    def one(hid, e, lab):
        return chunked_vocab_lm_loss(hid[0], e[0], lab[0], mask, chunk=16)[None]

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:w]), ("w",))
    inside = jax.shard_map(one, mesh=mesh, in_specs=P("w"), out_specs=P("w"))(
        hidden, emb, labels
    )
    want = [masked_lm_loss(hidden[i] @ emb[i].T, labels[i], mask) for i in range(w)]
    np.testing.assert_allclose(inside, jnp.stack(want), rtol=1e-5)
