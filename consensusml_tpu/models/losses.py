"""Shared loss functions (computed in float32 regardless of param dtype)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

__all__ = [
    "softmax_cross_entropy",
    "masked_lm_loss",
    "chunked_vocab_lm_loss",
]


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross-entropy over the batch; labels are int class ids."""
    logits = jnp.asarray(logits, jnp.float32)
    return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, labels))


def masked_lm_loss(logits: jax.Array, labels: jax.Array, mask: jax.Array) -> jax.Array:
    """Cross-entropy over masked positions only (BERT-MLM / causal LM).

    ``mask`` is 1.0 where the position contributes to the loss.
    """
    logits = jnp.asarray(logits, jnp.float32)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    mask = jnp.asarray(mask, jnp.float32)
    return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_vocab_lm_loss(
    hidden: jax.Array,
    embedding: jax.Array,
    labels: jax.Array,
    mask: jax.Array,
    chunk: int = 8192,
) -> jax.Array:
    """Tied-head LM cross-entropy WITHOUT materializing the logits.

    Numerically equal (to f32 rounding) to
    ``masked_lm_loss(hidden @ embedding.T, labels, mask)`` but the
    ``(N, V)`` logits tensor never exists: a ``lax.scan`` over vocab
    chunks keeps a running online logsumexp (max + scaled sumexp, the
    flash-attention recurrence applied to the vocab axis) plus the
    label's logit, and ``jax.checkpoint`` on the body makes the
    backward RECOMPUTE each chunk's logits instead of storing them. At
    GPT-2-medium scale (B8 S1024 V50257) that deletes ~2.5 GB of
    activation residuals (bf16 logits + their f32 upcast) per step for
    one extra lm-head matmul pass in the backward.

    ``hidden``: (..., H) pre-head states (post final-LN, model dtype);
    ``embedding``: (V, H) tied embedding table; ``labels``/``mask``
    must carry exactly ``hidden[..., 0].size`` elements (they are
    flattened, NOT broadcast — unlike dense ``masked_lm_loss``, a
    scalar/broadcastable mask is a reshape error here). The chunk
    matmul runs in
    the model dtype and upcasts per-chunk to f32, matching the dense
    path's ``attend``-then-``asarray(f32)`` exactly.
    """
    h2 = hidden.reshape(-1, hidden.shape[-1])
    n = h2.shape[0]
    labels = labels.reshape(n)
    mask = jnp.asarray(mask, jnp.float32).reshape(n)
    v, hdim = embedding.shape
    chunk = min(chunk, v)
    pad = (-v) % chunk
    emb = jnp.pad(embedding, ((0, pad), (0, 0))) if pad else embedding
    nch = (v + pad) // chunk
    w_chunks = emb.reshape(nch, chunk, hdim)
    offsets = jnp.arange(nch, dtype=jnp.int32) * chunk

    def body(carry, xs):
        m, s, lab = carry
        w, off = xs
        logits = jnp.asarray(
            h2 @ jnp.asarray(w, h2.dtype).T, jnp.float32
        )  # (n, chunk) — lives only inside this (rematerialized) body
        valid = (off + jnp.arange(chunk, dtype=jnp.int32)) < v
        logits = jnp.where(valid[None, :], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1
        )
        in_chunk = (labels >= off) & (labels < off + chunk)
        idx = jnp.clip(labels - off, 0, chunk - 1)
        picked = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        lab = lab + jnp.where(in_chunk, picked, 0.0)
        return (m_new, s, lab), None

    # made from the rows, so that under a checked shard_map the carry
    # varies over the mesh axes the rows vary over
    zeros = jnp.zeros_like(h2[:, 0], dtype=jnp.float32)
    carry0 = (jnp.full_like(zeros, -jnp.inf), zeros, zeros)
    (m, s, lab), _ = jax.lax.scan(
        jax.checkpoint(body), carry0, (w_chunks, offsets)
    )
    per_tok = m + jnp.log(s) - lab
    return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)
