"""Multi-head latent attention's projections (DeepSeek-V2/V3's), training form.

Queries go through a low-rank bottleneck behind an RMSNorm; keys and values
come out of ONE ``kv_lora_rank``-wide latent a token, behind its own RMSNorm,
plus ONE ``rope_dim``-wide rotary key a token that every head shares:

    c_q = RMSNorm(u W_qa)                [q_nope | q_rope] = c_q W_qb     per head nope | rope
    [c_kv | k_r] = u W_kva               [k_nope | v] = RMSNorm(c_kv) W_kvb   per head nope | v
    q = [q_nope | rope(q_rope)]          k = [k_nope | rope(k_r), the same for every head]
    o = softmax(causal(q k^T) * scale) v                                   y = concat_heads(o) W_o

so a key is ``nope_dim + rope_dim`` wide and a value ``v_dim`` (192 and 128 at
the published sizes), and the score scale is ``(nope_dim + rope_dim)^-1/2 x
mscale^2`` with yarn's ``mscale = 0.1 mscale_all_dim ln(factor) + 1``. This is
the NON-absorbed form: ``k`` and ``v`` are materialised per head, as training
wants them; serving's absorbed form over a latent cache is not here.

The block does the attention itself (:func:`~consensusml_tpu.models.attention.
dot_product_attention`, inline, so that the flash kernels' device ops keep the
block's name ``h_<i>``); this module holds the weights
(:class:`LatentAttentionWeights`) and the arithmetic around it
(:func:`latent_qkv`, :func:`latent_out`), under the spans ``mla.q_lora``,
``mla.kv_lora``, ``mla.rope`` and ``mla.out_proj``. Weights float32, products in
``config.dtype`` with float32 accumulation, norms and the rotation float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.models.attention import apply_rope, rope_frequencies
from consensusml_tpu.obs import span as _span

__all__ = ["LatentAttentionConfig", "LatentAttentionWeights", "latent_qkv", "latent_out"]


@dataclasses.dataclass(frozen=True)
class LatentAttentionConfig:
    hidden: int = 3584
    heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 10000.0
    # yarn (factor 1: plain rotary, mscale 1)
    rope_factor: float = 1.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max_len: int = 4096
    mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    out_init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @property
    def key_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def mscale(self) -> float:
        if self.rope_factor <= 1.0:
            return 1.0
        return 0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0

    @property
    def score_scale(self) -> float:
        return self.key_dim ** -0.5 * self.mscale ** 2


class LatentAttentionWeights(nn.Module):
    """The block's matrices and the two latent norms' weights, held under the
    block's ``mixer`` like the other kinds' weights."""

    config: LatentAttentionConfig

    @nn.compact
    def __call__(self) -> dict:
        c = self.config
        normal, ones, f32 = nn.initializers.normal, nn.initializers.ones_init(), jnp.float32
        return {
            "q_a": self.param("q_a", normal(0.02), (c.hidden, c.q_lora_rank), f32),
            "q_a_norm": self.param("q_a_norm", ones, (c.q_lora_rank,), f32),
            "q_b": self.param("q_b", normal(0.02), (c.q_lora_rank, c.heads * c.key_dim), f32),
            "kv_a": self.param("kv_a", normal(0.02), (c.hidden, c.kv_lora_rank + c.rope_dim), f32),
            "kv_a_norm": self.param("kv_a_norm", ones, (c.kv_lora_rank,), f32),
            "kv_b": self.param(
                "kv_b", normal(0.02), (c.kv_lora_rank, c.heads * (c.nope_dim + c.v_dim)), f32),
            "o": self.param("o", normal(c.out_init_std), (c.heads * c.v_dim, c.hidden), f32),
        }


def _rms(x, weight, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * weight


def latent_qkv(u: jax.Array, w: dict, c: LatentAttentionConfig):
    """``u`` (B, S, hidden) -> ``q``, ``k`` (B, S, heads, nope + rope) and ``v``
    (B, S, heads, v_dim), in ``c.dtype``."""
    b, s, _ = u.shape
    f32 = jnp.float32
    dot = lambda x, m: jnp.dot(x.astype(c.dtype), m.astype(c.dtype), preferred_element_type=f32)
    with _span("mla.q_lora"):
        c_q = _rms(dot(u, w["q_a"]), w["q_a_norm"], c.norm_eps)
        q = dot(c_q, w["q_b"]).reshape(b, s, c.heads, c.key_dim)
    with _span("mla.kv_lora"):
        latent = dot(u, w["kv_a"])
        c_kv, k_rope = latent[..., : c.kv_lora_rank], latent[..., c.kv_lora_rank :]
        kv = dot(_rms(c_kv, w["kv_a_norm"], c.norm_eps), w["kv_b"])
        kv = kv.reshape(b, s, c.heads, c.nope_dim + c.v_dim)
    with _span("mla.rope"):
        table = rope_frequencies(
            c.rope_dim, s, c.rope_theta, factor=c.rope_factor, beta_fast=c.beta_fast,
            beta_slow=c.beta_slow, original_max_len=c.original_max_len,
        )
        q_rope = apply_rope(q[..., c.nope_dim :], table)
        k_rope = apply_rope(k_rope[:, :, None, :], table)  # ONE key a token, every head's
        k_rope = jnp.broadcast_to(k_rope, (b, s, c.heads, c.rope_dim))
        q = jnp.concatenate([q[..., : c.nope_dim], q_rope], axis=-1).astype(c.dtype)
        k = jnp.concatenate([kv[..., : c.nope_dim], k_rope], axis=-1).astype(c.dtype)
    return q, k, kv[..., c.nope_dim :].astype(c.dtype)


def latent_out(attn: jax.Array, w: dict, c: LatentAttentionConfig):
    """``attn`` (B, S, heads, v_dim) -> ``(y (B, S, hidden), out_rms (B, heads))``:
    the output projection, and the root mean square of what attention put out,
    per head (what a step shows of itself)."""
    b, s = attn.shape[:2]
    with _span("mla.out_proj"):
        out_rms = jnp.sqrt(jnp.mean(jnp.square(attn.astype(jnp.float32)), axis=(1, 3)))
        y = jnp.dot(attn.reshape(b, s, c.heads * c.v_dim).astype(c.dtype), w["o"].astype(c.dtype))
    return y, out_rms
