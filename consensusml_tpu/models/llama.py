"""Llama-2 style decoder with built-in LoRA fine-tuning support.

Reference parity: "Llama-2-7B LoRA fine-tune, torus gossip over 4x4 mesh"
(BASELINE.json configs[3]; SURVEY.md L5 — mount empty; architecture is
canonical Touvron et al. 2023: RMSNorm pre-norm, RoPE, SwiGLU MLP,
optional grouped-query attention, untied LM head).

LoRA is a construction-time flag (``lora_rank``): attention projections
become base-kernel + low-rank ``A @ B`` adapters. Adapter params live at
paths containing ``lora_``, so :mod:`consensusml_tpu.models.lora` can mask
the optimizer to adapters only and the gossip engine can exchange ONLY
adapters (a few MB instead of 7B params — the decentralized-bandwidth win
that makes the torus-gossip LoRA config practical).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.models.attention import (
    apply_rope,
    cached_attention,
    cached_attention_window,
    dot_product_attention,
    gather_paged_kv,
    paged_update_kv_cache,
    paged_update_kv_cache_window,
    rope_frequencies,
    update_kv_cache,
)
from consensusml_tpu.models.losses import chunked_vocab_lm_loss, masked_lm_loss
from consensusml_tpu.models.paged_attention import (
    fused_paged_attention,
    fused_paged_attention_window,
)

__all__ = ["LlamaConfig", "LlamaLM", "llama2_7b", "llama_tiny", "llama_loss_fn"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    layers: int = 32
    heads: int = 32
    kv_heads: int = 32
    mlp_dim: int = 11008
    max_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    lora_rank: int = 0  # 0 = plain dense projections
    lora_alpha: float = 16.0
    # >0: llama_loss_fn computes the untied-head cross-entropy via
    # losses.chunked_vocab_lm_loss — the (B,S,V) logits never
    # materialize (the dominant activation at the 32k vocab).
    # 0 = dense (default).
    loss_vocab_chunk: int = 0
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def llama2_7b(**overrides) -> "LlamaLM":
    return LlamaLM(config=LlamaConfig(**overrides))


def llama_tiny(**overrides) -> "LlamaLM":
    """Test-scale Llama (same code path, tiny dims)."""
    defaults = dict(
        vocab_size=256, hidden=64, layers=2, heads=4, kv_heads=2, mlp_dim=128, max_len=128
    )
    defaults.update(overrides)
    return LlamaLM(config=LlamaConfig(**defaults))


class LoRADense(nn.Module):
    """Dense projection with optional low-rank adapter.

    ``y = x @ W  +  (alpha/r) * (x @ A) @ B``; ``A`` is N(0, 1/r)-init,
    ``B`` zero-init so fine-tuning starts at the base model. Adapter params
    are named ``lora_a`` / ``lora_b`` for path-based trainable filtering.
    """

    features: int
    rank: int = 0
    alpha: float = 16.0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        y = nn.Dense(self.features, use_bias=False, dtype=self.dtype, name="base")(x)
        if self.rank > 0:
            a = self.param(
                "lora_a",
                nn.initializers.normal(1.0 / self.rank),
                (x.shape[-1], self.rank),
                jnp.float32,
            )
            b = self.param(
                "lora_b", nn.initializers.zeros_init(), (self.rank, self.features), jnp.float32
            )
            lo = (jnp.asarray(x, self.dtype) @ a.astype(self.dtype)) @ b.astype(self.dtype)
            y = y + (self.alpha / self.rank) * lo
        return y


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        xf = jnp.asarray(x, jnp.float32)
        scale = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],), jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


class _LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(
        self,
        x,
        rope_table,
        cache=None,
        positions=None,
        return_kv: bool = False,
        block_table=None,
        attn_impl: str = "gather",
    ):
        c = self.config
        d = c.head_dim
        proj = lambda feats, name: LoRADense(
            feats, rank=c.lora_rank, alpha=c.lora_alpha, dtype=c.dtype, name=name
        )
        y = RMSNorm(c.norm_eps, name="attn_norm")(x)
        b, s, _ = y.shape
        q = proj(c.heads * d, "q_proj")(y).reshape(b, s, c.heads, d)
        k = proj(c.kv_heads * d, "k_proj")(y).reshape(b, s, c.kv_heads, d)
        v = proj(c.kv_heads * d, "v_proj")(y).reshape(b, s, c.kv_heads, d)
        if positions is None:
            pos2d = None
        elif positions.ndim == 2:
            pos2d = positions
        else:
            pos2d = positions[:, None]
        q = apply_rope(q, rope_table, pos2d)
        k = apply_rope(k, rope_table, pos2d)
        rep = c.heads // c.kv_heads
        if cache is not None and block_table is not None:
            if positions is not None and positions.ndim == 2:
                # paged VERIFY window (serve/pool/spec.py): W tokens per
                # slot; pages stay pre-repeat, GQA expands the gather
                k_pages, v_pages = paged_update_kv_cache_window(
                    cache, k, v, block_table, positions
                )
                new_cache = {"k": k_pages, "v": v_pages}
                if attn_impl == "gather":
                    kg, vg = gather_paged_kv(k_pages, v_pages, block_table)
                    if rep != 1:
                        kg = jnp.repeat(kg, rep, axis=2)
                        vg = jnp.repeat(vg, rep, axis=2)
                    attn = cached_attention_window(
                        q, kg, vg, positions=positions, dtype=c.dtype
                    )
                else:
                    # kernel tier (models/paged_attention.py): GQA
                    # expansion happens INSIDE the fused pass, pages
                    # stay pre-repeat — bit-exact vs the gather branch
                    attn = fused_paged_attention_window(
                        q, k_pages, v_pages, block_table,
                        positions=positions, dtype=c.dtype, impl=attn_impl,
                    )
            else:
                # paged decode: block-pool pages store pre-repeat
                # (kv_heads) rows; GQA expansion happens on the gather
                k_pages, v_pages, lengths = paged_update_kv_cache(
                    cache, k, v, block_table, positions
                )
                new_cache = {"k": k_pages, "v": v_pages}
                if attn_impl == "gather":
                    kg, vg = gather_paged_kv(k_pages, v_pages, block_table)
                    if rep != 1:
                        kg = jnp.repeat(kg, rep, axis=2)
                        vg = jnp.repeat(vg, rep, axis=2)
                    attn = cached_attention(
                        q, kg, vg, lengths=lengths, dtype=c.dtype
                    )
                else:
                    attn = fused_paged_attention(
                        q, k_pages, v_pages, block_table,
                        lengths=lengths, dtype=c.dtype, impl=attn_impl,
                    )
        elif cache is not None:
            # decode: cache stores PRE-repeat (kv_heads) rows — GQA
            # expansion happens on the read, so the cache stays small
            k_cache, v_cache, lengths = update_kv_cache(cache, k, v, positions)
            new_cache = {"k": k_cache, "v": v_cache}
            if rep != 1:
                k_cache = jnp.repeat(k_cache, rep, axis=2)
                v_cache = jnp.repeat(v_cache, rep, axis=2)
            attn = cached_attention(
                q, k_cache, v_cache, lengths=lengths, dtype=c.dtype
            )
        else:
            kv = (k, v)  # pre-repeat, for prefill cache insertion
            if rep != 1:  # grouped-query attention
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            attn = dot_product_attention(q, k, v, causal=True, dtype=c.dtype)
        x = x + proj(c.hidden, "o_proj")(attn.reshape(b, s, c.heads * d))
        y = RMSNorm(c.norm_eps, name="mlp_norm")(x)
        gate = nn.Dense(c.mlp_dim, use_bias=False, dtype=c.dtype, name="gate_proj")(y)
        up = nn.Dense(c.mlp_dim, use_bias=False, dtype=c.dtype, name="up_proj")(y)
        y = nn.Dense(c.hidden, use_bias=False, dtype=c.dtype, name="down_proj")(
            nn.silu(gate) * up
        )
        out = x + y
        if cache is not None:
            return out, new_cache
        if return_kv:
            return out, kv
        return out


class LlamaLM(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        deterministic: bool = True,
        return_hidden: bool = False,
        *,
        positions: jax.Array | None = None,
        kv_cache: list | None = None,
        return_kv: bool = False,
        block_table: jax.Array | None = None,
        attn_impl: str = "gather",
    ):
        """Serving hooks mirror :class:`~consensusml_tpu.models.gpt2.GPT2LM`:
        ``return_kv=True`` also returns per-layer pre-repeat ``(k, v)``
        for prefill insertion; ``kv_cache`` + ``positions`` runs one
        single-token decode step (against paged block pools when
        ``block_table`` is given); ``attn_impl`` selects the paged-
        attention tier (:mod:`consensusml_tpu.models.paged_attention` —
        all impls bit-exact). The training path passes none of them."""
        c = self.config
        if kv_cache is not None and return_kv:
            raise ValueError("kv_cache (decode) and return_kv (prefill) are exclusive")
        if block_table is not None and kv_cache is None:
            raise ValueError("block_table requires kv_cache (paged decode)")
        multi = positions is not None and positions.ndim == 2
        if kv_cache is not None and input_ids.shape[1] != 1 and not multi:
            raise ValueError(
                f"decode steps are single-token, got seq len "
                f"{input_ids.shape[1]} (a k-token verify window needs "
                "2-D positions)"
            )
        if multi and (kv_cache is None or block_table is None):
            raise ValueError(
                "2-D positions (verify window) need kv_cache + block_table"
            )
        if attn_impl != "gather" and block_table is None:
            raise ValueError(
                f"attn_impl={attn_impl!r} is the PAGED kernel tier and "
                "needs block_table (the slot path has no fused kernel; "
                "never silently fall back to the reference)"
            )
        x = nn.Embed(c.vocab_size, c.hidden, dtype=c.dtype, name="tok_emb")(input_ids)
        rope_table = rope_frequencies(c.head_dim, c.max_len, c.rope_theta)
        new_caches, kvs = [], []
        for i in range(c.layers):
            blk = _LlamaBlock(c, name=f"layer_{i}")
            if kv_cache is not None:
                x, layer_cache = blk(
                    x, rope_table, kv_cache[i], positions,
                    block_table=block_table, attn_impl=attn_impl,
                )
                new_caches.append(layer_cache)
            elif return_kv:
                x, kv = blk(x, rope_table, None, positions, True)
                kvs.append(kv)
            else:
                x = blk(x, rope_table)
        x = RMSNorm(c.norm_eps, name="final_norm")(x)
        head = nn.Dense(c.vocab_size, use_bias=False, dtype=c.dtype, name="lm_head")
        if return_hidden:  # chunked-loss path: head runs inside the loss
            # the head params must exist in EVERY init mode (the chunked
            # loss reads params["lm_head"] directly); a one-token call
            # creates them and XLA dead-code-eliminates it at runtime
            head(x[:, :1])
            return jnp.asarray(x, c.dtype)
        logits = jnp.asarray(head(x), jnp.float32)
        if kv_cache is not None:
            return logits, new_caches
        if return_kv:
            return logits, kvs
        return logits


def llama_loss_fn(model: LlamaLM):
    """Causal next-token loss; batch: ``input_ids`` (+ optional loss_mask).

    ``config.loss_vocab_chunk > 0`` routes through the chunked-vocab
    loss: the untied lm_head kernel (H, V) rides in as its transpose —
    one extra (V, H) copy per pass (~0.5 GB at 7B, vs the ~2 GB of
    logits it deletes)."""
    chunk = model.config.loss_vocab_chunk

    def loss_fn(params, model_state, batch, rng):
        ids = batch["input_ids"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones_like(ids[:, 1:], jnp.float32)
        else:
            mask = mask[:, 1:]
        if chunk > 0:
            hidden = model.apply({"params": params}, ids, return_hidden=True)
            return (
                chunked_vocab_lm_loss(
                    hidden[:, :-1], params["lm_head"]["kernel"].T,
                    ids[:, 1:], mask, chunk=chunk,
                ),
                model_state,
            )
        logits = model.apply({"params": params}, ids)
        return masked_lm_loss(logits[:, :-1], ids[:, 1:], mask), model_state

    return loss_fn
