"""GPT-2 decoder for causal-LM pretraining.

Reference parity: "GPT-2-medium pretrain, top-k sparsified + 8-bit
quantized gradient gossip" (BASELINE.json configs[4]; SURVEY.md L5 — mount
empty; architecture is canonical Radford et al. 2019: pre-LN transformer,
learned positions, GELU, tied LM head; medium = 24 layers / hidden 1024 /
16 heads).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.models.attention import (
    cached_attention,
    cached_attention_window,
    dot_product_attention,
    gather_paged_kv,
    paged_update_kv_cache,
    paged_update_kv_cache_window,
    update_kv_cache,
)
from consensusml_tpu.models.losses import chunked_vocab_lm_loss, masked_lm_loss
from consensusml_tpu.models.paged_attention import (
    fused_paged_attention,
    fused_paged_attention_window,
)

__all__ = ["GPT2Config", "GPT2LM", "gpt2_medium", "gpt2_loss_fn"]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    hidden: int = 1024
    layers: int = 24
    heads: int = 16
    max_len: int = 1024
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    # rematerialize each decoder block in the backward pass: activations
    # drop from O(layers) to O(1) blocks at ~1/3 extra fwd FLOPs — the
    # standard lever when batch scaling is HBM-bound, off by default
    remat: bool = False
    # "flax" (default) | "pallas" | "auto" | "interpret": the fused-LN
    # Pallas kernel (models/fused_ln.py). Parity-pinned; unmeasured on
    # this installation (ROADMAP C2) — flax stays the default.
    norm_impl: str = "flax"
    # >0: gpt2_loss_fn computes the LM cross-entropy via
    # losses.chunked_vocab_lm_loss with this vocab chunk — the (B,S,V)
    # logits tensor is never materialized (~2.5 GB of residuals at
    # medium scale). 0 = dense logits (default).
    loss_vocab_chunk: int = 0

    @property
    def mlp_dim(self) -> int:
        return 4 * self.hidden


def gpt2_medium(**overrides) -> "GPT2LM":
    return GPT2LM(config=GPT2Config(**overrides))


def _layer_norm(config: "GPT2Config", name: str):
    """LN factory: flax by default; the fused Pallas kernel emits bf16
    straight into the consuming bf16 matmul when opted in (identical
    numerics to f32-out-then-cast — see models/fused_ln.py)."""
    if config.norm_impl == "flax":
        return nn.LayerNorm(dtype=jnp.float32, name=name)
    from consensusml_tpu.models.fused_ln import FusedLayerNorm

    return FusedLayerNorm(
        out_dtype=config.dtype, impl=config.norm_impl, name=name
    )


class _DecoderBlock(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(
        self,
        x,
        deterministic: bool,
        cache=None,
        positions=None,
        return_kv: bool = False,
        block_table=None,
        attn_impl: str = "gather",
    ):
        c = self.config
        d_head = c.hidden // c.heads
        y = _layer_norm(c, "ln_1")(x)
        qkv = nn.DenseGeneral((c.heads, 3 * d_head), dtype=c.dtype, name="qkv")(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if cache is not None and block_table is not None:
            if positions.ndim == 2:
                # paged VERIFY window (serve/pool/spec.py): W tokens per
                # slot scattered + attended in one fixed-shape step
                k_pages, v_pages = paged_update_kv_cache_window(
                    cache, k, v, block_table, positions
                )
                if attn_impl == "gather":
                    kg, vg = gather_paged_kv(k_pages, v_pages, block_table)
                    attn = cached_attention_window(
                        q, kg, vg, positions=positions, dtype=c.dtype
                    )
                else:
                    # kernel tier: one fused pallas pass per layer, no
                    # gathered view in HBM (models/paged_attention.py;
                    # bit-exact vs the gather branch per impl)
                    attn = fused_paged_attention_window(
                        q, k_pages, v_pages, block_table,
                        positions=positions, dtype=c.dtype, impl=attn_impl,
                    )
            else:
                # paged decode step: the cache is a shared block pool;
                # this slot's logical view assembles by block-table
                # gather (serve/pool/ paged-KV path)
                k_pages, v_pages, lengths = paged_update_kv_cache(
                    cache, k, v, block_table, positions
                )
                if attn_impl == "gather":
                    kg, vg = gather_paged_kv(k_pages, v_pages, block_table)
                    attn = cached_attention(
                        q, kg, vg, lengths=lengths, dtype=c.dtype
                    )
                else:
                    attn = fused_paged_attention(
                        q, k_pages, v_pages, block_table,
                        lengths=lengths, dtype=c.dtype, impl=attn_impl,
                    )
            new_cache = {"k": k_pages, "v": v_pages}
        elif cache is not None:
            # decode step: write this token's K/V into the slot cache and
            # attend over the valid prefix (serve/ KV-cache path)
            k_cache, v_cache, lengths = update_kv_cache(cache, k, v, positions)
            attn = cached_attention(
                q, k_cache, v_cache, lengths=lengths, dtype=c.dtype
            )
            new_cache = {"k": k_cache, "v": v_cache}
        else:
            attn = dot_product_attention(q, k, v, causal=True, dtype=c.dtype)
        attn = nn.DenseGeneral(c.hidden, axis=(-2, -1), dtype=c.dtype, name="out")(attn)
        x = x + nn.Dropout(c.dropout, deterministic=deterministic)(attn)
        y = _layer_norm(c, "ln_2")(x)
        y = nn.Dense(c.mlp_dim, dtype=c.dtype, name="mlp_in")(y)
        y = nn.gelu(y)
        y = nn.Dense(c.hidden, dtype=c.dtype, name="mlp_out")(y)
        out = x + nn.Dropout(c.dropout, deterministic=deterministic)(y)
        if cache is not None:
            return out, new_cache
        if return_kv:
            return out, (k, v)
        return out


class GPT2LM(nn.Module):
    config: GPT2Config

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
        """Logits (f32) by default; ``return_hidden=True`` returns the
        pre-head states (post final-LN, model dtype) instead — the
        chunked-vocab loss path computes the head inside the loss so the
        full logits tensor is never materialized.

        ``attn_impl`` selects the paged-attention tier ("gather" = the
        two-step reference, "jnp"/"interpret"/"pallas" via
        :mod:`consensusml_tpu.models.paged_attention` — all bit-exact);
        it is a static construction-time string, so each serving stage
        fn compiles exactly one program either way.

        Serving hooks (:mod:`consensusml_tpu.serve`): ``return_kv=True``
        additionally returns each layer's ``(k, v)`` — (B, S, H, D) — for
        prefill cache insertion; ``kv_cache`` (a per-layer list of
        ``{"k", "v"}`` slot caches) with ``positions`` ((B,) per-slot
        token index) runs one single-token decode step against the cache
        and returns ``(logits, new_kv_cache)``. With ``block_table`` the
        per-layer dicts are PAGED block pools instead of per-slot rows
        (:mod:`consensusml_tpu.serve.pool`). kv_cache and return_kv are
        mutually exclusive; the training/eval path passes neither and is
        unchanged.
        """
        c = self.config
        if kv_cache is not None and return_kv:
            raise ValueError("kv_cache (decode) and return_kv (prefill) are exclusive")
        if block_table is not None and kv_cache is None:
            raise ValueError("block_table requires kv_cache (paged decode)")
        b, s = input_ids.shape
        multi = positions is not None and positions.ndim == 2
        if kv_cache is not None and s != 1 and not multi:
            raise ValueError(
                f"decode steps are single-token, got seq len {s} (a "
                "k-token verify window needs 2-D positions)"
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
        tok_emb = nn.Embed(c.vocab_size, c.hidden, dtype=c.dtype, name="wte")
        x = tok_emb(input_ids)
        if positions is None:
            pos = jnp.arange(s)[None, :]
        else:
            pos = positions if multi else positions[:, None]
        # clamp the TABLE LOOKUP only (raw positions still drive the
        # paged scatter + masks): window lanes past a slot's block table
        # legitimately carry positions >= max_len — they scatter to the
        # trash block and every consumer masks them, but an unclamped
        # lookup is jnp's NaN fill, and NaN K/V poisons even EXCLUDED
        # attention rows through 0 * NaN in the output matmul
        x = x + nn.Embed(c.max_len, c.hidden, dtype=c.dtype, name="wpe")(
            jnp.minimum(pos, c.max_len - 1)
        )
        x = nn.Dropout(c.dropout, deterministic=deterministic)(x)
        # static_argnums: `deterministic` is a python bool, not a tracer.
        # The serving paths (kv_cache / return_kv) bypass remat outright:
        # remat is a BACKWARD-pass memory lever and inference has no
        # backward — and the extra flag args would otherwise ride through
        # nn.remat as tracers and break the python branches on them.
        block = (
            nn.remat(_DecoderBlock, static_argnums=(2,))
            if c.remat and kv_cache is None and not return_kv
            else _DecoderBlock
        )
        new_caches, kvs = [], []
        for i in range(c.layers):
            blk = block(c, name=f"h_{i}")
            if kv_cache is not None:
                x, layer_cache = blk(
                    x, deterministic, kv_cache[i], positions,
                    block_table=block_table, attn_impl=attn_impl,
                )
                new_caches.append(layer_cache)
            elif return_kv:
                x, kv = blk(x, deterministic, None, None, True)
                kvs.append(kv)
            else:
                x = blk(x, deterministic)
        x = _layer_norm(c, "ln_f")(x)
        if return_hidden:
            return jnp.asarray(x, c.dtype)
        logits = tok_emb.attend(jnp.asarray(x, tok_emb.dtype))
        logits = jnp.asarray(logits, jnp.float32)
        if kv_cache is not None:
            return logits, new_caches
        if return_kv:
            return logits, kvs
        return logits


def gpt2_loss_fn(model: GPT2LM):
    """Next-token prediction: batch has ``input_ids`` (B, S); loss over all
    positions predicting token t+1 (shift inside). With
    ``config.loss_vocab_chunk > 0`` the head runs inside
    ``chunked_vocab_lm_loss`` and the logits tensor never exists."""
    chunk = model.config.loss_vocab_chunk

    def loss_fn(params, model_state, batch, rng):
        ids = batch["input_ids"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones_like(ids[:, 1:], jnp.float32)
        else:
            mask = mask[:, 1:]
        if chunk > 0:
            hidden = model.apply(
                {"params": params}, ids, deterministic=False,
                return_hidden=True, rngs={"dropout": rng},
            )
            loss = chunked_vocab_lm_loss(
                hidden[:, :-1], params["wte"]["embedding"],
                ids[:, 1:], mask, chunk=chunk,
            )
            return loss, model_state
        logits = model.apply(
            {"params": params}, ids, deterministic=False, rngs={"dropout": rng}
        )
        return masked_lm_loss(logits[:, :-1], ids[:, 1:], mask), model_state

    return loss_fn
