"""Hybrid decoder whose blocks are of three kinds by a pattern string.

The ``nemotron_h`` family (NVIDIA Nemotron 3 Nano 30B-A3B: 52 blocks by
``MEMEM*EMEMEM*...``, 23 Mamba-2, 23 mixture-of-experts, 6 attention).
Block ``i`` is ONE mixer behind one RMSNorm and a residual,
``x <- x + Mixer_i(RMSNorm_i(x))``, the mixer chosen by character ``i``:

- ``M`` :class:`~consensusml_tpu.models.ssm.Mamba2Mixer` (chunked SSD scan);
- ``E`` :class:`~consensusml_tpu.models.moe.HeldExpertsMLP` (sigmoid router,
  top-k, non-gated ``relu^2`` experts, one shared expert; told which experts
  it holds);
- ``*`` grouped-query causal attention with NO rotary or learned positions
  (the family's attention applies none), K/V repeated to the query heads
  and, past the dense threshold on a TPU, :mod:`~consensusml_tpu.models.
  flash_attention` called from the block itself, so that the kernels' device
  ops carry the block's name ``h_<i>``.

Embedding and head are untied. Parameters float32, products in
``config.dtype``, router and norms float32. ``apply`` returns ``(logits or
hidden states, counts)`` with ``counts`` the expert layers' device counters
stacked over the ``E`` blocks, and what the step shows of itself: the experts
every token chose, the size of every scan's output
(:func:`nemotron_h_loss_fn` hands both to the round's metrics).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.models.attention import dot_product_attention
from consensusml_tpu.models.llama import RMSNorm
from consensusml_tpu.models.losses import chunked_vocab_lm_loss, masked_lm_loss
from consensusml_tpu.models.moe import HeldExpertsConfig, HeldExpertsMLP
from consensusml_tpu.models.ssm import Mamba2Config, Mamba2Mixer
from consensusml_tpu.obs import span as _span

__all__ = ["NemotronHConfig", "NemotronHLM", "nemotron_h_tiny", "nemotron_h_loss_fn"]

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden: int = 2688
    pattern: str = PUBLISHED_PATTERN
    # rescale_prenorm_residual: every mixer's output matrix starts at
    # 0.02 / sqrt(2 * depth_published), whatever part of the depth runs here
    depth_published: int = 52
    # M
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    groups: int = 8
    state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # *
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    # E
    experts: int = 128
    held: int = 128
    held_start: int = 0
    top_k: int = 6
    route_scale: float = 2.5
    expert_width: int = 1856
    shared_width: int = 3712
    score_correction: str = "zeros"  # or "centred": HeldExpertsConfig
    norm_eps: float = 1e-5
    remat: bool = True  # per block
    loss_vocab_chunk: int = 0  # >0: the head runs inside chunked_vocab_lm_loss
    dtype: Any = jnp.bfloat16

    @property
    def out_init_std(self) -> float:
        return 0.02 / (2.0 * self.depth_published) ** 0.5

    @property
    def ssm(self) -> Mamba2Config:
        return Mamba2Config(
            hidden=self.hidden, heads=self.mamba_heads, head_dim=self.mamba_head_dim,
            groups=self.groups, state=self.state, conv_kernel=self.conv_kernel,
            chunk=self.chunk, dt_min=self.dt_min, dt_max=self.dt_max,
            dt_floor=self.dt_floor, norm_eps=self.norm_eps,
            out_init_std=self.out_init_std, dtype=self.dtype,
        )

    @property
    def moe(self) -> HeldExpertsConfig:
        return HeldExpertsConfig(
            hidden=self.hidden, experts=self.experts, held=self.held,
            held_start=self.held_start, top_k=self.top_k, route_scale=self.route_scale,
            expert_width=self.expert_width, shared_width=self.shared_width,
            score_correction=self.score_correction,
            out_init_std=self.out_init_std, dtype=self.dtype,
        )

    @property
    def expert_layers(self) -> tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.pattern) if kind == "E")


def nemotron_h_tiny(**overrides) -> "NemotronHLM":
    """Test-scale hybrid (same code path, tiny widths): the benchmark's cut of
    the pattern, 8 experts of which 4 are held."""
    defaults = dict(
        vocab_size=64, hidden=32, pattern="MEMEM*EME", mamba_heads=4, mamba_head_dim=8,
        groups=2, state=16, chunk=8, heads=4, kv_heads=2, head_dim=8, experts=8, held=4,
        top_k=3, expert_width=24, shared_width=48,
    )
    defaults.update(overrides)
    return NemotronHLM(config=NemotronHConfig(**defaults))


class _AttentionWeights(nn.Module):
    """The ``*`` block's four matrices, held under the block's ``mixer`` like
    the other kinds' weights; the block itself does the arithmetic."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self):
        c = self.config
        normal, f32 = nn.initializers.normal, jnp.float32
        d_q, d_kv = c.heads * c.head_dim, c.kv_heads * c.head_dim
        return (
            self.param("q", normal(0.02), (c.hidden, d_q), f32),
            self.param("k", normal(0.02), (c.hidden, d_kv), f32),
            self.param("v", normal(0.02), (c.hidden, d_kv), f32),
            self.param("o", normal(c.out_init_std), (d_q, c.hidden), f32),
        )


class _Block(nn.Module):
    config: NemotronHConfig
    kind: str
    layer: int

    @nn.compact
    def __call__(self, x):
        """``(x + mixer(norm(x)), counts)``; ``counts`` is an E block's
        counters, an M block's ``{"scan_rms": ...}``, None for attention."""
        c = self.config
        u = RMSNorm(c.norm_eps, name="norm")(x)
        counts = None
        if self.kind == "M":
            y, scan_rms = Mamba2Mixer(c.ssm, layer=self.layer, name="mixer")(u)
            counts = {"scan_rms": scan_rms}
        elif self.kind == "E":
            y, counts = HeldExpertsMLP(c.moe, name="mixer")(u)
        elif self.kind == "*":
            # inline, not a method: flax opens a scope per method, a Pallas
            # kernel's device op takes the innermost scope's name, and the
            # trace's readers find flash attention by the block's, h_<i>
            b, s, _ = u.shape
            wq, wk, wv, wo = (w.astype(c.dtype) for w in _AttentionWeights(c, name="mixer")())
            u = u.astype(c.dtype)
            q = jnp.dot(u, wq).reshape(b, s, c.heads, c.head_dim)
            k = jnp.dot(u, wk).reshape(b, s, c.kv_heads, c.head_dim)
            v = jnp.dot(u, wv).reshape(b, s, c.kv_heads, c.head_dim)
            rep = c.heads // c.kv_heads  # KV head j serves query heads j*rep .. (j+1)*rep - 1
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
            with _span("attn.flash", scope=False):  # no scope of its own, for the same reason
                attn = dot_product_attention(q, k, v, causal=True, dtype=c.dtype)
            y = jnp.dot(attn.reshape(b, s, c.heads * c.head_dim), wo)
        else:
            raise ValueError(f"unknown block kind {self.kind!r} (M, E or *)")
        return x + y.astype(x.dtype), counts


class NemotronHLM(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array, return_hidden: bool = False):
        """``(logits float32 (B, S, V), counts)``; with ``return_hidden`` the
        final-norm states in the model dtype instead of the logits (the head
        then runs inside the chunked loss). ``counts``: ``{"moe_rows": (E
        blocks, held), "moe_absent_pairs": (E blocks,), "moe_chosen": (E
        blocks, B * S, top_k)}`` int32 and ``"ssm_scan_rms": (M blocks, B,
        heads)`` float32; a key is there if the pattern has such a block."""
        c = self.config
        x = nn.Embed(
            c.vocab_size, c.hidden, dtype=c.dtype, param_dtype=jnp.float32,
            embedding_init=nn.initializers.normal(0.02), name="embed",
        )(input_ids)
        block = nn.remat(_Block) if c.remat else _Block
        seen = {"E": [], "M": []}
        for i, kind in enumerate(c.pattern):
            x, counts = block(c, kind, i, name=f"h_{i}")(x)
            if counts is not None:
                seen[kind].append(counts)
        counts = {}
        if seen["E"]:
            counts = {f"moe_{k}": jnp.stack([e[k] for e in seen["E"]])
                      for k in ("rows", "absent_pairs", "chosen")}
        if seen["M"]:
            counts["ssm_scan_rms"] = jnp.stack([m["scan_rms"] for m in seen["M"]])
        x = RMSNorm(c.norm_eps, name="norm_f")(x)
        head = nn.Dense(
            c.vocab_size, use_bias=False, dtype=c.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.02), name="lm_head",
        )
        if return_hidden:
            head(x[:, :1])  # the head's weights exist in every init mode (dead code at run time)
            return jnp.asarray(x, c.dtype), counts
        return jnp.asarray(head(jnp.asarray(x, c.dtype)), jnp.float32), counts


def nemotron_h_loss_fn(model: NemotronHLM):
    """Causal next-token loss over ``input_ids`` (+ optional ``loss_mask``);
    beside the model state (:class:`~consensusml_tpu.train.local_sgd.LossAux`)
    ride out the expert layers' counters, summed over the round's inner steps
    into the round's metrics, and what the round's first step chose and its
    scans put out (``moe_chosen``, ``ssm_scan_rms``), as they are."""
    from consensusml_tpu.train.local_sgd import LossAux

    chunk = model.config.loss_vocab_chunk

    def loss_fn(params, model_state, batch, rng):
        ids = batch["input_ids"]
        mask = batch.get("loss_mask")
        mask = jnp.ones_like(ids[:, 1:], jnp.float32) if mask is None else mask[:, 1:]
        if chunk > 0:
            hidden, counts = model.apply({"params": params}, ids, return_hidden=True)
            loss = chunked_vocab_lm_loss(
                hidden[:, :-1], params["lm_head"]["kernel"].T, ids[:, 1:], mask, chunk=chunk
            )
        else:
            logits, counts = model.apply({"params": params}, ids)
            loss = masked_lm_loss(logits[:, :-1], ids[:, 1:], mask)
        shown = {k: counts.pop(k) for k in ("moe_chosen", "ssm_scan_rms") if k in counts}
        return loss, LossAux(model_state, counts, shown)

    return loss_fn
