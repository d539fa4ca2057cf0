"""Hybrid decoder whose blocks are of seven kinds by a pattern string.

Three families run through it. The ``nemotron_h`` family (NVIDIA Nemotron 3 Nano
30B-A3B: 52 blocks by ``MEMEM*EMEMEM*...``, 23 Mamba-2, 23 mixture-of-experts,
6 attention) and the ``qwen3_next`` family (Qwen3-Next-80B-A3B: 48 layers of
two sub-blocks each, a mixer and an expert layer, every fourth mixer softmax
attention and the others Gated DeltaNet: ``GEGEGEAE`` a period,
:func:`qwen3_next_share`), and the ``xing4_0`` family (Xing4.0-29B-A4B: 40
layers of latent attention and a dense or an expert MLP on a residual of four
hyper-connected streams, with a multi-token-prediction module:
:func:`xing4_share`). Block ``i`` is ONE mixer behind one RMSNorm and a
residual, ``x <- x + Mixer_i(RMSNorm_i(x))``, the mixer chosen by character ``i``:

- ``M`` :class:`~consensusml_tpu.models.ssm.Mamba2Mixer` (chunked SSD scan);
- ``G`` :class:`~consensusml_tpu.models.gated_delta.GatedDeltaNetMixer` (the
  chunked gated delta rule);
- ``E`` :class:`~consensusml_tpu.models.moe.HeldExpertsMLP` (told which experts
  it holds; sigmoid or softmax router, ``relu^2`` or SwiGLU experts, one shared
  expert with or without a gate: ``config.moe``);
- ``*`` grouped-query causal attention with NO rotary or learned positions
  (the ``nemotron_h`` family's attention applies none);
- ``A`` the same attention GATED: RMSNorm on each head's q and k, rotary on the
  first ``rotary_dim`` dimensions, the output times a sigmoid of a second
  query-sized projection;
- ``L`` multi-head latent attention (:mod:`~consensusml_tpu.models.mla`: low-rank
  queries, one key/value latent and one shared rotary key a token; keys
  ``nope_dim + rope_dim`` wide, values ``v_dim``, yarn's frequencies and scale);
- ``D`` a dense SwiGLU MLP ``W2 (silu(W1 u) * W3 u)``, ``dense_width`` wide.

``config.streams`` > 1 widens the residual to that many streams, ``X`` (B,
streams, S, hidden), mixed by manifold-constrained hyper-connections
(:mod:`~consensusml_tpu.models.hyper_connections`): every block reads ``u =
H_pre X``, runs its mixer on ``RMSNorm(u)`` and writes ``X' = H_res X +
H_post^T y``; the embedding is replicated into the streams and the streams are
summed before the final norm. ``streams`` = 1 is ``x + y``, the code the other
families run. ``config.mtp`` adds a multi-token-prediction module
(DeepSeek-V3's): one more layer (``L`` then ``E``) over ``[RMSNorm(Emb(t_{i+1}))
| RMSNorm(h_i)] W_eh``, ``h`` the summed streams before the final norm, whose
output goes through the SAME head against the tokens two ahead
(:func:`nemotron_h_loss_fn` adds ``mtp_lambda`` times that loss).

In the attention kinds ``*`` and ``A`` K/V are repeated to the query heads and, past the dense
threshold on a TPU, :mod:`~consensusml_tpu.models.flash_attention` is called
from the block itself (kind ``L`` too), so that the kernels' device ops carry the block's name
``h_<i>``. ``config.zero_centred_norm`` makes every RMSNorm of the residual
stream and of ``A``'s q and k ``x / rms(x) * (1 + w)`` with ``w`` from zero.

Embedding and head are untied. Parameters float32, products in
``config.dtype``, router and norms float32. ``apply`` returns ``(logits or
hidden states, counts)`` with ``counts`` the expert layers' device counters
stacked over the ``E`` blocks, and what the step shows of itself: the experts
every token chose, the size of every scan's, every delta rule's and every
latent attention's output and of every stream a block wrote
(:func:`nemotron_h_loss_fn` hands them to the round's metrics).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.models.attention import (
    apply_rope, auto_impl, dot_product_attention, rope_frequencies)
from consensusml_tpu.models.gated_delta import GatedDeltaConfig, GatedDeltaNetMixer
from consensusml_tpu.models.hyper_connections import HyperConfig, HyperConnection, hyper_post
from consensusml_tpu.models.llama import RMSNorm
from consensusml_tpu.models.losses import chunked_vocab_lm_loss, masked_lm_loss
from consensusml_tpu.models.mla import (
    LatentAttentionConfig, LatentAttentionWeights, latent_out, latent_qkv)
from consensusml_tpu.models.moe import HeldExpertsConfig, HeldExpertsMLP
from consensusml_tpu.obs import get_registry
from consensusml_tpu.models.ssm import Mamba2Config, Mamba2Mixer
from consensusml_tpu.obs import span as _span

__all__ = [
    "NemotronHConfig", "NemotronHLM", "nemotron_h_tiny", "nemotron_h_loss_fn",
    "qwen3_next_share", "qwen3_next_tiny", "xing4_share", "xing4_tiny",
]

# what a step shows of itself
FIRST_STEP_KEYS = ("moe_chosen", "ssm_scan_rms", "gdn_rms", "mla_rms", "mhc_stream_rms")
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
    dt_min: float = 0.001  # M and G
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # G
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_chunk: int = 64
    # * and A
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    rotary_dim: int = 64  # A: the leading dimensions of a head that turn
    rope_theta: float = 1e7  # A and L
    # L
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_factor: float = 1.0  # yarn: these four and mscale_all_dim
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max_len: int = 4096
    mscale_all_dim: float = 1.0
    # D
    dense_width: int = 9216
    # E
    experts: int = 128
    held: int = 128
    held_start: int = 0
    top_k: int = 6
    route_scale: float = 2.5
    expert_width: int = 1856
    shared_width: int = 3712
    score_correction: str = "zeros"  # or "centred": HeldExpertsConfig
    moe_scores: str = "sigmoid"  # these three: HeldExpertsConfig
    moe_activation: str = "relu2"
    shared_gate: bool = False
    norm_eps: float = 1e-5
    zero_centred_norm: bool = False
    out_init_std_fixed: float = 0.0  # > 0: every output matrix starts at it, no rescaling by depth
    # the residual: 1 = ``x + y``; more = that many hyper-connected streams
    streams: int = 1
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    # the multi-token-prediction module (one ``L`` + ``E`` layer) and its loss's weight
    mtp: bool = False
    mtp_lambda: float = 0.3
    remat: bool = True  # per block
    loss_vocab_chunk: int = 0  # >0: the head runs inside chunked_vocab_lm_loss
    dtype: Any = jnp.bfloat16

    @property
    def out_init_std(self) -> float:
        return self.out_init_std_fixed or 0.02 / (2.0 * self.depth_published) ** 0.5

    @property
    def gdn(self) -> GatedDeltaConfig:
        return GatedDeltaConfig(
            hidden=self.hidden, key_heads=self.gdn_key_heads, value_heads=self.gdn_value_heads,
            key_dim=self.gdn_key_dim, value_dim=self.gdn_value_dim, conv_kernel=self.conv_kernel,
            chunk=self.gdn_chunk, dt_min=self.dt_min, dt_max=self.dt_max, dt_floor=self.dt_floor,
            norm_eps=self.norm_eps, out_init_std=self.out_init_std, dtype=self.dtype,
        )

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
            score_correction=self.score_correction, scores=self.moe_scores,
            activation=self.moe_activation, shared_gate=self.shared_gate,
            out_init_std=self.out_init_std, dtype=self.dtype,
        )

    @property
    def mla(self) -> LatentAttentionConfig:
        return LatentAttentionConfig(
            hidden=self.hidden, heads=self.heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, nope_dim=self.nope_dim, rope_dim=self.rope_dim,
            v_dim=self.v_dim, rope_theta=self.rope_theta, rope_factor=self.rope_factor,
            beta_fast=self.beta_fast, beta_slow=self.beta_slow,
            original_max_len=self.original_max_len, mscale_all_dim=self.mscale_all_dim,
            norm_eps=self.norm_eps, out_init_std=self.out_init_std, dtype=self.dtype,
        )

    @property
    def hc(self) -> HyperConfig:
        return HyperConfig(
            hidden=self.hidden, streams=self.streams, sinkhorn_iters=self.sinkhorn_iters,
            eps=self.hc_eps, clamp_min=self.hc_clamp_min, clamp_max=self.hc_clamp_max,
        )

    @property
    def expert_layers(self) -> tuple[int, ...]:
        """The expert layers by block number, in their counters' order; the
        multi-token-prediction module's two blocks count on from the pattern's."""
        layers = tuple(i for i, kind in enumerate(self.pattern) if kind == "E")
        return layers + ((len(self.pattern) + 1,) if self.mtp else ())


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


def qwen3_next_share(**overrides) -> "NemotronHLM":
    """Qwen3-Next-80B-A3B's layer at its published widths, as ONE chip of a
    16-way expert-parallel deployment holds it: one period of four layers
    (``GEGEGEAE``), 32 of the 512 routed experts of each expert layer (the
    router stays 512 wide and picks 10), an eighth of the vocabulary."""
    defaults = dict(
        vocab_size=18992, hidden=2048, pattern="GEGEGEAE", conv_kernel=4,
        heads=16, kv_heads=2, head_dim=256, rotary_dim=64, rope_theta=1e7,
        experts=512, held=32, held_start=0, top_k=10, route_scale=1.0, expert_width=512,
        shared_width=512, moe_scores="softmax", moe_activation="swiglu", shared_gate=True,
        norm_eps=1e-6, zero_centred_norm=True, out_init_std_fixed=0.02,
    )
    defaults.update(overrides)
    return NemotronHLM(config=NemotronHConfig(**defaults))


def qwen3_next_tiny(**overrides) -> "NemotronHLM":
    """Test-scale :func:`qwen3_next_share` (same code path, tiny widths): 16
    experts of which 4 are held."""
    defaults = dict(
        vocab_size=64, hidden=32, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8,
        gdn_value_dim=8, gdn_chunk=8, heads=4, kv_heads=2, head_dim=16, rotary_dim=4,
        experts=16, held=4, top_k=3, expert_width=16, shared_width=16,
    )
    defaults.update(overrides)
    return qwen3_next_share(**defaults)


def xing4_share(**overrides) -> "NemotronHLM":
    """Xing4.0-29B-A4B's layer at its published widths, as ONE chip of an
    8-way expert-parallel deployment holds it: five layers (a leading dense
    one, four expert layers: ``LDLELELELE``), 8 of the 64 routed experts of
    each expert layer (the router stays 64 wide and picks 4), an eighth of the
    vocabulary, four residual streams and the multi-token-prediction module."""
    defaults = dict(
        vocab_size=16384, hidden=3584, pattern="LDLELELELE", depth_published=40,
        heads=32, rope_theta=1e4, rope_factor=64.0, beta_fast=32.0, beta_slow=1.0,
        original_max_len=4096, mscale_all_dim=1.0, dense_width=9216,
        experts=64, held=8, held_start=0, top_k=4, route_scale=2.0, expert_width=1024,
        shared_width=1024, moe_scores="sigmoid", moe_activation="swiglu", shared_gate=False,
        norm_eps=1e-6, out_init_std_fixed=0.02, streams=4, mtp=True, mtp_lambda=0.3,
    )
    defaults.update(overrides)
    return NemotronHLM(config=NemotronHConfig(**defaults))


def xing4_tiny(**overrides) -> "NemotronHLM":
    """Test-scale :func:`xing4_share` (same code path, tiny widths): two
    layers, 8 experts of which 4 are held."""
    defaults = dict(
        vocab_size=64, hidden=32, pattern="LDLE", heads=4, q_lora_rank=16, kv_lora_rank=12,
        nope_dim=8, rope_dim=4, v_dim=8, original_max_len=16, rope_factor=4.0, beta_fast=4.0,
        dense_width=48, experts=8, held=4, top_k=3, expert_width=16, shared_width=16,
    )
    defaults.update(overrides)
    return xing4_share(**defaults)


class _RMSNorm0(nn.Module):
    """Zero-centred RMSNorm: ``x / rms(x) * (1 + scale)``, ``scale`` from zero."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        xf = jnp.asarray(x, jnp.float32)
        scale = self.param("scale", nn.initializers.zeros_init(), (x.shape[-1],), jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (y * (1.0 + scale)).astype(x.dtype)


def _norm(config: "NemotronHConfig", name: str):
    return (_RMSNorm0 if config.zero_centred_norm else RMSNorm)(config.norm_eps, name=name)


class _AttentionWeights(nn.Module):
    """An attention block's matrices, held under the block's ``mixer`` like the
    other kinds' weights; the block itself does the arithmetic. ``gated``
    (kind ``A``): ``q`` is twice as wide, per head ``[q | gate]``, and each
    head's q and k have a zero-centred norm weight."""

    config: NemotronHConfig
    gated: bool = False

    @nn.compact
    def __call__(self):
        c = self.config
        normal, f32 = nn.initializers.normal, jnp.float32
        d_q, d_kv = c.heads * c.head_dim, c.kv_heads * c.head_dim
        weights = (
            self.param("q", normal(0.02), (c.hidden, d_q * (2 if self.gated else 1)), f32),
            self.param("k", normal(0.02), (c.hidden, d_kv), f32),
            self.param("v", normal(0.02), (c.hidden, d_kv), f32),
            self.param("o", normal(c.out_init_std), (d_q, c.hidden), f32),
        )
        if self.gated:
            zeros = nn.initializers.zeros_init()
            weights += (
                self.param("q_norm", zeros, (c.head_dim,), f32),
                self.param("k_norm", zeros, (c.head_dim,), f32),
            )
        return weights


class _DenseMLP(nn.Module):
    """Kind ``D``: ``W2 (silu(W1 u) * W3 u)``."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        c, f32, normal = self.config, jnp.float32, nn.initializers.normal
        w1 = self.param("w1", normal(0.02), (c.hidden, c.dense_width), f32)
        w3 = self.param("w3", normal(0.02), (c.hidden, c.dense_width), f32)
        w2 = self.param("w2", normal(c.out_init_std), (c.dense_width, c.hidden), f32)
        with _span("mlp.dense"):
            u = u.astype(c.dtype)
            gate = jnp.dot(u, w1.astype(c.dtype), preferred_element_type=f32)
            up = jnp.dot(u, w3.astype(c.dtype), preferred_element_type=f32)
            return jnp.dot((jax.nn.silu(gate) * up).astype(c.dtype), w2.astype(c.dtype))


class _Block(nn.Module):
    config: NemotronHConfig
    kind: str
    layer: int

    @nn.compact
    def __call__(self, x):
        """``(x + mixer(norm(x)), counts)``; ``counts`` is an E block's
        counters, an M block's ``{"scan_rms": ...}``, a G or L block's
        ``{"out_rms": ...}``, None for the other attention kinds and for D.
        With ``config.streams`` > 1 ``x`` is (B, streams, S, hidden), the mixer
        reads ``H_pre x`` and the block returns ``H_res x + H_post^T y``;
        ``counts`` then has the written streams' sizes too (``stream_rms``)."""
        c = self.config
        hyper = c.streams > 1
        if hyper:
            streams = x
            # the streams come back as the write is to read them: the kernels' backward pass adds dX up through them
            x, h_res, h_post, streams = HyperConnection(c.hc, layer=self.layer, name="hc")(streams, return_streams=True)
        u = _norm(c, "norm")(x)
        counts = None
        if self.kind == "M":
            y, scan_rms = Mamba2Mixer(c.ssm, layer=self.layer, name="mixer")(u)
            counts = {"scan_rms": scan_rms}
        elif self.kind == "G":
            y, out_rms = GatedDeltaNetMixer(c.gdn, layer=self.layer, name="mixer")(u)
            counts = {"out_rms": out_rms}
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
        elif self.kind == "A":  # inline for the same reason
            b, s, _ = u.shape
            f32, hd, rot = jnp.float32, c.head_dim, c.rotary_dim
            wq, wk, wv, wo, q_norm, k_norm = _AttentionWeights(c, gated=True, name="mixer")()
            u = u.astype(c.dtype)
            project = lambda w, heads, width: jnp.dot(
                u, w.astype(c.dtype), preferred_element_type=f32).reshape(b, s, heads, width)
            qg, k = project(wq, c.heads, 2 * hd), project(wk, c.kv_heads, hd)
            v = project(wv, c.kv_heads, hd).astype(c.dtype)
            gate = qg[..., hd:]
            with _span("attn.qk_norm_rope"):
                table = rope_frequencies(rot, s, c.rope_theta)

                def normed_turned(t, w):  # float32: the norm over the head, then the rotary part
                    t = t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + c.norm_eps)
                    t = t * (1.0 + w)
                    turned = apply_rope(t[..., :rot], table, rotate_half=True)
                    return jnp.concatenate([turned, t[..., rot:]], axis=-1).astype(c.dtype)

                q, k = normed_turned(qg[..., :hd], q_norm), normed_turned(k, k_norm)
            rep = c.heads // c.kv_heads
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
            with _span("attn.flash", scope=False):
                attn = dot_product_attention(q, k, v, causal=True, dtype=c.dtype)
            with _span("attn.gate"):
                attn = (attn.astype(f32) * _attn_gate(gate)).astype(c.dtype)
            y = jnp.dot(attn.reshape(b, s, c.heads * hd), wo.astype(c.dtype))
        elif self.kind == "L":  # inline for the same reason
            m = c.mla
            weights = LatentAttentionWeights(m, name="mixer")()
            q, k, v = latent_qkv(u, weights, m)
            flash = auto_impl(q, k, v) == "flash"
            get_registry().counter(
                "consensusml_mla_flash_impl_total",
                "latent-attention blocks traced, by what ran their attention",
                labels={"layer": str(self.layer), "impl": "kernel" if flash else "xla"},
            ).inc()
            with _span("attn.flash", scope=False):
                attn = dot_product_attention(
                    q, k, v, causal=True, dtype=c.dtype, scale=m.score_scale)
            y, out_rms = latent_out(attn, weights, m)
            counts = {"out_rms": out_rms}
        elif self.kind == "D":
            y = _DenseMLP(c, name="mixer")(u)
        else:
            raise ValueError(f"unknown block kind {self.kind!r} (M, G, E, *, A, L or D)")
        if not hyper:
            return x + y.astype(x.dtype), counts
        streams, stream_rms = hyper_post(streams, h_res, h_post, y)
        return streams, {**(counts or {}), "stream_rms": stream_rms}


def _attn_gate(gate):
    """Kind ``A``'s output gate, per element. The planted-fault tests make it ones."""
    return jax.nn.sigmoid(gate)


class NemotronHLM(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array, return_hidden: bool = False):
        """``(logits float32 (B, S, V), counts)``; with ``return_hidden`` the
        final-norm states in the model dtype instead of the logits (the head
        then runs inside the chunked loss). ``counts``: ``{"moe_rows": (E
        blocks, held), "moe_absent_pairs": (E blocks,), "moe_chosen": (E
        blocks, B * S, top_k)}`` int32, ``"ssm_scan_rms": (M blocks, B, heads)``
        and ``"gdn_rms": (G blocks, B, value heads)`` float32; a key is there if
        the pattern has such a block."""
        c = self.config
        embed = nn.Embed(
            c.vocab_size, c.hidden, dtype=c.dtype, param_dtype=jnp.float32,
            embedding_init=nn.initializers.normal(0.02), name="embed",
        )
        x = _into_streams(embed(input_ids), c)
        block = nn.remat(_Block) if c.remat else _Block
        seen = []  # (kind, counts) of every block that shows something, in order
        for i, kind in enumerate(c.pattern):
            x, counts = block(c, kind, i, name=f"h_{i}")(x)
            if counts is not None:
                seen.append((kind, counts))
        x = _summed_streams(x, c)
        mtp = None
        if c.mtp:
            mtp, shown = _MultiTokenPrediction(c, block, name="mtp")(x, embed(_next_ids(input_ids)))
            seen.extend(shown)
        stacked = lambda kind, key: jnp.stack([n[key] for k, n in seen if k == kind])
        kinds = {kind for kind, _ in seen}
        counts = {}
        if "E" in kinds:
            counts = {f"moe_{k}": stacked("E", k) for k in ("rows", "absent_pairs", "chosen")}
        if "M" in kinds:
            counts["ssm_scan_rms"] = stacked("M", "scan_rms")
        if "G" in kinds:
            counts["gdn_rms"] = stacked("G", "out_rms")
        if "L" in kinds:
            counts["mla_rms"] = stacked("L", "out_rms")
        if c.streams > 1:
            counts["mhc_stream_rms"] = jnp.stack([n["stream_rms"] for _, n in seen])
        x = _norm(c, "norm_f")(x)
        head = nn.Dense(
            c.vocab_size, use_bias=False, dtype=c.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.02), name="lm_head",
        )
        if return_hidden:
            head(x[:, :1])  # the head's weights exist in every init mode (dead code at run time)
            out = jnp.asarray(x, c.dtype)
            return ((out, mtp) if c.mtp else out), counts
        logits = lambda h: jnp.asarray(head(jnp.asarray(h, c.dtype)), jnp.float32)
        return ((logits(x), logits(mtp)) if c.mtp else logits(x)), counts


def _into_streams(x, c: NemotronHConfig):
    """``x`` (B, S, hidden) in every stream, (B, streams, S, hidden); as it is
    with one stream."""
    if c.streams == 1:
        return x
    return jnp.broadcast_to(x[:, None], x.shape[:1] + (c.streams,) + x.shape[1:])


def _summed_streams(x, c: NemotronHConfig):
    """The streams summed back into (B, S, hidden), in float32."""
    if c.streams == 1:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=1).astype(c.dtype)


def _next_ids(ids):
    """Every position's NEXT token (the row's first under its last position,
    which the multi-token loss masks out)."""
    return jnp.roll(ids, -1, axis=1)


class _MultiTokenPrediction(nn.Module):
    """DeepSeek-V3's module, depth 1: ``h'_i = [RMSNorm(Emb(t_{i+1})) |
    RMSNorm(h_i)] W_eh``, one more layer (``L`` then ``E``, hyper-connected like
    the decoder's, its own weights) and a norm; the decoder's head reads it.
    Runs over all ``S`` positions so that shapes tile; the loss masks the last
    two. Returns the normed states (B, S, hidden) and what its blocks showed."""

    config: NemotronHConfig
    block: Any  # _Block, rematted or not, as the decoder's

    @nn.compact
    def __call__(self, h, next_embedding):
        c = self.config
        w_eh = self.param(
            "eh_proj", nn.initializers.normal(0.02), (2 * c.hidden, c.hidden), jnp.float32)
        with _span("mtp.embed_proj"):
            both = jnp.concatenate(
                [_norm(c, "enorm")(next_embedding), _norm(c, "hnorm")(h)], axis=-1)
            x = jnp.dot(both.astype(c.dtype), w_eh.astype(c.dtype))
        with _span("mtp.block"):
            x = _into_streams(x, c)
            seen = []
            for j, kind in enumerate("LE"):
                x, counts = self.block(c, kind, len(c.pattern) + j, name=f"h_{j}")(x)
                seen.append((kind, counts))
            x = _summed_streams(x, c)
        return jnp.asarray(_norm(c, "norm")(x), c.dtype), seen


def nemotron_h_loss_fn(model: NemotronHLM):
    """Causal next-token loss over ``input_ids`` (+ optional ``loss_mask``);
    beside the model state (:class:`~consensusml_tpu.train.local_sgd.LossAux`)
    ride out the expert layers' counters, summed over the round's inner steps
    into the round's metrics, and what the round's first step chose and its
    scans, delta rules, latent attentions and streams put out
    (:data:`FIRST_STEP_KEYS`), as they are. With ``config.mtp`` the loss is
    ``next-token + mtp_lambda x two-ahead``, the second through the SAME head
    over the module's states, and ``mtp_loss`` rides out with the counters
    (summed like them: divide by the round's inner steps)."""
    from consensusml_tpu.train.local_sgd import LossAux

    config = model.config
    chunk = config.loss_vocab_chunk

    def loss_fn(params, model_state, batch, rng):
        ids = batch["input_ids"]
        mask = batch.get("loss_mask")
        mask = jnp.ones_like(ids[:, 1:], jnp.float32) if mask is None else mask[:, 1:]
        out, counts = model.apply({"params": params}, ids, return_hidden=chunk > 0)
        main, ahead = out if config.mtp else (out, None)
        head = lambda: params["lm_head"]["kernel"].T
        if chunk > 0:
            loss = chunked_vocab_lm_loss(main[:, :-1], head(), ids[:, 1:], mask, chunk=chunk)
        else:
            loss = masked_lm_loss(main[:, :-1], ids[:, 1:], mask)
        if config.mtp:
            with _span("mtp.loss"):  # every position, the last two masked out: the shapes tile
                labels = jnp.roll(ids, -2, axis=1)
                two_on = jnp.pad(mask[:, 1:], ((0, 0), (0, 2)))
                if chunk > 0:
                    mtp_loss = chunked_vocab_lm_loss(ahead, head(), labels, two_on, chunk=chunk)
                else:
                    mtp_loss = masked_lm_loss(ahead, labels, two_on)
            loss = loss + config.mtp_lambda * mtp_loss
            counts["mtp_loss"] = mtp_loss
        shown = {k: counts.pop(k) for k in FIRST_STEP_KEYS if k in counts}
        return loss, LossAux(model_state, counts, shown)

    return loss_fn
