"""Gated DeltaNet mixer: linear attention by the gated delta rule, in chunks.

The linear-attention layer of the decoders whose period is three of these to
one softmax attention (:mod:`consensusml_tpu.models.nemotron_h`, kind ``G``;
Yang, Kautz & Hatamizadeh 2024, "Gated Delta Networks"). Per value head the
layer keeps a (key width x value width) float32 state and, token by token,

    S <- exp(g_t) S;   S <- S + k_t (x) beta_t (v_t - S^T k_t);   o_t = S^T q_t

with ``g_t <= 0`` a log-decay and ``beta_t`` in (0, 1): the state forgets by
``exp(g_t)`` and then REPLACES what it held under key ``k_t`` by a step
``beta_t`` towards ``v_t``. Unlike the state-space dual of
:mod:`~consensusml_tpu.models.ssm` the update multiplies the state by
``(I - beta_t k_t k_t^T)``, so a chunk is not a decay triangle alone:
:func:`gated_delta_chunked` computes it ``chunk`` tokens at a time as batched
matrix products (forward, and backward by autodiff of the same products):

- within a chunk, with ``gamma`` the cumulative log-decay and ``Gamma[i, j] =
  exp(gamma_i - gamma_j)``: ``T = (I + tril(diag(beta) (K K^T o Gamma), -1))^-1``,
  ``W = T diag(beta) (K o e^gamma)``, ``U = T diag(beta) V``; the inverse of the
  unit lower-triangular matrix is a product of ``log2(chunk)`` factors
  (:func:`unit_lower_inverse`), float32 at ``Precision.HIGHEST``;
- across chunks a ``lax.scan`` carries ``S`` (float32): what the chunk's
  tokens really write is ``V' = U - W S``, their outputs ``(Q o e^gamma) S +
  (Q K^T o Gamma) V'``, and ``S <- e^{gamma_L} S + (K o e^{gamma_L - gamma})^T V'``.

The cumulative sums, the decays, ``T`` and the carried state are float32; the
other products take operands in the compute dtype and accumulate in float32.
XLA schedules all of it: no kernel yet (PERF.md section 7).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.models.ssm import _dt_bias_init  # the step's initialiser is Mamba-2's
from consensusml_tpu.obs import get_registry
from consensusml_tpu.obs import span as _span

__all__ = ["GatedDeltaConfig", "GatedDeltaNetMixer", "gated_delta_chunked", "unit_lower_inverse"]

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GatedDeltaConfig:
    hidden: int = 2048
    key_heads: int = 16
    value_heads: int = 32  # value head j reads key head j // (value_heads / key_heads)
    key_dim: int = 128
    value_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 64
    dt_min: float = 0.001  # the step is drawn log-uniform in [dt_min, dt_max], as Mamba-2's
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    norm_eps: float = 1e-6
    out_init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_heads * self.key_dim + self.value_heads * self.value_dim


def unit_lower_inverse(strict: jax.Array) -> jax.Array:
    """``(I - N)^-1`` for ``N = strict`` (..., c, c) STRICTLY lower triangular,
    float32: ``N`` is nilpotent (``N^c = 0``), so the Neumann series ends and
    factors as ``(I + N)(I + N^2)(I + N^4)...`` — ``log2(c)`` factors, each a
    squaring and a product at full float32 precision; no row-by-row loop."""
    c = strict.shape[-1]
    mm = lambda a, b: jnp.matmul(a, b, precision=_HIGHEST)
    out = jnp.eye(c, dtype=strict.dtype) + strict
    power, span = strict, 2
    while span < c:  # ``out`` holds the series up to N^(span - 1)
        power = mm(power, power)
        out = out + mm(out, power)
        span *= 2
    return out


def _carried(state):
    """What a chunk reads of the state carried to it. The identity; the
    planted-fault tests make it zeros (the state lost at every chunk boundary)."""
    return state


def gated_delta_chunked(
    q: jax.Array,  # (b, t, h, dk): normalised and scaled by the caller
    k: jax.Array,  # (b, t, h, dk): normalised
    v: jax.Array,  # (b, t, h, dv)
    g: jax.Array,  # (b, t, h) float32 log-decay, <= 0
    beta: jax.Array,  # (b, t, h) float32, in (0, 1)
    *,
    chunk: int = 64,
) -> jax.Array:
    """``o_t = S_t^T q_t`` of the recurrence above, float32, (b, t, h, dv), the
    state zero before each row's first token. ``t`` need not be a multiple of
    ``chunk``: the tail is padded with tokens of ``beta = 0`` and ``g = 0``,
    which leave the state as it is."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    dtype, f32 = q.dtype, jnp.float32
    pad = (-t) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta)
        )
    c = (t + pad) // chunk
    # (b, h, c, L, ...): heads beside the batch, a chunk's tokens last but one
    by_chunk = lambda x: jnp.moveaxis(x.reshape((bsz, c, chunk) + x.shape[2:]), 3, 1)
    qs, ks, vs = by_chunk(q), by_chunk(k), by_chunk(v)
    gs, betas = by_chunk(g.astype(f32)), by_chunk(beta.astype(f32))
    # cumulative log-decay inside each chunk, as a product with a triangle of
    # ones: on a TPU ``jnp.cumsum`` is a reduce-window (PERF.md section 6, PR 30)
    gamma = jnp.einsum(
        "bhcj,ji->bhci", gs, jnp.triu(jnp.ones((chunk, chunk), f32)), precision=_HIGHEST
    )
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # Gamma[i, j] = exp(gamma_i - gamma_j) for j <= i (the exponent never positive), else 0
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    kk = jnp.einsum("bhcid,bhcjd->bhcij", ks, ks, preferred_element_type=f32)
    strict = jnp.where(jnp.tril(lower, -1), betas[..., None] * kk * decay, 0.0)
    solve = unit_lower_inverse(-strict).astype(dtype)  # T
    up = jnp.exp(gamma)[..., None]  # e^gamma, (b, h, c, L, 1)
    k_beta = ks.astype(f32) * betas[..., None]
    w = jnp.einsum("bhcij,bhcjd->bhcid", solve, (k_beta * up).astype(dtype), preferred_element_type=f32)
    u = jnp.einsum(
        "bhcij,bhcjd->bhcid", solve, (vs.astype(f32) * betas[..., None]).astype(dtype),
        preferred_element_type=f32,
    )
    qk = jnp.einsum("bhcid,bhcjd->bhcij", qs, ks, preferred_element_type=f32)
    within = (qk * decay).astype(dtype)  # (Q K^T o Gamma), the diagonal kept
    q_up = (qs.astype(f32) * up).astype(dtype)
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None]  # e^{gamma_L - gamma}
    k_end = (ks.astype(f32) * to_end).astype(dtype)
    chunk_decay = jnp.exp(gamma[..., -1])  # (b, h, c)

    def step(state, inp):
        w_c, u_c, q_c, within_c, k_c, decay_c = inp
        seen = _carried(state).astype(dtype)
        written = u_c - jnp.einsum("bhid,bhde->bhie", w_c.astype(dtype), seen, preferred_element_type=f32)
        out = jnp.einsum("bhid,bhde->bhie", q_c, seen, preferred_element_type=f32) + jnp.einsum(
            "bhij,bhje->bhie", within_c, written.astype(dtype), preferred_element_type=f32
        )
        state = _carried(state) * decay_c[..., None, None] + jnp.einsum(
            "bhid,bhie->bhde", k_c, written.astype(dtype), preferred_element_type=f32
        )
        return state, out

    chunk_major = lambda x: jnp.moveaxis(x, 2, 0)
    # inside a checked shard_map the carried state varies as the inputs do
    state0 = jax.lax.pcast(jnp.zeros((bsz, h, dk, dv), f32), tuple(jax.typeof(w).vma), to="varying")
    _, out = jax.lax.scan(
        step,
        state0,
        tuple(chunk_major(x) for x in (w, u, q_up, within, k_end, chunk_decay)),
    )
    # (c, b, h, L, dv) -> (b, t, h, dv)
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(bsz, c * chunk, h, dv)[:, :t]


class GatedDeltaNetMixer(nn.Module):
    """``u (b, t, hidden) -> ((b, t, hidden), out_rms (b, value_heads))``;
    parameters float32, products in ``config.dtype``. ``out_rms`` is the root
    mean square, per value head, of what the delta rule put out (``o``): a
    device value that a comparison with the plain recurrence reads. ``layer``
    labels the trace-time chunk counter."""

    config: GatedDeltaConfig
    layer: int = 0

    @nn.compact
    def __call__(self, u: jax.Array) -> tuple[jax.Array, jax.Array]:
        c = self.config
        bsz, t, _ = u.shape
        kh, vh, dk, dv, kw = c.key_heads, c.value_heads, c.key_dim, c.value_dim, c.conv_kernel
        r = vh // kh
        f32 = jnp.float32
        normal = nn.initializers.normal
        # per key head [q dk | k dk | v r x dv | z r x dv]; per key head [b r | a r]
        w_qkvz = self.param("in_proj_qkvz", normal(0.02), (c.hidden, 2 * kh * dk + 2 * vh * dv), f32)
        w_ba = self.param("in_proj_ba", normal(0.02), (c.hidden, 2 * vh), f32)
        conv_w = self.param(
            "conv_kernel", lambda key, s, d=f32: jax.random.uniform(key, s, d, -0.5, 0.5),
            (kw, c.conv_dim), f32,
        )
        dt_bias = self.param("dt_bias", _dt_bias_init(c), (vh,), f32)
        a_log = self.param(
            "A_log", lambda key, s, d=f32: jnp.log(jax.random.uniform(key, s, d, 1.0, 16.0)),
            (vh,), f32,
        )
        norm_w = self.param("norm", nn.initializers.ones_init(), (dv,), f32)
        w_out = self.param("out_proj", normal(c.out_init_std), (vh * dv, c.hidden), f32)

        with _span("gdn.in_proj"):
            x = u.astype(c.dtype)
            qkvz = jnp.dot(x, w_qkvz.astype(c.dtype), preferred_element_type=f32)
            qkvz = qkvz.reshape(bsz, t, kh, 2 * dk + 2 * r * dv)
            ba = jnp.dot(x, w_ba.astype(c.dtype), preferred_element_type=f32).reshape(bsz, t, kh, 2 * r)
            z = qkvz[..., 2 * dk + r * dv :].reshape(bsz, t, vh, dv).astype(c.dtype)
            beta = jax.nn.sigmoid(ba[..., :r]).reshape(bsz, t, vh)
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., r:].reshape(bsz, t, vh) + dt_bias)
        with _span("gdn.conv"):
            # depthwise, causal, no bias, over [q | k | v] each flattened over its heads
            mixed = jnp.concatenate(
                [
                    qkvz[..., :dk].reshape(bsz, t, kh * dk),
                    qkvz[..., dk : 2 * dk].reshape(bsz, t, kh * dk),
                    qkvz[..., 2 * dk : 2 * dk + r * dv].reshape(bsz, t, vh * dv),
                ],
                axis=-1,
            )
            padded = jnp.pad(mixed, ((0, 0), (kw - 1, 0), (0, 0)))
            mixed = jax.nn.silu(sum(padded[:, j : j + t] * conv_w[j] for j in range(kw)))
        with _span("gdn.scan", chunk=c.chunk):
            get_registry().counter(
                "consensusml_gdn_chunks_total",
                "chunks of the gated delta rule traced (rows x chunks a call), by layer",
                labels={"layer": str(self.layer)},
            ).inc(bsz * -(-t // c.chunk))
            q = mixed[..., : kh * dk].reshape(bsz, t, kh, dk)
            k = mixed[..., kh * dk : 2 * kh * dk].reshape(bsz, t, kh, dk)
            v = mixed[..., 2 * kh * dk :].reshape(bsz, t, vh, dv).astype(c.dtype)
            unit = lambda y: y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
            q = jnp.repeat((unit(q) * dk**-0.5).astype(c.dtype), r, axis=2)
            k = jnp.repeat(unit(k).astype(c.dtype), r, axis=2)
            o = gated_delta_chunked(q, k, v, g, beta, chunk=c.chunk)
            out_rms = jnp.sqrt(jnp.mean(o * o, axis=(1, 3)))
        with _span("gdn.gate_norm"):
            # RMSNorm over each head's value_dim first, the gate second
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.norm_eps)
            y = (o * norm_w * jax.nn.silu(z.astype(f32))).astype(c.dtype)
        with _span("gdn.out_proj"):
            out = jnp.dot(
                y.reshape(bsz, t, vh * dv), w_out.astype(c.dtype), preferred_element_type=f32
            ).astype(c.dtype)
        return out, out_rms
