"""Mamba-2 mixer with the chunked state-space-dual (SSD) scan.

The state-space layer of the hybrid decoders (:mod:`consensusml_tpu.models.
nemotron_h`; Dao & Gu 2024, "Transformers are SSMs"). Per head the layer is
the recurrence ``S_t = a_t S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``
with a scalar decay ``a_t = exp(dt_t A)``; :func:`ssd_chunked` computes it in
its dual form, a chunk of ``chunk`` tokens at a time, as batched matrix
products that XLA puts on the MXU, forward and (by autodiff of the same
products) backward:

- within a chunk ``(L o C B^T) X`` with ``L[i, j] = exp(sum_{j<k<=i} dt_k A)``
  the lower-triangular decay;
- one state per chunk, ``sum_j exp(cum_last - cum_j) dt_j x_j (x) B_j``;
- a scan over the chunk states (float32) that carries them across chunks;
- ``C . state`` for what a chunk inherits from the ones before it.

The decays' cumulative sums and the carried state are float32; the products
take operands in the compute dtype and accumulate in float32. There is no
Pallas kernel here yet (ROADMAP B-i.5): every operation is XLA's.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.obs import get_registry
from consensusml_tpu.obs import span as _span

__all__ = ["Mamba2Config", "Mamba2Mixer", "ssd_chunked", "carried_states"]


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    hidden: int = 2688
    heads: int = 64
    head_dim: int = 64
    groups: int = 8
    state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    norm_eps: float = 1e-5
    out_init_std: float = 0.02  # the caller scales it by 1/sqrt(2 * depth)
    dtype: Any = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state


def carried_states(states: jax.Array, chunk_decay: jax.Array) -> jax.Array:
    """The state that ENTERS each chunk. ``states`` (b, c, ..., p, n) is what
    each chunk adds, ``chunk_decay`` (b, c, ...) the decay across each whole
    chunk; float32. ``entering[0] = 0``, ``entering[c+1] = decay[c] *
    entering[c] + states[c]``."""

    def step(carry, inp):
        added, decay = inp
        return carry * decay[..., None, None] + added, carry

    _, entering = jax.lax.scan(
        step,
        jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    return jnp.moveaxis(entering, 0, 1)


def ssd_chunked(
    x: jax.Array,  # (b, t, h, p)
    dt: jax.Array,  # (b, t, h) float32, after softplus
    a: jax.Array,  # (h,) float32, negative
    b_in: jax.Array,  # (b, t, g, n)
    c_in: jax.Array,  # (b, t, g, n)
    *,
    chunk: int = 128,
) -> jax.Array:
    """``y_t = S_t C_t`` of the recurrence above (without the ``D x`` skip),
    float32, (b, t, h, p). Head ``h`` reads group ``h // (heads / groups)``.
    ``t`` need not be a multiple of ``chunk``: the tail is padded with steps
    of ``dt = 0``, which leave the state as it is."""
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2:]
    r = h // g
    dtype = x.dtype
    pad = (-t) % chunk
    if pad:
        x, dt, b_in, c_in = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b_in, c_in)
        )
    c = (t + pad) // chunk
    f32 = jnp.float32
    xs = x.reshape(bsz, c, chunk, g, r, p)
    dts = dt.astype(f32).reshape(bsz, c, chunk, g, r)
    bs = b_in.reshape(bsz, c, chunk, g, n)
    cs = c_in.reshape(bsz, c, chunk, g, n)
    # cumulative log-decay inside each chunk, (b, c, g, r, q), float32
    cum = jnp.cumsum(jnp.moveaxis(dts * a.astype(f32).reshape(g, r), 2, -1), axis=-1)
    xdt = (xs * dts[..., None]).astype(dtype)  # dt_j x_j

    # within a chunk: (L o C B^T) X
    cb = jnp.einsum("bcign,bcjgn->bcgij", cs, bs, preferred_element_type=f32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    mixed = (decay * cb[:, :, :, None]).astype(dtype)  # (b, c, g, r, i, j)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mixed, xdt, preferred_element_type=f32)

    # one state per chunk, then the states carried across chunks
    to_end = jnp.exp(cum[..., -1:] - cum)  # (b, c, g, r, q)
    weighted = (xdt * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype)
    states = jnp.einsum("bcjgrp,bcjgn->bcgrpn", weighted, bs, preferred_element_type=f32)
    entering = carried_states(states, jnp.exp(cum[..., -1]))

    # what a chunk inherits: C . state, decayed to each position
    inherited = jnp.einsum(
        "bcign,bcgrpn->bcigrp", cs, entering.astype(dtype), preferred_element_type=f32
    )
    y = y + inherited * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y.reshape(bsz, c * chunk, h, p)[:, :t]


def _dt_bias_init(config: Mamba2Config):
    def init(key, shape, dtype=jnp.float32):
        lo, hi = jnp.log(config.dt_min), jnp.log(config.dt_max)
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (hi - lo) + lo)
        dt = jnp.maximum(dt, config.dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1

    return init


class Mamba2Mixer(nn.Module):
    """``u (b, t, hidden) -> ((b, t, hidden), scan_rms (b, heads))``;
    parameters float32, products in ``config.dtype``. ``scan_rms`` is the
    root mean square, per head, of what the scan put out (``S C + D x``): a
    device value that a comparison with the plain recurrence reads. ``layer``
    labels the trace-time chunk counter."""

    config: Mamba2Config
    layer: int = 0

    @nn.compact
    def __call__(self, u: jax.Array) -> tuple[jax.Array, jax.Array]:
        c = self.config
        bsz, t, _ = u.shape
        d_in, gn, kw = c.d_inner, c.groups * c.state, c.conv_kernel
        f32 = jnp.float32
        normal = nn.initializers.normal
        w_in = self.param("in_proj", normal(0.02), (c.hidden, 2 * d_in + 2 * gn + c.heads), f32)
        conv_w = self.param(
            "conv_kernel", lambda k, s, d=f32: jax.random.uniform(k, s, d, -0.5, 0.5),
            (kw, c.conv_dim), f32,
        )
        conv_b = self.param("conv_bias", nn.initializers.zeros_init(), (c.conv_dim,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init(c), (c.heads,), f32)
        a_log = self.param(
            "A_log", lambda k, s, d=f32: jnp.log(jax.random.uniform(k, s, d, 1.0, 16.0)),
            (c.heads,), f32,
        )
        d_skip = self.param("D", nn.initializers.ones_init(), (c.heads,), f32)
        gate_w = self.param("gate_norm", nn.initializers.ones_init(), (d_in,), f32)
        w_out = self.param("out_proj", normal(c.out_init_std), (d_in, c.hidden), f32)

        with _span("ssm.in_proj"):
            proj = jnp.dot(
                u.astype(c.dtype), w_in.astype(c.dtype), preferred_element_type=f32
            )
            z = proj[..., :d_in].astype(c.dtype)
            xbc = proj[..., d_in : d_in + c.conv_dim]
            dt = jax.nn.softplus(proj[..., d_in + c.conv_dim :] + dt_bias)  # float32
        with _span("ssm.conv"):
            # depthwise, causal: conv[t] = sum_j w[j] xBC[t - (K-1) + j] + b
            padded = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
            conv = sum(padded[:, j : j + t] * conv_w[j] for j in range(kw)) + conv_b
            xbc = jax.nn.silu(conv).astype(c.dtype)
        x = xbc[..., :d_in].reshape(bsz, t, c.heads, c.head_dim)
        b_in = xbc[..., d_in : d_in + gn].reshape(bsz, t, c.groups, c.state)
        c_in = xbc[..., d_in + gn :].reshape(bsz, t, c.groups, c.state)
        with _span("ssm.scan", chunk=c.chunk):
            get_registry().counter(
                "consensusml_ssm_chunks_total",
                "chunks of the SSD scan traced (rows x chunks a call), by layer",
                labels={"layer": str(self.layer)},
            ).inc(bsz * -(-t // c.chunk))
            y = ssd_chunked(x, dt, -jnp.exp(a_log), b_in, c_in, chunk=c.chunk)
            y = y + d_skip[:, None] * x.astype(f32)
            scan_rms = jnp.sqrt(jnp.mean(y * y, axis=(1, 3)))
        with _span("ssm.gate_norm"):
            # gate first, then RMSNorm over each of the `groups` slices of d_inner
            y = y.reshape(bsz, t, d_in) * jax.nn.silu(z.astype(f32))
            yg = y.reshape(bsz, t, c.groups, d_in // c.groups)
            yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + c.norm_eps)
            y = (yg.reshape(bsz, t, d_in) * gate_w).astype(c.dtype)
        with _span("ssm.out_proj"):
            out = jnp.dot(y, w_out.astype(c.dtype), preferred_element_type=f32).astype(c.dtype)
        return out, scan_rms
