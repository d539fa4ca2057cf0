"""Manifold-constrained hyper-connections: a residual of ``n`` streams.

A token's residual is ``X`` in R^{n x hidden} instead of one vector. A sub-block
``F`` behind its norm ``N`` reads ONE mixed vector and writes back to all ``n``:

    x~     = vec(X) / sqrt(mean(vec(X)^2) + eps)                     (float32)
    H~pre  = a_pre  (x~ Phi_pre)  + b_pre          H_pre  = sigmoid(H~pre)      (n,)
    H~post = a_post (x~ Phi_post) + b_post         H_post = 2 sigmoid(H~post)   (n,)
    H~res  = a_res  mat(x~ Phi_res) + B_res        M_0 = exp(clip(H~res, lo, hi))   (n, n)
    M_t    = cols(rows(M_{t-1})), rows(M) = M / (row sums + eps), cols likewise;  H_res = M_T
    u = H_pre X          y = F(N(u))          X' = H_res X + H_post^T y

``H_res`` is made doubly stochastic by ``T`` Sinkhorn-Knopp iterations, per
token and per sub-block (DeepSeek, "mHC: Manifold-Constrained Hyper-
Connections", arXiv:2512.24880; the replication of the embedding into the
streams and their sum at the output are Zhu et al.'s "Hyper-Connections",
arXiv:2409.19606, and the decoder's). Plain XLA, float32 throughout: the maps
are a (tokens, n x hidden) by (n x hidden, 2n + n^2) product at ``highest``
precision, the rest is elementwise over the streams.

Layout: ``X`` is (B, n, S, hidden), the streams OUTSIDE the rows, so that a
stream is a well-tiled (S, hidden) matrix (a 4-row second-minor dimension
would be padded fourfold in HBM); the per-token maps are carried with the
tokens minor, ``H_res`` as (n, n, B, S), so that Sinkhorn's divisions run over
full lanes. Spans: ``mhc.maps``, ``mhc.sinkhorn(iters=)``, ``mhc.pre`` in
:class:`HyperConnection`, ``mhc.post`` in :func:`hyper_post`.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from consensusml_tpu.obs import get_registry
from consensusml_tpu.obs import span as _span

__all__ = ["HyperConfig", "HyperConnection", "hyper_post", "sinkhorn"]


@dataclasses.dataclass(frozen=True)
class HyperConfig:
    hidden: int = 3584
    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0

    @property
    def maps(self) -> int:
        return 2 * self.streams + self.streams**2


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` times: rows, then columns, each divided by its sum + ``eps``.
    ``m`` is (n, n, ...), entry ``[i, j]`` of every token's matrix; a row's sum
    runs over ``j``. Straight-line code: twenty steps over a few kilobytes a
    token fuse into a handful of loops, and reverse mode needs no scan."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def _init_bias(streams: int):
    """``b_pre``, ``b_post`` N(0, 1); ``B_res`` N(0, 1) + 2 I: away from the
    point (all zeros) at which ``exp`` is already doubly stochastic and
    Sinkhorn has nothing to do."""

    def init(key, shape, dtype=jnp.float32):
        bias = jax.random.normal(key, shape, dtype)
        return bias.at[2 * streams :].add(2.0 * jnp.eye(streams, dtype=dtype).reshape(-1))

    return init


class HyperConnection(nn.Module):
    """The maps of ONE sub-block and its read: ``X`` (B, n, S, hidden) ->
    ``(u (B, S, hidden), H_res (n, n, B, S), H_post (B, n, S))``, float32.
    ``phi`` is (n, hidden, 2n + n^2), columns ``[pre | post | res]``; ``bias``
    (2n + n^2,); ``gate`` the three learned scalars ``a_pre, a_post, a_res``."""

    config: HyperConfig
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array):
        c, f32 = self.config, jnp.float32
        n = c.streams
        phi = self.param("phi", nn.initializers.normal(0.02), (n, c.hidden, c.maps), f32)
        bias = self.param("bias", _init_bias(n), (c.maps,), f32)
        gate = self.param("gate", nn.initializers.ones_init(), (3,), f32)
        b, _, s, _ = x.shape
        xf = x.astype(f32)
        with _span("mhc.maps"):
            # x~ Phi = (vec(X) Phi) / rms: the scalar goes through the product
            inv_rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(1, 3)) + c.eps)  # (B, S)
            raw = jnp.einsum("bnsh,nhk->bsk", xf, phi, precision=jax.lax.Precision.HIGHEST)
            gates = jnp.concatenate(
                [jnp.broadcast_to(gate[i], (w,)) for i, w in enumerate((n, n, n * n))])
            raw = raw * inv_rms[..., None] * gates + bias
            h_pre = jax.nn.sigmoid(raw[..., :n])  # (B, S, n)
            h_post = 2.0 * jax.nn.sigmoid(raw[..., n : 2 * n])
            h_res = jnp.moveaxis(raw[..., 2 * n :].reshape(b, s, n, n), (2, 3), (0, 1))
        with _span("mhc.sinkhorn", iters=c.sinkhorn_iters):
            get_registry().counter(
                "consensusml_mhc_sinkhorn_iters_total",
                "Sinkhorn-Knopp iterations traced into hyper-connected sub-blocks",
                labels={"layer": str(self.layer)},
            ).inc(c.sinkhorn_iters)
            h_res = sinkhorn(
                jnp.exp(jnp.clip(h_res, c.clamp_min, c.clamp_max)), c.sinkhorn_iters, c.eps)
        with _span("mhc.pre"):  # elementwise over the streams: no product for the MXU to round
            u = sum(h_pre[..., j, None] * xf[:, j] for j in range(n))
        return u, h_res, jnp.moveaxis(h_post, 2, 1)


def hyper_post(x: jax.Array, h_res: jax.Array, h_post: jax.Array, y: jax.Array):
    """``X' = H_res X + H_post^T y`` in float32, back in ``x``'s dtype, and the
    root mean square of each stream of ``X'`` (B, n): what a step shows of
    itself. ``x`` (B, n, S, hidden), ``y`` (B, S, hidden)."""
    with _span("mhc.post"):
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        n = x.shape[1]
        mixed = jnp.stack(
            [sum(h_res[i, j][..., None] * xf[:, j] for j in range(n)) for i in range(n)], axis=1)
        mixed = mixed + h_post[..., None] * yf[:, None]
        stream_rms = jnp.sqrt(jnp.mean(jnp.square(mixed), axis=(2, 3)))
        return mixed.astype(x.dtype), stream_rms
