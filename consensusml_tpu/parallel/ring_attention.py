"""Ring attention: exact long-context attention over a sequence-sharded mesh axis.

Liu et al. 2023 ("Ring Attention with Blockwise Transformers") pattern,
TPU-native: the sequence is sharded across devices along a named mesh
axis; each device holds a Q/K/V block. K/V blocks rotate around the ring
with ``jax.lax.ppermute`` (ICI neighbor traffic — the same primitive as
the gossip step) while every device accumulates its Q-block's attention
with a numerically-stable online softmax (flash-attention style running
max/sum in f32). After P steps each Q block has attended to the FULL
sequence with only (1/P)-sized KV resident per device — sequence length
scales linearly with the ring size.

Call inside ``shard_map`` with the sequence axis sharded over
``axis_name``; shapes are per-device blocks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["ring_attention", "ring_flash_attention"]

_NEG_INF = -1e30


def _pvary(x: jax.Array, axis_name: str) -> jax.Array:
    """Mark ``x`` as device-varying along ``axis_name`` (VMA annotation)."""
    return jax.lax.pcast(x, axis_name, to="varying")


def ring_attention(
    q: jax.Array,  # (B, S_blk, H, D) — this device's query block
    k: jax.Array,  # (B, S_blk, H, D)
    v: jax.Array,  # (B, S_blk, H, D)
    axis_name: str,
    *,
    causal: bool = False,
) -> jax.Array:
    """Exact attention across the full (sharded) sequence.

    Returns this device's output block ``(B, S_blk, H, D)`` in ``q.dtype``.
    Causal masking uses absolute positions derived from the device's ring
    index, so the result matches single-device causal attention on the
    gathered sequence (tested against it).
    """
    p = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s_blk, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    pos_q = my * s_blk + jnp.arange(s_blk)  # absolute positions of our queries

    perm = [(i, (i + 1) % p) for i in range(p)]

    def step(t, carry):
        out, row_max, row_sum, kv = carry
        k_t, v_t = kv
        # the block we hold after t rotations originated at rank (my - t) % p
        src = (my - t) % p
        pos_k = src * s_blk + jnp.arange(s_blk)
        logits = (
            jnp.einsum("bshd,bthd->bhst", q, k_t, preferred_element_type=jnp.float32)
            * scale
        )
        if causal:
            mask = pos_q[:, None] >= pos_k[None, :]
            logits = jnp.where(mask[None, None], logits, _NEG_INF)
        blk_max = jnp.max(logits, axis=-1)  # (B,H,S)
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(logits - new_max[..., None])  # (B,H,S,T)
        new_sum = row_sum * correction + jnp.sum(probs, axis=-1)
        blk_out = jnp.einsum(
            "bhst,bthd->bshd", probs, jnp.asarray(v_t, jnp.float32),
            preferred_element_type=jnp.float32,
        )
        new_out = out * correction.transpose(0, 2, 1)[..., None] + blk_out
        # rotate KV to the next device (the final rotation restores the
        # original block; unconditional so no collective sits under a cond)
        kv = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm), (k_t, v_t))
        return new_out, new_max, new_sum, kv

    # initial accumulators must carry the device-varying axis annotation
    # (VMA) or the fori_loop carry types mismatch after the first ppermute
    out0 = _pvary(jnp.zeros((b, s_blk, h, d), jnp.float32), axis_name)
    max0 = _pvary(jnp.full((b, h, s_blk), _NEG_INF, jnp.float32), axis_name)
    sum0 = _pvary(jnp.zeros((b, h, s_blk), jnp.float32), axis_name)
    out, _, row_sum, _ = jax.lax.fori_loop(0, p, step, (out0, max0, sum0, (k, v)))
    denom = jnp.maximum(row_sum, 1e-30).transpose(0, 2, 1)[..., None]
    return (out / denom).astype(q.dtype)


# ---------------------------------------------------------------------------
# ring flash attention: the same rotation schedule, but each device's
# (q-block x visiting-kv-block) tile runs the Pallas flash kernels
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis_name, causal, interpret):
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal, interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, interpret):
    from consensusml_tpu.models import flash_attention as fa

    p = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s_blk, h, d = q.shape
    scale = 1.0 / float(d) ** 0.5
    block = math.lcm(fa._BQ, fa._BK)
    q3 = fa.fold_pad(q, block)
    k3 = fa.fold_pad(k, block)
    v3 = fa.fold_pad(v, block)
    bh, sq_pad, _ = q3.shape
    perm = [(i, (i + 1) % p) for i in range(p)]

    def step(t, carry):
        acc, m, l, kv = carry
        k_t, v_t = kv
        src = (my - t) % p
        o_t, lse_t = fa._fwd(
            q3, k_t, v_t, causal, s_blk, scale, interpret,
            q_offset=my * s_blk, k_offset=src * s_blk,
        )
        lse_col = lse_t[..., :1]  # (BH, sq_pad, 1) — lanes are replicas
        m_new = jnp.maximum(m, lse_col)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(lse_col - m_new)
        acc = acc * c_old + o_t.astype(jnp.float32) * c_new
        l = l * c_old + c_new
        kv = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm), (k_t, v_t))
        return acc, m_new, l, kv

    acc0 = _pvary(jnp.zeros((bh, sq_pad, d), jnp.float32), axis_name)
    m0 = _pvary(jnp.full((bh, sq_pad, 1), _NEG_INF, jnp.float32), axis_name)
    l0 = _pvary(jnp.zeros((bh, sq_pad, 1), jnp.float32), axis_name)
    acc, m, l, _ = jax.lax.fori_loop(0, p, step, (acc0, m0, l0, (k3, v3)))
    l_safe = jnp.maximum(l, 1e-30)
    out3 = (acc / l_safe).astype(q.dtype)
    lse_total = jnp.broadcast_to(
        m + jnp.log(l_safe), (bh, sq_pad, fa._LANE)
    )  # lane-replicated, the layout the backward kernels read
    out = jnp.moveaxis(out3[:, :s_blk].reshape(b, h, s_blk, d), 1, 2)
    return out, (q3, k3, v3, out3, lse_total)


def _ring_flash_bwd(axis_name, causal, interpret, res, dout):
    from consensusml_tpu.models import flash_attention as fa

    q3, k3, v3, out3, lse = res
    p = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    bh, sq_pad, d = q3.shape
    b = dout.shape[0]
    h = bh // b
    s_blk = dout.shape[1]
    scale = 1.0 / float(d) ** 0.5

    # fold dout and zero-pad its rows out to the residuals' padded length
    do3 = fa.fold_pad(dout, sq_pad).astype(v3.dtype)
    delta = fa.delta_rows(do3, out3)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def step(t, carry):
        dq, blk = carry
        k_t, v_t, dk_t, dv_t = blk
        src = (my - t) % p
        dq_c, dk_c, dv_c = fa._bwd_call(
            q3, k_t, v_t, do3, lse, delta, causal, s_blk, scale, interpret,
            q_offset=my * s_blk, k_offset=src * s_blk,
        )
        dq = dq + dq_c.astype(jnp.float32)
        # the kv block's gradient travels WITH the block: after the full
        # rotation both land back on the block's home device
        blk = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm),
            (k_t, v_t, dk_t + dk_c.astype(jnp.float32), dv_t + dv_c.astype(jnp.float32)),
        )
        return dq, blk

    dq0 = _pvary(jnp.zeros((bh, sq_pad, d), jnp.float32), axis_name)
    dk0 = _pvary(jnp.zeros((bh, sq_pad, d), jnp.float32), axis_name)
    dv0 = _pvary(jnp.zeros((bh, sq_pad, d), jnp.float32), axis_name)
    dq, (_, _, dk, dv) = jax.lax.fori_loop(
        0, p, step, (dq0, (k3, v3, dk0, dv0))
    )

    def unfold(g3, like):
        g = g3[:, :s_blk].reshape(b, h, s_blk, d)
        return jnp.moveaxis(g, 1, 2).astype(like.dtype)

    # reconstruct (B, S, H, D) reference dtypes from the folded residuals
    return (
        unfold(dq, q3),
        unfold(dk, k3),
        unfold(dv, v3),
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(
    q: jax.Array,  # (B, S_blk, H, D) — this device's blocks
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Ring attention whose per-step tiles run the Pallas flash kernels.

    Same contract and rotation schedule as :func:`ring_attention` (call
    inside ``shard_map`` with the sequence sharded over ``axis_name``),
    but each device's (local-q x visiting-kv) computation is the fused
    flash kernel with dynamic position offsets; per-step partial outputs
    merge by logsumexp (the flash-decoding combine), and the backward is
    a second ring pass where each kv block's (dk, dv) travels with it
    back to its home device. ``interpret=True`` runs the kernels in the
    Pallas interpreter (CPU tests).
    """
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(
            f"ring_flash_attention needs equal block shapes: q{q.shape} k{k.shape}"
        )
    return _ring_flash(q, k, v, axis_name, causal, interpret)
