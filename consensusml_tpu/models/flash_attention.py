"""Pallas TPU flash attention (forward + backward kernels, custom VJP).

Reference parity: the reference's fused attention would be a CUDA kernel
(unknowable — mount empty); on TPU the XLA-fused blockwise recurrence in
:mod:`consensusml_tpu.models.attention` already gives the O(S) memory
bound, but measured on a v5e it runs fwd+bwd at ~11 TFLOP/s (dense:
~16). This kernel keeps each (q-block, kv-block) tile entirely in VMEM
with MXU matmuls and the online-softmax recurrence — the
flash-attention-2 schedule — and a custom VJP whose backward recomputes
tiles from the saved logsumexp instead of storing S x S probabilities.

Layout notes (TPU-specific):
- inputs (B, S, H, D) fold to (B*H, S, D); grids walk (batch*heads,
  q blocks) forward/dq and (batch*heads, kv blocks) for dk/dv;
- per-row scalars (logsumexp, delta) are stored REPLICATED across a
  128-lane minor dim — rows stay on sublanes, so kernels never need a
  sublane<->lane transpose (the layout the public jax pallas op uses);
- the sequence pads to a block multiple; padded keys are masked by
  absolute position, padded query rows are sliced off at the end;
- causal grids skip blocks strictly above the diagonal.

Supports causal and full self-attention, plus an optional per-key
padding mask (``kv_mask``, (B, S) with 1 = attend): the only "bias" the
BERT workload needs, carried as one f32 row per batch instead of a full
(B, H, S, T) bias tile — padded keys drop out of the online softmax in
every kernel (VERDICT r2 item 8; arbitrary additive score biases remain
on the XLA blockwise path).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from consensusml_tpu.pallas_util import interpret_arg, out_struct

__all__ = ["flash_attention"]

_NEG_INF = -1e30
_BQ = 512
_BK = 512
_LANE = 128


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def fold_pad(x: jax.Array, block: int) -> jax.Array:
    """(B, S, H, D) -> (B*H, S_pad, D), S zero-padded up to a multiple of
    ``block`` — THE layout every kernel in this module assumes. The ring
    path (parallel.ring_attention) shares it; keep one definition."""
    b, s, h, d = x.shape
    x3 = jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)
    pad = (-s) % block
    if pad:
        x3 = jnp.pad(x3, ((0, 0), (0, pad), (0, 0)))
    return x3


def _fwd_kernel(
    causal, aligned, s_real, scale, bk, has_mask,
    qoff_ref, koff_ref, kvm_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
):
    """One (batch*head, q-block) tile: stream kv blocks, online softmax.

    ``aligned`` (static) means q and k share the origin (plain
    self-attention), enabling the above-diagonal block skip; the ring
    path passes dynamic offsets (SMEM scalars) and keeps the full loop.
    """
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    bq, d = q.shape
    s_pad = k_ref.shape[1]
    nk = s_pad // bk
    q_pos = (
        qoff_ref[0, 0]
        + qi * bq
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    )
    koff = koff_ref[0, 0]

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (bq, bk)
        k_local = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_local < s_real  # padded tail keys
        if causal:
            mask = mask & (q_pos >= koff + k_local)
        if has_mask:  # per-key padding mask, one f32 row per batch
            km = _kvm_row(kvm_ref, j * bk, bk)  # (1, bk)
            mask = mask & jnp.broadcast_to(km, (bq, bk))
        s = jnp.where(mask, s, _NEG_INF)
        m_blk = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)  # (bq, bk)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    if causal and aligned:
        # kv blocks strictly above the diagonal contribute nothing
        nk_eff = jnp.clip(pl.cdiv((qi + 1) * bq, bk), 1, nk)
    else:
        nk_eff = nk
    acc, m, l = jax.lax.fori_loop(0, nk_eff, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # per-row logsumexp, replicated across the lane dim (no transpose).
    # Fully-masked rows keep m = -inf => lse ~ -inf, so a later merge
    # weights them to zero (the ring path relies on this).
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l_safe), (bq, _LANE))


def _kvm_spec(kv_mask, sk_pad, heads):
    """(mask array, its BlockSpec) for the per-key padding mask.

    The mask is expanded host-side to ``(B*heads, 1, S_pad)`` so each
    program's block is ``(1, 1, S_pad)`` indexed by the batch*head grid
    id directly. The detours that do NOT work: a ``(1, S_pad)`` block on
    a ``(B, S_pad)`` array violates Mosaic's block rule (sublane dim must
    divide 8 or equal the array's — B is neither), a ``b // heads`` index
    map lowers sign-correction selects Mosaic rejects, and an in-kernel
    dynamic sublane pick breaks the interpreter's lowering. With the
    leading axis folded to batch*heads and a unit sublane dim, the block
    equals the array on its last two dims — legal everywhere, and the
    replication costs B*heads*S_pad f32 (a few hundred KiB)."""
    if kv_mask is None:
        dummy = jnp.ones((1, 1, _LANE), jnp.float32)
        return dummy, pl.BlockSpec(
            (1, 1, _LANE), lambda b, *_: (0, 0, 0), memory_space=pltpu.VMEM
        )
    kvm3 = jnp.repeat(kv_mask, heads, axis=0)[:, None, :]
    return kvm3, pl.BlockSpec(
        (1, 1, sk_pad), lambda b, *_: (b, 0, 0), memory_space=pltpu.VMEM
    )


def _kvm_row(kvm_ref, start, size):
    """(1, size) slice of this program's key-mask row."""
    return kvm_ref[0, :, pl.ds(start, size)] > 0.0


def _fwd(
    q3, k3, v3, causal: bool, s_real: int, scale: float,
    interpret: bool = False,
    q_offset=None, k_offset=None,
    kv_mask=None, heads: int = 1,
):
    """q3/k3/v3: (BH, S_pad, D) -> (o (BH,S_pad,D), lse (BH,S_pad,LANE)).

    ``q_offset``/``k_offset``: absolute positions of row 0 (traced int32
    scalars, e.g. a ring rank index) — None means 0/0, which also enables
    the causal block-skip fast path. ``kv_mask``: padded (B, S_pad) f32
    per-key mask (>0 = attend), ``heads`` folding the BH grid index back
    to a batch row.
    """
    bh, s_pad, d = q3.shape
    nq = s_pad // _BQ
    aligned, qoff, koff = _offsets_smem(q_offset, k_offset)
    kvm, kvm_spec = _kvm_spec(kv_mask, s_pad, heads)
    kernel = functools.partial(
        _fwd_kernel, causal, aligned, s_real, scale, _BK,
        kv_mask is not None,
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands = (qoff, koff, kvm, q3, k3, v3)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq),
        interpret=interpret_arg(interpret, *operands),
        in_specs=[
            smem,
            smem,
            kvm_spec,
            pl.BlockSpec((1, _BQ, d), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s_pad, d), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s_pad, d), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, _BQ, d), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (1, _BQ, _LANE), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_shape=[
            out_struct((bh, s_pad, d), q3.dtype, *operands),
            out_struct((bh, s_pad, _LANE), jnp.float32, *operands),
        ],
    )(*operands)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    causal, aligned, s_real, scale, bk, has_mask,
    qoff_ref, koff_ref, kvm_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]  # (bq, 1) — lane-replicated scalar
    delta = delta_ref[0][:, :1]
    bq, d = q.shape
    s_pad = k_ref.shape[1]
    nk = s_pad // bk
    q_pos = (
        qoff_ref[0, 0]
        + qi * bq
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    )
    koff = koff_ref[0, 0]

    def body(j, dq):
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        k_local = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_local < s_real
        if causal:
            mask = mask & (q_pos >= koff + k_local)
        if has_mask:
            km = _kvm_row(kvm_ref, j * bk, bk)  # (1, bk)
            mask = mask & jnp.broadcast_to(km, (bq, bk))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal and aligned:
        nk_eff = jnp.clip(pl.cdiv((qi + 1) * bq, bk), 1, nk)
    else:
        nk_eff = nk
    dq = jax.lax.fori_loop(0, nk_eff, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    causal, aligned, s_real, scale, bq, has_mask,
    qoff_ref, koff_ref, kvm_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
):
    kj = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    s_pad = q_ref.shape[1]
    nq = s_pad // bq
    k_pos = (
        koff_ref[0, 0]
        + kj * bk
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    )
    k_local = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    qoff = qoff_ref[0, 0]

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * bq, bq), :][:, :1]
        delta = delta_ref[0, pl.ds(i * bq, bq), :][:, :1]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (bq, bk)
        q_pos = qoff + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask = k_local < s_real
        if causal:
            mask = mask & (q_pos >= k_pos)
        if has_mask:
            km = _kvm_row(kvm_ref, kj * bk, bk)  # this kv block's keys
            mask = mask & jnp.broadcast_to(km, (bq, bk))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    # q blocks strictly above this kv block's diagonal never see it
    i0 = (kj * bk) // bq if (causal and aligned) else 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(i0, nq, body, (dk0, dv0))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _offsets_smem(q_offset, k_offset):
    aligned = q_offset is None and k_offset is None
    qoff = jnp.reshape(
        jnp.asarray(0 if q_offset is None else q_offset, jnp.int32), (1, 1)
    )
    koff = jnp.reshape(
        jnp.asarray(0 if k_offset is None else k_offset, jnp.int32), (1, 1)
    )
    return aligned, qoff, koff


def _bwd_dq(
    q3, k3, v3, do3, lse, delta, causal, s_real, scale, interpret,
    q_offset=None, k_offset=None, kv_mask=None, heads: int = 1,
):
    """dq for local queries against a (possibly offset) kv span."""
    bh, sq_pad, d = q3.shape
    sk_pad = k3.shape[1]
    aligned, qoff, koff = _offsets_smem(q_offset, k_offset)
    kvm, kvm_spec = _kvm_spec(kv_mask, sk_pad, heads)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    lane_spec_blk = pl.BlockSpec(
        (1, _BQ, _LANE), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM
    )
    operands = (qoff, koff, kvm, q3, k3, v3, do3, lse, delta)
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal, aligned, s_real, scale, _BK,
            kv_mask is not None,
        ),
        grid=(bh, sq_pad // _BQ),
        interpret=interpret_arg(interpret, *operands),
        in_specs=[
            smem,
            smem,
            kvm_spec,
            pl.BlockSpec((1, _BQ, d), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BQ, d), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM),
            lane_spec_blk,
            lane_spec_blk,
        ],
        out_specs=pl.BlockSpec(
            (1, _BQ, d), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=out_struct((bh, sq_pad, d), q3.dtype, *operands),
    )(*operands)


def _bwd_dkv(
    q3, k3, v3, do3, lse, delta, causal, s_real, scale, interpret,
    q_offset=None, k_offset=None, kv_mask=None, heads: int = 1,
):
    """dk/dv for a (possibly offset) kv span against local queries."""
    bh, sq_pad, d = q3.shape
    sk_pad = k3.shape[1]
    aligned, qoff, koff = _offsets_smem(q_offset, k_offset)
    kvm, kvm_spec = _kvm_spec(kv_mask, sk_pad, heads)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    lane_spec_full = pl.BlockSpec(
        (1, sq_pad, _LANE), lambda b, j: (b, 0, 0), memory_space=pltpu.VMEM
    )
    operands = (qoff, koff, kvm, q3, k3, v3, do3, lse, delta)
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal, aligned, s_real, scale, _BQ,
            kv_mask is not None,
        ),
        grid=(bh, sk_pad // _BK),
        interpret=interpret_arg(interpret, *operands),
        in_specs=[
            smem,
            smem,
            kvm_spec,
            pl.BlockSpec((1, sq_pad, d), lambda b, j: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BK, d), lambda b, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BK, d), lambda b, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, sq_pad, d), lambda b, j: (b, 0, 0), memory_space=pltpu.VMEM),
            lane_spec_full,
            lane_spec_full,
        ],
        out_specs=[
            pl.BlockSpec((1, _BK, d), lambda b, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BK, d), lambda b, j: (b, j, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            out_struct((bh, sk_pad, d), q3.dtype, *operands),
            out_struct((bh, sk_pad, d), q3.dtype, *operands),
        ],
    )(*operands)


def _bwd(causal, s_real, scale, interpret, heads, res, do3):
    q3, k3, v3, kvm, o3, lse = res
    bh, s_pad, d = q3.shape
    do3 = do3.astype(jnp.float32)
    delta = jnp.sum(do3 * o3.astype(jnp.float32), axis=-1)  # (BH, S_pad)
    delta = jnp.broadcast_to(delta[..., None], (bh, s_pad, _LANE))
    dq = _bwd_dq(
        q3, k3, v3, do3, lse, delta, causal, s_real, scale, interpret,
        kv_mask=kvm, heads=heads,
    )
    dk, dv = _bwd_dkv(
        q3, k3, v3, do3, lse, delta, causal, s_real, scale, interpret,
        kv_mask=kvm, heads=heads,
    )
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom VJP over the padded/folded layout)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash3(q3, k3, v3, kvm, causal, s_real, scale, interpret, heads):
    o3, _ = _fwd(
        q3, k3, v3, causal, s_real, scale, interpret,
        kv_mask=kvm, heads=heads,
    )
    return o3


def _flash3_fwd(q3, k3, v3, kvm, causal, s_real, scale, interpret, heads):
    o3, lse = _fwd(
        q3, k3, v3, causal, s_real, scale, interpret,
        kv_mask=kvm, heads=heads,
    )
    return o3, (q3, k3, v3, kvm, o3, lse)


def _flash3_bwd(causal, s_real, scale, interpret, heads, res, do3):
    dq, dk, dv = _bwd(causal, s_real, scale, interpret, heads, res, do3)
    # the mask is data, not weights: its cotangent is structurally zero
    dkvm = None if res[3] is None else jnp.zeros_like(res[3])
    return dq, dk, dv, dkvm


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    kv_mask: jax.Array | None = None,  # (B, S), >0 = attend to that key
    dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """Fused Pallas self-attention (same contract as
    ``dot_product_attention``). Requires ``q.shape == k.shape``.

    ``kv_mask`` is the per-key padding mask ((B, S), nonzero = attend):
    the BERT attention_mask, applied inside every kernel's online
    softmax. Arbitrary additive biases are NOT supported — use the
    blockwise path for those.
    """
    b, s, h, d = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"flash_attention is self-attention-shaped: q{q.shape} k{k.shape}"
        )
    scale = 1.0 / float(d) ** 0.5
    # pad to a common multiple of both block sizes: the kv loops count
    # s_pad // _BK blocks, so a _BQ-only pad would silently drop tail keys
    # under retuned, non-dividing block constants
    block = math.lcm(_BQ, _BK)
    kvm = None
    if kv_mask is not None:
        if kv_mask.shape != (b, s):
            raise ValueError(
                f"kv_mask must be (batch, seq) = {(b, s)}, got {kv_mask.shape}"
            )
        kvm = jnp.pad(
            jnp.asarray(kv_mask, jnp.float32), ((0, 0), (0, (-s) % block))
        )
    o3 = _flash3(
        fold_pad(q, block), fold_pad(k, block), fold_pad(v, block),
        kvm, causal, s, scale, interpret, h,
    )
    o = o3[:, :s].reshape(b, h, s, d)
    return jnp.moveaxis(o, 1, 2).astype(dtype)
