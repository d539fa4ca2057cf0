"""Shared attention building blocks for the transformer families.

TPU-first: head dims padded to MXU-friendly sizes by construction, bf16
QKV matmuls with f32 softmax, optional causal masking via static masks
(no dynamic shapes), RoPE computed in f32. The long-context path (ring
attention over a sequence-parallel mesh axis) lives in
:mod:`consensusml_tpu.parallel.ring_attention` and reuses these blocks.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from consensusml_tpu.pallas_util import on_tpu

__all__ = [
    "dot_product_attention",
    "auto_impl",
    "blockwise_attention",
    "cached_attention",
    "cached_attention_window",
    "update_kv_cache",
    "paged_update_kv_cache",
    "paged_update_kv_cache_window",
    "paged_cow_copy",
    "gather_paged_kv",
    "apply_rope",
    "rope_frequencies",
]

_NEG_INF = -1e30

# auto dispatch: above this many logits per (batch, head) the dense S x T
# f32 score matrix dominates activation memory and the blockwise path wins
_BLOCKWISE_THRESHOLD = 512 * 512
_DEFAULT_BLOCK_KV = 512


def dot_product_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, H, D)
    v: jax.Array,  # (B, T, H, D)
    *,
    causal: bool = False,
    bias: jax.Array | None = None,
    kv_mask: jax.Array | None = None,
    mask: jax.Array | None = None,
    dtype: Any = jnp.bfloat16,
    impl: str = "auto",
    scale: float | None = None,
) -> jax.Array:
    """Multi-head attention with f32 logits/softmax. ``v`` may be narrower or
    wider than ``q`` and ``k`` (the output is as wide as ``v``); ``scale``
    multiplies the scores, ``D^-1/2`` of ``q``'s width when None.

    ``impl``: "dense" materializes the (B, H, S, T) score matrix — fine
    for short sequences; "blockwise" streams KV blocks with an online
    softmax (flash-attention recurrence, O(S) activation memory);
    "flash" is the Pallas TPU kernel version of the same schedule
    (:mod:`consensusml_tpu.models.flash_attention`; its times on a v5e
    are in PERF.md section 6, against dense and blockwise not measured
    since PR 21 deleted the old records); "auto"
    picks, once S*T crosses the dense threshold, flash on TPU when the
    kernel's contract holds (self-attention shapes, no full bias) and
    blockwise otherwise. All paths share the recipe: logits accumulate
    in f32 on the MXU, softmax in f32, output in ``dtype``.

    ``kv_mask`` ((B, T), nonzero = attend) is the per-key padding mask —
    BERT's attention_mask. Unlike a general additive ``bias`` it rides
    the flash kernel (one f32 row per batch); on blockwise it is folded
    into the bias, and on dense it is applied with ``where`` like
    ``mask``. Pass at most one of ``bias``/``kv_mask`` for a padding
    mask; arbitrary score biases still need ``bias``.

    ``mask`` ((B, S, T) or (B, 1, T) boolean, True = attend) is the
    per-query-row exclusion mask, dense-only, applied with ``jnp.where``
    on the f32 logits — NOT as an additive bias. The distinction
    matters when excluded KEYS hold non-finite garbage (e.g. ±inf in a
    stale pool page): ``garbage + (-1e30)`` keeps the garbage while
    ``where`` replaces the score outright. Excluded columns contribute
    exactly zero probability either way. Note the VALUE side has no
    such shield — probability-zero rows still enter the output matmul
    as ``0 * v``, so NaN values poison the sum regardless of masking;
    pool writers must keep even junk rows finite (see the clamped
    position-table lookups in :func:`apply_rope` / gpt2's ``wpe``).
    """
    if kv_mask is not None:
        if bias is not None:
            raise ValueError(
                "pass either bias or kv_mask, not both (fold the padding "
                "mask into your bias, or drop the bias)"
            )
        if kv_mask.shape != (k.shape[0], k.shape[1]):
            raise ValueError(
                f"kv_mask must be (batch, kv_len) = "
                f"{(k.shape[0], k.shape[1])}, got {kv_mask.shape}"
            )
    if impl == "auto":
        impl = auto_impl(q, k, v, bias)
    if mask is not None and impl != "dense":
        raise ValueError(
            f"mask= is dense-only (where-masking on the materialized "
            f"score matrix), got impl={impl!r}"
        )
    if impl == "flash":
        if bias is not None:
            raise ValueError(
                "impl='flash' does not support bias (the Pallas kernel has "
                "no bias input; a padding mask can ride kv_mask instead); "
                "use impl='blockwise' or 'auto'"
            )
        from consensusml_tpu.models.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, kv_mask=kv_mask, dtype=dtype, scale=scale
        )
    if kv_mask is not None:
        if impl == "dense":  # where-masked below, garbage-robust
            mask = kv_mask[:, None, :] > 0
        else:  # blockwise takes it as an additive bias
            bias = jnp.where(kv_mask[:, None, None, :] > 0, 0.0, _NEG_INF)
    if impl == "blockwise":
        return blockwise_attention(
            q, k, v, causal=causal, bias=bias, dtype=dtype, scale=scale)
    if impl != "dense":
        raise ValueError(
            f"unknown attention impl {impl!r} (auto|dense|blockwise|flash)"
        )
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum(
        "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        logits = logits + jnp.asarray(bias, jnp.float32)
    if mask is not None:
        # broadcast (B, S|1, T) over heads; where, not +bias: a NaN score
        # from garbage keys must not survive its own exclusion
        logits = jnp.where(
            mask[:, None], logits, jnp.asarray(_NEG_INF, jnp.float32)
        )
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), jnp.bool_), k=t - s)
        logits = jnp.where(mask, logits, jnp.asarray(_NEG_INF, jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhst,bthd->bshd", probs.astype(dtype), v, preferred_element_type=jnp.float32
    )
    return out.astype(dtype)


def auto_impl(q: jax.Array, k: jax.Array, v: jax.Array, bias=None) -> str:
    """What ``impl="auto"`` resolves to: dense up to the threshold; past it
    the flash kernel on a TPU when its contract holds (self-attention shapes,
    ``v``'s width free, no full bias), else blockwise."""
    if q.shape[1] * k.shape[1] <= _BLOCKWISE_THRESHOLD:
        return "dense"
    if bias is None and q.shape == k.shape and v.shape[:-1] == q.shape[:-1] and on_tpu():
        return "flash"
    return "blockwise"


def blockwise_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, H, D)
    v: jax.Array,  # (B, T, H, D)
    *,
    causal: bool = False,
    bias: jax.Array | None = None,
    dtype: Any = jnp.bfloat16,
    block_kv: int = _DEFAULT_BLOCK_KV,
    scale: float | None = None,
) -> jax.Array:
    """Exact attention that never materializes the full score matrix.

    ``lax.scan`` over KV blocks with the flash-attention online-softmax
    recurrence (running row max / row sum in f32) — the single-device
    sibling of :func:`consensusml_tpu.parallel.ring_attention`, which runs
    the same recurrence with ``ppermute`` rotations across a mesh axis.
    Peak activation memory is O(S * block_kv) instead of O(S * T); XLA
    fuses each block's mask+softmax+matmul chain.

    ``bias`` must broadcast against ``(B, H, S, T)``; it is sliced along
    T per block (BERT's padding bias ``(B, 1, 1, T)`` and full score
    biases both work).
    """
    b, s, h, d = q.shape
    t, d_v = k.shape[1], v.shape[-1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    block_kv = min(block_kv, t)
    nblk = -(-t // block_kv)
    pad = nblk * block_kv - t

    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (nblk, B, block, H, D) — scan carries one block at a time
    kb = jnp.moveaxis(kp.reshape(b, nblk, block_kv, h, d), 1, 0)
    vb = jnp.moveaxis(vp.reshape(b, nblk, block_kv, h, d_v), 1, 0)
    if bias is not None:
        bias = jnp.broadcast_to(
            jnp.asarray(bias, jnp.float32),
            jnp.broadcast_shapes(bias.shape, (b, 1, 1, t)),
        )
        bp = jnp.pad(bias, [(0, 0)] * (bias.ndim - 1) + [(0, pad)])
        # (nblk, B, Hb, Sb, block) with Hb/Sb possibly 1 (broadcast dims)
        bb = jnp.moveaxis(
            bp.reshape(*bp.shape[:-1], nblk, block_kv), -2, 0
        )
    else:
        bb = None

    pos_q = jnp.arange(s) + (t - s if causal else 0)  # absolute query rows

    def step(carry, blk):
        out, row_max, row_sum, start = carry
        k_t, v_t, b_t = blk
        logits = (
            jnp.einsum("bshd,bthd->bhst", q, k_t, preferred_element_type=jnp.float32)
            * scale
        )
        if b_t is not None:
            logits = logits + b_t
        pos_k = start + jnp.arange(block_kv)
        valid = pos_k < t  # padded tail keys never contribute
        if causal:
            valid = valid[None, :] & (pos_q[:, None] >= pos_k[None, :])
        else:
            valid = jnp.broadcast_to(valid[None, :], (s, block_kv))
        logits = jnp.where(valid[None, None], logits, _NEG_INF)
        blk_max = jnp.max(logits, axis=-1)  # (B, H, S)
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(logits - new_max[..., None])
        new_sum = row_sum * correction + jnp.sum(probs, axis=-1)
        # same MXU recipe as the dense path: inputs in compute dtype,
        # accumulate f32 (a full-f32 matmul would halve MXU throughput)
        blk_out = jnp.einsum(
            "bhst,bthd->bshd", probs.astype(v_t.dtype), v_t,
            preferred_element_type=jnp.float32,
        )
        new_out = out * correction.transpose(0, 2, 1)[..., None] + blk_out
        return (new_out, new_max, new_sum, start + block_kv), None

    # derive the accumulators FROM q (zeros via q*0) rather than fresh
    # constants: inside shard_map the carry must match the body's
    # varying-manual-axes annotation, and inheriting q's does that on
    # every path (plain jit included, where it is a no-op)
    zeros_bshd = jnp.asarray(q, jnp.float32) * 0.0
    zeros_bhs = jnp.moveaxis(zeros_bshd[..., 0], 1, 2)
    if d_v != d:  # the output's accumulator is as wide as the values
        zeros_bshd = jnp.broadcast_to(zeros_bshd[..., :1], (b, s, h, d_v))
    carry0 = (
        zeros_bshd,
        zeros_bhs + _NEG_INF,
        zeros_bhs,
        jnp.asarray(0, jnp.int32),
    )
    # remat the block step: without it, grad-of-scan stores every block's
    # probs residuals — O(S*T) again, exactly what this path exists to
    # avoid. Recomputing a block's softmax in the backward trades a few
    # flops for the flash-attention memory bound.
    (out, _, row_sum, _), _ = jax.lax.scan(
        jax.checkpoint(step), carry0,
        (kb, vb, bb) if bb is not None else (kb, vb, None),
    )
    denom = jnp.maximum(row_sum, 1e-30).transpose(0, 2, 1)[..., None]
    return (out / denom).astype(dtype)


def update_kv_cache(
    cache: dict[str, jax.Array],
    k: jax.Array,  # (B, 1, H, D) — the decode step's single new key
    v: jax.Array,  # (B, 1, H, D)
    positions: jax.Array,  # (B,) per-row write index into the cache
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Write one decode step's K/V into per-row cache slots.

    ``cache`` holds ``{"k": (B, T, H, D), "v": (B, T, H, D)}`` where each
    batch row is an independent sequence slot (the serving engine's
    continuous batcher packs unrelated requests into the rows, each at its
    own length). Rows write at DIFFERENT positions — a per-row scatter,
    not a ``dynamic_update_slice`` — so one fused decode step serves the
    whole batch regardless of how staggered the sequences are.

    Returns ``(k_cache, v_cache, lengths)`` where ``lengths = positions+1``
    counts the now-valid rows (the just-written token included), ready for
    :func:`cached_attention`'s mask.
    """
    rows = jnp.arange(k.shape[0])
    k_cache = cache["k"].at[rows, positions].set(
        jnp.asarray(k[:, 0], cache["k"].dtype)
    )
    v_cache = cache["v"].at[rows, positions].set(
        jnp.asarray(v[:, 0], cache["v"].dtype)
    )
    return k_cache, v_cache, positions + 1


def paged_update_kv_cache(
    cache: dict[str, jax.Array],
    k: jax.Array,  # (S, 1, H, D) — the decode step's single new key per slot
    v: jax.Array,  # (S, 1, H, D)
    block_table: jax.Array,  # (S, blocks_per_slot) physical block ids
    positions: jax.Array,  # (S,) per-slot token index
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Write one decode step's K/V into a PAGED block pool.

    ``cache`` holds ``{"k": (N, bs, H, D), "v": (N, bs, H, D)}`` — N
    physical blocks of ``bs`` tokens each, shared by every slot. A slot's
    logical position ``p`` maps through its block-table row:
    ``physical = block_table[s, p // bs]``, ``offset = p % bs``. The
    scatter indices are computed INSIDE the jit (ints on device, no host
    round-trip), so the compiled decode step is position-oblivious — the
    pool engine's zero-recompile contract.

    Free lanes write into physical block 0, the reserved TRASH block the
    pool never allocates (their table rows are all-zero); active lanes
    write into blocks they own exclusively, so no scatter can corrupt
    another slot's live tokens. Returns ``(k_pages, v_pages, lengths)``
    with ``lengths = positions + 1`` for :func:`gather_paged_kv` +
    :func:`cached_attention`.
    """
    bs = cache["k"].shape[1]
    rows = jnp.arange(k.shape[0])
    phys = block_table[rows, positions // bs]
    off = positions % bs
    k_pages = cache["k"].at[phys, off].set(jnp.asarray(k[:, 0], cache["k"].dtype))
    v_pages = cache["v"].at[phys, off].set(jnp.asarray(v[:, 0], cache["v"].dtype))
    return k_pages, v_pages, positions + 1


def paged_update_kv_cache_window(
    cache: dict[str, jax.Array],
    k: jax.Array,  # (S, W, H, D) — a W-token verify window per slot
    v: jax.Array,  # (S, W, H, D)
    block_table: jax.Array,  # (S, cols) physical block ids (trash-padded)
    positions: jax.Array,  # (S, W) per-slot, per-window-token index
) -> tuple[jax.Array, jax.Array]:
    """Write a ``W``-token window of K/V into the paged pool — the
    speculative k-verify's fixed-shape widening of
    :func:`paged_update_kv_cache` (``W = k + 1``: the pending token plus
    k draft proposals, all scattered in ONE step).

    Index math is the single-token scatter's, per window column:
    ``physical = block_table[s, p // bs]``, ``offset = p % bs`` — all on
    device, zero host sync. Window positions that run past a slot's real
    block-table row (a stream within ``k`` of ``max_len``) index the
    TRASH-padded columns the engine appends in speculative mode, so
    overflow writes land in the trash block, never in pages another slot
    owns. Rejected draft positions are *not* rolled back here: their
    rows sit beyond the slot's committed length, the length mask zeroes
    them exactly, and the next verify window overwrites them — rollback
    is pure host-side position/block accounting.
    """
    bs = cache["k"].shape[1]
    phys = jnp.take_along_axis(block_table, positions // bs, axis=1)
    off = positions % bs
    k_pages = cache["k"].at[phys, off].set(jnp.asarray(k, cache["k"].dtype))
    v_pages = cache["v"].at[phys, off].set(jnp.asarray(v, cache["v"].dtype))
    return k_pages, v_pages


def paged_cow_copy(
    cache: dict[str, jax.Array],
    src: jax.Array,  # () physical block id — shared block being diverged
    dst: jax.Array,  # () physical block id — the diverging slot's fresh block
) -> dict[str, jax.Array]:
    """Copy one physical block's K/V rows ``src -> dst`` inside the jit
    — the prefix cache's copy-on-write step. A slot whose first write
    would land mid-way into a block other streams still share instead
    (a) points its block-table entry at a fresh block and (b) runs this
    copy before the scatter, so the fresh block holds the shared rows
    plus the slot's own writes while every other holder keeps reading
    the untouched source. ``src == dst == 0`` (the trash block) is the
    disabled case: a trash self-copy is a benign no-op lane, the same
    trick the decode scatter plays for free lanes — one executable
    whether or not this admission diverged, no host sync either way."""
    return {
        "k": cache["k"].at[dst].set(cache["k"][src]),
        "v": cache["v"].at[dst].set(cache["v"][src]),
    }


def gather_paged_kv(
    k_pages: jax.Array,  # (N, bs, H, D)
    v_pages: jax.Array,  # (N, bs, H, D)
    block_table: jax.Array,  # (S, blocks_per_slot)
) -> tuple[jax.Array, jax.Array]:
    """Assemble each slot's logical KV view from its block-table row.

    One gather per tensor: ``pages[block_table]`` is ``(S, nb, bs, H, D)``
    which reshapes to the ``(S, T, H, D)`` layout
    :func:`cached_attention` expects (``T = nb * bs``; when the block
    size divides ``max_len`` this is EXACTLY the per-slot cache shape, so
    the attention math — and its reduction order — is bit-identical to
    the non-paged path). Rows past a slot's length gather whatever block
    the table names (trash, or a block's not-yet-overwritten tail);
    the length mask zeroes their probability exactly, so the garbage
    never contributes. The gather materializes the view transiently
    inside the step; the RESIDENT cache stays the block pool, bounded by
    total live tokens rather than ``num_slots * max_len``.
    """
    s, nb = block_table.shape
    bs, h, d = k_pages.shape[1:]
    k = k_pages[block_table].reshape(s, nb * bs, h, d)
    v = v_pages[block_table].reshape(s, nb * bs, h, d)
    return k, v


def cached_attention(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, T, H, D)
    v_cache: jax.Array,  # (B, T, H, D)
    *,
    lengths: jax.Array,  # (B,) valid cache rows per slot
    dtype: Any = jnp.bfloat16,
) -> jax.Array:
    """Decode-step attention over a KV cache.

    The query is the single current token per slot; it attends to the
    first ``lengths[b]`` cache rows of its own slot (everything at or
    before its position — causality is enforced by the LENGTH mask, so no
    causal matrix is needed for a one-row query). Cache rows past the
    length carry stale garbage from earlier occupants of the slot; the
    mask zeroes their probability exactly, so slot reuse needs no cache
    clearing. Fixed shapes throughout: the compiled step is reused for
    every decode step at every fill level (the serving engine's
    zero-recompile contract, asserted by cml-check's decode jaxpr pass).
    """
    t = k_cache.shape[1]
    kv_mask = jnp.arange(t)[None, :] < lengths[:, None]
    return dot_product_attention(
        q, k_cache, v_cache, kv_mask=kv_mask, dtype=dtype, impl="dense"
    )


def cached_attention_window(
    q: jax.Array,  # (B, W, H, D) — W query tokens per slot
    k_cache: jax.Array,  # (B, T, H, D)
    v_cache: jax.Array,  # (B, T, H, D)
    *,
    positions: jax.Array,  # (B, W) absolute position of each query token
    dtype: Any = jnp.bfloat16,
) -> jax.Array:
    """Multi-query-token decode attention — :func:`cached_attention`
    widened to a ``W``-token window (the speculative verify step).

    Query token ``w`` of slot ``b`` sits at absolute position
    ``positions[b, w]`` and attends cache rows ``<= positions[b, w]`` —
    its own just-written row included, everything later masked. That one
    per-row mask encodes BOTH causality inside the window (window tokens
    are written to the cache before the gather, and a later window
    token's position exceeds an earlier one's) and the stale-garbage
    exclusion past each slot's length, so no separate causal matrix is
    needed. ``W = 1`` with ``positions[:, None]`` degenerates to exactly
    :func:`cached_attention`'s mask.

    The mask rides ``mask=`` (a ``where`` on the logits), not an
    additive bias: excluded trash-block rows hold junk that only stays
    finite by the position-clamp convention (overflow window lanes
    embed a clamped position, then scatter to trash), and ``where``
    keeps the score side robust even if that junk is extreme — an
    additive ``junk + (-1e30)`` would carry ±inf straight through.
    """
    t = k_cache.shape[1]
    mask = jnp.arange(t)[None, None, :] <= positions[:, :, None]  # (B, W, T)
    return dot_product_attention(
        q, k_cache, v_cache, mask=mask, dtype=dtype, impl="dense"
    )


def rope_frequencies(
    head_dim: int, max_len: int, theta: float = 10000.0, *,
    factor: float = 1.0, beta_fast: float = 32.0, beta_slow: float = 1.0,
    original_max_len: int | None = None,
) -> jax.Array:
    """Precompute RoPE cos/sin table ``(max_len, head_dim//2, 2)`` in f32.

    ``factor`` > 1 with ``original_max_len`` is yarn's table (Peng et al.
    2023, as the DeepSeek family computes it): a pair that turns more than
    ``beta_fast`` times in the original ``original_max_len`` positions keeps
    its frequency, one that turns less than ``beta_slow`` times has it
    divided by ``factor``, a linear ramp over the pairs between. Cos and sin
    are not scaled here: the attention's ``mscale`` goes into its score scale."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if factor != 1.0:
        if original_max_len is None:
            raise ValueError("yarn (factor != 1) needs original_max_len")
        half = head_dim // 2

        def pair_of(turns):  # the pair that turns ``turns`` times in the original length
            return head_dim * math.log(original_max_len / (2 * math.pi * turns)) / (2 * math.log(theta))

        lo = max(math.floor(pair_of(beta_fast)), 0)
        hi = min(math.ceil(pair_of(beta_slow)), half - 1)
        ramp = (jnp.arange(half, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3)
        keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)
        inv = inv * keep + (inv / factor) * (1.0 - keep)
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # (max_len, head_dim//2)
    return jnp.stack([jnp.cos(freqs), jnp.sin(freqs)], axis=-1)


def apply_rope(
    x: jax.Array, table: jax.Array, positions: jax.Array | None = None, *,
    rotate_half: bool = False,
) -> jax.Array:
    """Rotary position embedding. ``x``: (B, S, H, D); table from
    :func:`rope_frequencies` (at least S rows, or indexed by ``positions``).
    Dimension ``2i`` turns with ``2i + 1``, or with ``rotate_half`` dimension
    ``i`` with ``i + D/2`` (the layout of checkpoints whose rotary part is
    ``x cos + rotate_half(x) sin``); a partial rotary passes the leading
    dimensions alone, with a table of their width."""
    b, s, h, d = x.shape
    if positions is None:
        cs = table[:s]  # (S, D/2, 2)
    else:
        # clamped lookup: window lanes past a slot's block table carry
        # positions >= max_len by design (they scatter to trash and are
        # masked everywhere) — unclamped, jnp's out-of-bounds NaN fill
        # would ride the K rows into the pool and poison even excluded
        # attention rows via 0 * NaN in the output matmul
        cs = table[
            jnp.minimum(positions, table.shape[0] - 1)
        ]  # (B?, S, D/2, 2) — positions (S,) or (B, S)
    cos = cs[..., 0]
    sin = cs[..., 1]
    xf = jnp.asarray(x, jnp.float32)
    if rotate_half:
        x1, x2 = xf[..., : d // 2], xf[..., d // 2 :]
    else:  # reshape to pairs
        xf = xf.reshape(b, s, h, d // 2, 2)
        x1, x2 = xf[..., 0], xf[..., 1]
    if cos.ndim == 2:  # (S, D/2) -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # (B, S, D/2)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    if rotate_half:
        out = jnp.concatenate([r1, r2], axis=-1)
    else:
        out = jnp.stack([r1, r2], axis=-1).reshape(b, s, h, d)
    return out.astype(x.dtype)
