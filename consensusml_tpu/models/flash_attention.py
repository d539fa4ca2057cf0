"""Pallas TPU flash attention (forward + backward kernels, custom VJP).

Each (q-block, kv-block) tile stays in VMEM with MXU matmuls and the
online-softmax recurrence — the flash-attention-2 schedule — and the
custom VJP's backward recomputes tiles from the saved logsumexp instead
of storing S x S probabilities: ONE kernel, a program per kv block, makes
each tile's scores and probabilities once and writes ``dk`` and ``dv``
and its share of ``dq`` (five matmuls and one ``exp`` a tile, where a dq
kernel and a dk/dv kernel took seven and two). What the kernels cost on
a v5e, and which change bought what, is in PERF.md section 6; the short
of it: at 64-wide heads both are bound by MXU passes (a 64-deep or
64-wide matmul fills half the array), so the levers are fewer passes, on
masked-away scores and made twice, and no idle MXU between a tile's
matmuls.

Layout and schedule notes (TPU-specific):
- inputs (B, S, H, D) fold to (B*H, S, D) and pad to a block multiple;
  padded keys are masked by position, padded query rows are sliced off;
- matmul operands go to the MXU in the dtype they arrive in (bfloat16 in
  training; float32 inputs are cast nowhere), accumulation, softmax and
  the running statistics are float32; ``scale`` is folded into q (or k)
  once per block;
- a head whose tile grid is small runs as ONE program of straight-line
  code: which tiles lie above the diagonal (skipped), which it crosses
  or which hold padded keys (masked) and which need no mask is static,
  and the scheduler overlaps one tile's matmuls with another's softmax.
  Longer sequences, and the ring path's dynamic offsets, run one program
  per block that loops over its tiles (:func:`_schedule`);
- the per-row logsumexp is stored REPLICATED across a 128-lane minor
  dim, rows on sublanes; the backward kernel, which holds its scores
  transposed, turns it per tile, and takes ``delta`` as a row;
- the backward adds each tile's ``dq`` into a whole head's float32 ``dq``
  in VMEM (``(S_pad, D_k)``: 4 MiB at 8,192 tokens of 128-wide heads),
  kept across the head's programs and written once after the last.

Supports causal and full self-attention, plus an optional per-key
padding mask (``kv_mask``, (B, S) with 1 = attend): the only "bias" the
BERT workload needs, carried as one f32 row per batch instead of a full
(B, H, S, T) bias tile — padded keys drop out of the online softmax in
every kernel (arbitrary additive score biases remain on the XLA
blockwise path).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from consensusml_tpu.obs import get_registry
from consensusml_tpu.pallas_util import call_once, interpret_arg, out_struct

__all__ = ["flash_attention"]

_NEG_INF = -1e30
_BQ = 512
_BK = 512
_LANE = 128
# a head's tiles (the whole grid's) up to which its schedule is straight-line
# code: past it the spills of so many tiles in flight outgrow scoped VMEM
_UNROLL_TILES = 16
_VMEM_DEFAULT = 16 * 2**20  # the compiler's scoped-VMEM limit for a kernel


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


def _tiles(s_pad: int, d: int) -> tuple[int, int]:
    """(bq, bk) of the straight-line schedule for a ``(s_pad, d)`` call,
    from the static shape alone: half of ``_BQ`` / ``_BK`` (never under
    two lane widths) while that leaves at most four blocks a side, so that
    the diagonal's tiles, half of whose work is masked away, are small
    where they are most of the work; the whole of them for longer
    sequences (and whenever the schedule is a loop, :func:`_schedule`).
    At 64-wide heads on a v5e every kernel is bound by MXU passes, and at
    ``s_pad`` 1024 the forward and the two backward kernels it had then
    (dq, dk/dv) took 0.32 / 0.37 / 0.48 ms at 256 x 256 against 0.33 /
    0.43 / 0.60 at 512 x 512 (PERF.md section 6); 128 lost to both, and the
    one backward kernel keeps their tiles. Wider heads have been measured
    only where the schedule is the loop, which takes ``_BQ`` x ``_BK`` whatever this
    returns: 8,192 tokens of 32 heads of 128 (45.0 ms a round of two steps,
    43% of the kernels' roofline, PR 27 to 30) and of 16 heads of 256 (40.8
    ms for the same 4,096 total width, 47.9% of the roofline:
    ``flash_attn_roofline.train`` in that cell, PERF.md section 6, PR 31);
    both compile inside the VMEM they ask for (``_launch``), d = 256 under
    ``vmap`` and a checked ``shard_map`` too
    (tests/test_flash_compile_tpu.py). The straight-line tiles at 128 and
    256 wide are compiled for the described v5e there and not timed."""
    del d  # the rule was timed at 64 wide; 128 and 256 wide ran the loop alone, which ignores it
    half = lambda b: max(b // 2, min(b, 2 * _LANE))
    bq, bk = half(_BQ), half(_BK)
    if s_pad // min(bq, bk) <= 4:
        return bq, bk
    return _BQ, _BK


def _kv_runs(xp, qi, bq, bk, nk, s_real, causal, has_mask):
    """kv tiles of q block ``qi`` when q and k share the origin:
    ``[0, plain)`` need no mask, ``[plain, end)`` are crossed by the
    diagonal, hold padded keys or carry a ``kv_mask``, ``[end, nk)`` lie
    above the diagonal and are skipped. ``xp`` is ``numpy`` over ints (the
    trace-time count, the straight-line schedule) or ``jax.numpy`` over a
    program id (a loop's bounds): one arithmetic for all."""
    # never under one tile, and said so: a loop the compiler knows to run
    # at least once costs the looped forward a sixth less (PERF.md, PR 26)
    end = xp.clip(((qi + 1) * bq + bk - 1) // bk, 1, nk) if causal else nk
    if has_mask:
        return 0, end
    plain = s_real // bk  # tiles before the first padded key
    if causal:
        plain = xp.minimum(plain, (qi * bq + 1) // bk)
    return plain, end


def _q_runs(xp, kj, bq, bk, nq, s_real, causal, has_mask):
    """The same for kv block ``kj``'s q tiles: ``[0, start)`` skipped,
    ``[start, masked_end)`` masked, ``[masked_end, nq)`` plain."""
    # capped, for the same reason as ``end`` above: nq - start >= 1
    start = xp.minimum((kj * bk) // bq, nq - 1) if causal else 0
    if has_mask:
        return start, nq
    masked_end = (
        xp.minimum(nq, ((kj + 1) * bk - 1 + bq - 1) // bq) if causal else start
    )
    if s_real < nq * bq:  # some kv block holds padded keys: all its tiles mask
        masked_end = xp.where((kj + 1) * bk > s_real, nq, masked_end)
    return start, masked_end


def tile_plan(s_pad, s_real, bq, bk, causal, has_mask=False) -> dict:
    """{kernel: {kind: tiles}} of one (batch, head) slice of an aligned
    call, counted from the functions that bound the kernels' loops: the
    forward's by q block (``_kv_runs``), the backward's by kv block
    (``_q_runs``)."""
    nq, nk = s_pad // bq, s_pad // bk
    rows = {"plain": 0, "masked": 0, "skipped": 0}
    cols = dict(rows)
    for qi in range(nq):
        plain, end = _kv_runs(np, qi, bq, bk, nk, s_real, causal, has_mask)
        rows["plain"] += int(plain)
        rows["masked"] += int(end - plain)
        rows["skipped"] += int(nk - end)
    for kj in range(nk):
        start, masked_end = _q_runs(np, kj, bq, bk, nq, s_real, causal, has_mask)
        cols["skipped"] += int(start)
        cols["masked"] += int(masked_end - start)
        cols["plain"] += int(nq - masked_end)
    return {"fwd": rows, "bwd": cols}


def _schedule(kernel, aligned, bh, s_pad, s_real, d, causal, has_mask):
    """(bq, bk, unrolled) of one kernel call, and its trace-time tile
    accounting (the program replays, so the steady-state cost is zero).

    ``unrolled``: a head with few tiles runs as ONE program of
    straight-line code, every tile's bounds and body (plain or masked)
    static, so the scheduler overlaps one tile's matmuls with another's
    softmax. Otherwise a program per block loops over its tiles, every
    one through the masked body (the compare and select hide under the
    MXU; a second body costs more than it saves). With dynamic offsets
    (the ring path) which tiles contribute is the data's to say."""
    bq, bk = _tiles(s_pad, d)
    unrolled = aligned and (s_pad // bq) * (s_pad // bk) <= _UNROLL_TILES
    if not unrolled:  # a loop pays per tile: the largest (looped 256s take 1.5 x)
        bq, bk = _BQ, _BK
    kinds = tile_plan(s_pad, s_real, bq, bk, causal, has_mask)[kernel]
    if not aligned:
        kinds = {"runtime": sum(kinds.values())}
    elif not unrolled:
        kinds = {"plain": 0, "masked": kinds["plain"] + kinds["masked"],
                 "skipped": kinds["skipped"]}
    for kind, n in kinds.items():
        get_registry().counter(
            "consensusml_flash_tiles_total",
            "flash-attention tiles traced, by kernel and by the body they take",
            labels={"kernel": kernel, "kind": kind},
        ).inc(bh * n)
    return bq, bk, unrolled


def _tile_iotas(shape, transposed, causal, padded):
    """What :func:`_tile_mask` compares, made once per block and not per
    tile: ``query index - key index`` and ``key index`` over a tile that is
    (queries, keys), or (keys, queries) when ``transposed``."""
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    ks = jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
    diff = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) - ks
    return (diff if causal else None), (ks if padded else None)


def _tile_mask(iotas, q0, k0, k_local0, s_real, km):
    """Boolean for one masked tile, or None where nothing masks. Queries
    are absolute positions ``q0 + i``, keys ``k0 + j`` (local
    ``k_local0 + j``, padded from ``s_real`` on). ``km`` is the tile's
    slice of the per-key mask, a row or a column that broadcasts along the
    queries."""
    diff, ks = iotas
    mask = None
    if diff is not None:  # causal: q0 + i >= k0 + j
        mask = diff >= (k0 - q0)
    if ks is not None:  # padded keys
        tail = ks < (s_real - k_local0)
        mask = tail if mask is None else mask & tail
    if km is not None:
        mask = km if mask is None else mask & km
    return mask


def _blocks(unrolled, n, size):
    """[(index, rows)] of the blocks one program covers: all ``n`` of the
    head as static slices of full-length refs, or the grid's own."""
    if unrolled:
        return [(i, pl.ds(i * size, size)) for i in range(n)]
    return [(pl.program_id(1), slice(None))]


def _sweep(unrolled, tile, segments, init):
    """``tile(t, carry, masked)`` over ``segments`` = [(lo, hi, masked)],
    contiguous and in order; ``masked`` is static, bound here. Unrolled:
    straight-line code over static bounds, each tile with its own body,
    the first one handed ``None`` (nothing to add to yet). Else ONE loop
    over the whole span through the masked body, started from ``init()``."""
    if not unrolled:
        body = functools.partial(tile, masked=True)
        return jax.lax.fori_loop(segments[0][0], segments[-1][1], body, init())
    carry = None
    for lo, hi, masked in segments:
        for t in range(int(lo), int(hi)):
            carry = tile(t, carry, masked)
    return carry


def _start(t, size):
    """First row of tile ``t``, aligned and known to be."""
    return t * size if isinstance(t, int) else pl.multiple_of(t * size, size)


def _scaled(x, scale):
    """``x * scale`` in float32, back in ``x``'s dtype: once per block,
    instead of once per score tile."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    """MXU matmul on the operands as they come (bfloat16 in training),
    accumulated in float32."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(
    causal, aligned, unrolled, s_real, scale, bq, bk, has_mask,
    qoff_ref, koff_ref, kvm_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
):
    """Stream kv tiles past a q block with the online softmax.

    ``aligned`` (static) means q and k share the origin (plain
    self-attention): the tile runs then say which tiles are skipped and
    which need no mask. The ring path passes dynamic offsets (SMEM
    scalars) and keeps the full loop.
    """
    s_pad, d = v_ref.shape[1:]  # the accumulator is as wide as the values
    nk = s_pad // bk
    padded = s_real < s_pad
    xp = np if unrolled else jnp
    iotas = _tile_iotas((bq, bk), False, causal, padded)
    for qi, rows in _blocks(unrolled, s_pad // bq, bq):
        q = _scaled(q_ref[0, rows, :], scale)  # (bq, d_k)
        q0 = qoff_ref[0, 0] + qi * bq
        koff = koff_ref[0, 0]

        def tile(j, carry, masked):
            k0 = _start(j, bk)
            k = k_ref[0, pl.ds(k0, bk), :]  # (bk, d_k)
            v = v_ref[0, pl.ds(k0, bk), :]
            s = _dot(q, k, _NT)  # (bq, bk) f32
            if masked:
                km = _kvm_row(kvm_ref, k0, bk) if has_mask else None
                mask = _tile_mask(iotas, q0, koff + k0, k0, s_real, km)
                if mask is not None:
                    s = jnp.where(mask, s, _NEG_INF)
            m_blk = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
            if carry is None:  # a block's first tile: nothing to rescale
                p = jnp.exp(s - m_blk)
                return (
                    _dot(p.astype(v.dtype), v, _NN), m_blk,
                    jnp.sum(p, axis=1, keepdims=True),
                )
            acc, m, l = carry
            m_new = jnp.maximum(m, m_blk)
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)  # (bq, bk)
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * corr + _dot(p.astype(v.dtype), v, _NN)
            return acc_new, m_new, l_new

        plain, end = (
            _kv_runs(xp, qi, bq, bk, nk, s_real, causal, has_mask)
            if aligned else (0, nk)
        )
        acc, m, l = _sweep(
            unrolled, tile, [(0, plain, False), (plain, end, True)],
            lambda: (
                jnp.zeros((bq, d), jnp.float32),
                jnp.full((bq, 1), _NEG_INF, jnp.float32),
                jnp.zeros((bq, 1), jnp.float32),
            ),
        )
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l_safe).astype(o_ref.dtype)
        # per-row logsumexp, replicated across the lane dim (no transpose).
        # Fully-masked rows keep m = -inf => lse ~ -inf, so a later merge
        # weights them to zero (the ring path relies on this).
        lse_ref[0, rows, :] = jnp.broadcast_to(m + jnp.log(l_safe), (bq, _LANE))


def _kvm_rows(kv_mask, heads):
    """The per-key padding mask as the kernels read it: ``(B*heads, 1,
    S_pad)``, so each program's block is ``(1, 1, S_pad)`` indexed by the
    batch*head grid id directly (a one-lane dummy without a mask). The
    detours that do NOT work: a ``(1, S_pad)`` block on a ``(B, S_pad)``
    array violates Mosaic's block rule (sublane dim must divide 8 or equal
    the array's — B is neither), a ``b // heads`` index map lowers
    sign-correction selects Mosaic rejects, and an in-kernel dynamic
    sublane pick breaks the interpreter's lowering. With the leading axis
    folded to batch*heads and a unit sublane dim, the block equals the
    array on its last two dims — legal everywhere, and the replication
    costs B*heads*S_pad f32 (a few hundred KiB)."""
    if kv_mask is None:
        return jnp.ones((1, 1, _LANE), jnp.float32)
    return jnp.repeat(kv_mask, heads, axis=0)[:, None, :]


def _kvm_row(kvm_ref, start, size):
    """(1, size) slice of this program's key-mask row."""
    return kvm_ref[0, :, pl.ds(start, size)] > 0.0


def _kvm_col(kvm_ref, start, size):
    """The same slice as a (size, 1) column, for a transposed tile."""
    row = kvm_ref[0, :, pl.ds(start, size)]  # (1, size) f32
    return jnp.transpose(jnp.broadcast_to(row, (8, size)))[:, :1] > 0.0


_TRACED: dict = {}  # pallas_util.call_once keeps each kernel's one trace here


def _launch(
    name, kernel, tensors, layout, outs, causal, s_real, scale, interpret,
    q_offset, k_offset, kv_mask, heads, scratch=(),
):
    """One of the two kernels over ``tensors`` (each (BH, S_pad, width),
    or (BH, 1, S_pad) rows), under the schedule its shape gets.

    ``layout`` says per tensor how a program sees it: ``"block"`` (the
    program's own block of rows: of queries forward, of keys backward),
    ``"whole"`` (all rows of the head, sliced per tile) or ``"row"``;
    under the straight-line schedule a program IS a head and both are
    whole. ``outs``: (layout, width, dtype) of each output, a ``"whole"``
    one staying put while the program's blocks go by. ``scratch``:
    (rows, width) of each float32 VMEM buffer, kept across a head's
    programs.
    """
    bh, s_pad, d = tensors[0].shape
    aligned, qoff, koff = _offsets_smem(q_offset, k_offset)
    has_mask = kv_mask is not None
    bq, bk, unrolled = _schedule(
        name, aligned, bh, s_pad, s_real, d, causal, has_mask
    )
    block = bk if name == "bwd" else bq
    kvm = _kvm_rows(kv_mask, heads)

    def spec(kind, rows_of, width):
        vmem = pltpu.VMEM
        if kind == "row":  # (n, 1, width): the key mask (n = 1: its dummy), delta
            at = (lambda b, *_: (b, 0, 0)) if rows_of > 1 else (lambda *_: (0, 0, 0))
            return pl.BlockSpec((1, 1, width), at, memory_space=vmem)
        if unrolled:
            return pl.BlockSpec((1, s_pad, width), lambda b: (b, 0, 0), memory_space=vmem)
        if kind == "block":
            return pl.BlockSpec((1, block, width), lambda b, i: (b, i, 0), memory_space=vmem)
        return pl.BlockSpec((1, s_pad, width), lambda b, i: (b, 0, 0), memory_space=vmem)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands = (qoff, koff, kvm, *tensors)
    statics = (causal, aligned, unrolled, s_real, scale, bq, bk, has_mask)
    # what a program keeps in VMEM, each block double-buffered: a head's whole
    # q, do and lane-replicated lse at 8,192 tokens of 128-wide heads are
    # 16 MB, the default scoped limit, and the backward keeps a head's dq
    # beside them, as its output and as its float32 sum. Only such a call
    # asks for more (a v5e has 128 MiB); the shorter ones compile as they did.
    rows_of = {"row": 1, "block": s_pad if unrolled else block, "whole": s_pad}
    resident = 2 * (
        sum(rows_of[kind] * x.shape[-1] * x.dtype.itemsize for kind, x in zip(layout, tensors))
        + sum(rows_of[kind] * width * jnp.dtype(dtype).itemsize for kind, width, dtype in outs)
    ) + sum(rows * width * 4 for rows, width in scratch)
    params = {}
    if resident > _VMEM_DEFAULT // 2:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=resident + _VMEM_DEFAULT
        )
    call = pl.pallas_call(
        functools.partial(kernel, *statics),
        grid=(bh,) if unrolled else (bh, s_pad // block),
        interpret=interpret_arg(interpret, *operands),
        **params,
        in_specs=[smem, smem, spec("row", *kvm.shape[::2])]
        + [spec(kind, *x.shape[::2]) for kind, x in zip(layout, tensors)],
        out_specs=[spec(kind, bh, width) for kind, width, _ in outs],
        out_shape=[
            out_struct((bh, s_pad, width), dtype, *operands)
            for _, width, dtype in outs
        ],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
    )
    return call_once(_TRACED, (name, interpret, *statics), call, operands)


def _fwd(
    q3, k3, v3, causal: bool, s_real: int, scale: float,
    interpret: bool = False,
    q_offset=None, k_offset=None,
    kv_mask=None, heads: int = 1,
):
    """q3/k3: (BH, S_pad, D_k), v3: (BH, S_pad, D_v) -> (o (BH,S_pad,D_v),
    lse (BH,S_pad,LANE)).

    ``q_offset``/``k_offset``: absolute positions of row 0 (traced int32
    scalars, e.g. a ring rank index) — None means 0/0, which also enables
    the causal block-skip fast path. ``kv_mask``: padded (B, S_pad) f32
    per-key mask (>0 = attend), ``heads`` folding the BH grid index back
    to a batch row.
    """
    return _launch(
        "fwd", _fwd_kernel, (q3, k3, v3), ("block", "whole", "whole"),
        [("block", v3.shape[-1], q3.dtype), ("block", _LANE, jnp.float32)],
        causal, s_real, scale, interpret, q_offset, k_offset, kv_mask, heads,
    )


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_kernel(
    causal, aligned, unrolled, s_real, scale, bq, bk, has_mask,
    qoff_ref, koff_ref, kvm_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_acc,
):
    """All three gradients in one pass: a program owns a kv block and
    streams the q tiles past it, computing each tile's scores and
    probabilities ONCE (five matmuls and one ``exp`` a tile).

    Scores are held TRANSPOSED, (bk, bq) with keys on sublanes: ``p.T``
    and ``ds.T`` are then the streamed left operands of the kv block's
    gradient matmuls as they stand (``dv = p.T @ do``, ``dk = ds.T @ q``).
    So the per-row scalars are wanted as (1, bq) rows: ``delta`` comes as
    one, ``lse`` as the forward's (bq, 128) lane-replicated column and is
    turned per tile. The tile's share of ``dq`` (``ds @ k``, contracted
    over the block's keys) is added into ``dq_acc``, a whole head's float32
    ``dq`` in VMEM: zeroed before the head's first kv block, scaled, cast
    and written after its last, to an output block that stays put across
    the kv axis."""
    s_pad, d = q_ref.shape[1:]
    d_v = v_ref.shape[-1]
    nq, nk = s_pad // bq, s_pad // bk
    padded = s_real < s_pad
    xp = np if unrolled else jnp
    iotas = _tile_iotas((bk, bq), True, causal, padded)

    @_once_a_head(unrolled, 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    for kj, rows in _blocks(unrolled, nk, bk):
        k_in = k_ref[0, rows, :]  # (bk, d): dq's operand, its scale applied to the sum
        k = _scaled(k_in, scale)
        v = v_ref[0, rows, :]
        k_local0 = kj * bk
        k0 = koff_ref[0, 0] + k_local0
        qoff = qoff_ref[0, 0]
        # this kv block's slice of the per-key mask: the same for every q tile
        km = _kvm_col(kvm_ref, k_local0, bk) if has_mask else None

        def tile(i, carry, masked):
            r0 = _start(i, bq)
            q = q_ref[0, pl.ds(r0, bq), :]
            do = do_ref[0, pl.ds(r0, bq), :]
            lse = _row(lse_ref[0, pl.ds(r0, bq), :])  # (1, bq)
            delta = delta_ref[0, :, pl.ds(r0, bq)]
            p = jnp.exp(_dot(k, q, _NT) - lse)  # (bk, bq)
            if masked:
                mask = _tile_mask(iotas, qoff + r0, k0, k_local0, s_real, km)
                if mask is not None:
                    p = jnp.where(mask, p, 0.0)
            dv = _dot(p.astype(do.dtype), do, _NN)  # (bk, d_v)
            ds = (p * (_dot(v, do, _NT) - delta)).astype(q.dtype)
            dk = _dot(ds, q, _NN)
            dq_acc[pl.ds(r0, bq), :] += _dot(ds, k_in, _TN)  # (bq, d)
            return (dk, dv) if carry is None else (carry[0] + dk, carry[1] + dv)

        # q blocks strictly above this kv block's diagonal never see it
        start, masked_end = (
            _q_runs(xp, kj, bq, bk, nq, s_real, causal, has_mask)
            if aligned else (0, nq)
        )
        dk, dv = _sweep(
            unrolled, tile, [(start, masked_end, True), (masked_end, nq, False)],
            lambda: (jnp.zeros((bk, d), jnp.float32),) * 2 if d_v == d else (
                jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d_v), jnp.float32)),
        )
        dk_ref[0, rows, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)

    @_once_a_head(unrolled, nk - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _once_a_head(unrolled, kj):
    """Run the decorated body once a head, at its kv block ``kj`` (the
    first or the last): in place where a program is the whole head, under
    ``pl.when`` where the grid's second axis walks its blocks."""
    def run(body):
        if unrolled:
            body()
        else:
            pl.when(pl.program_id(1) == kj)(body)
    return run


def _row(col):
    """(n, 128) lane-replicated per-row scalars -> the same as a (1, n) row."""
    return jnp.transpose(col)[:1, :]


def _offsets_smem(q_offset, k_offset):
    aligned = q_offset is None and k_offset is None
    qoff = jnp.reshape(
        jnp.asarray(0 if q_offset is None else q_offset, jnp.int32), (1, 1)
    )
    koff = jnp.reshape(
        jnp.asarray(0 if k_offset is None else k_offset, jnp.int32), (1, 1)
    )
    return aligned, qoff, koff


def _bwd_call(
    q3, k3, v3, do3, lse, delta, causal, s_real, scale, interpret,
    q_offset=None, k_offset=None, kv_mask=None, heads: int = 1,
):
    """(dq, dk, dv) of local queries against a (possibly offset) kv span:
    ``dq`` is this span's share (the ring path adds its rotations' up).
    ``delta``: ``rowsum(do * o)`` as (BH, 1, S_pad) float32 rows."""
    d = q3.shape[-1]
    return _launch(
        "bwd", _bwd_kernel, (q3, k3, v3, do3, lse, delta),
        ("whole", "block", "block", "whole", "whole", "row"),
        [("whole", d, q3.dtype), ("block", d, q3.dtype),
         ("block", v3.shape[-1], q3.dtype)],
        causal, s_real, scale, interpret, q_offset, k_offset, kv_mask, heads,
        scratch=[(q3.shape[1], d)],
    )


def _bwd(causal, s_real, scale, interpret, heads, res, do3):
    q3, k3, v3, kvm, o3, lse = res
    do3 = do3.astype(v3.dtype)  # an MXU operand beside v
    return _bwd_call(
        q3, k3, v3, do3, lse, delta_rows(do3, o3), causal, s_real, scale,
        interpret, kv_mask=kvm, heads=heads,
    )


def delta_rows(do3, o3):
    """``rowsum(do * o)`` in float32 as (BH, 1, S_pad): the layout the
    backward kernel reads (the ring path shares it)."""
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)
    return delta[:, None, :]


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
    scale: float | None = None,
) -> jax.Array:
    """Fused Pallas self-attention (same contract as
    ``dot_product_attention``). ``q`` and ``k`` share a shape (B, S, H, D_k);
    ``v`` is (B, S, H, D_v) and the output as wide as ``v``: the widths may
    differ (latent attention's 192-wide keys beside 128-wide values; a width
    that is no multiple of the 128 lanes is a block as wide as the array, the
    compiler pads its last vreg). ``scale`` multiplies the scores, ``D_k^-1/2``
    when None.

    ``kv_mask`` is the per-key padding mask ((B, S), nonzero = attend):
    the BERT attention_mask, applied inside every kernel's online
    softmax. Arbitrary additive biases are NOT supported — use the
    blockwise path for those.
    """
    b, s, h, d = q.shape
    if k.shape != q.shape:
        raise ValueError(
            f"flash_attention is self-attention-shaped, k as wide as q: q{q.shape} k{k.shape}"
        )
    if v.shape[:-1] != q.shape[:-1]:
        raise ValueError(
            f"flash_attention is self-attention-shaped, v's rows and heads q's "
            f"(its width alone may differ): q{q.shape} v{v.shape}"
        )
    if scale is None:
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
    o = o3[:, :s].reshape(b, h, s, v.shape[-1])
    return jnp.moveaxis(o, 1, 2).astype(dtype)
