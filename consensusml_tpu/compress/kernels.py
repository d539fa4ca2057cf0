"""Pallas TPU kernels for the compression hot paths.

Reference parity: the CUDA gradient-compression kernels (BASELINE.json
north_star: "CUDA gradient-compression and top-k sparsification kernels
become Pallas kernels"; SURVEY.md L0 — mount empty). Numerical semantics
are defined by :mod:`consensusml_tpu.compress.reference` and enforced by
parity tests (tests/test_kernels.py).

Layout strategy: tensors are flattened and chunked to ``(nchunks, chunk)``
with ``chunk`` a multiple of 128 (VPU lane width). Each grid step processes
a sublane-aligned row-block entirely in VMEM:

- int8 quantize: rowwise absmax -> scale -> round-to-nearest-even, one
  pass, fused (the reference needs separate absmax + quantize CUDA
  launches; here it is one VMEM-resident kernel).
- chunked top-k: per chunk, k iterative max-extractions on the VPU
  (k passes over a VMEM-resident row — no full sort, no HBM traffic).

On non-TPU backends the same kernels run under the Pallas interpreter
(tests), and the ``auto`` dispatch falls back to the jnp reference.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from consensusml_tpu.pallas_util import interpret_arg, on_tpu, out_struct
from consensusml_tpu.compress.base import (
    FP8_E4M3_MAX,
    ComposedCompressor,
    Compressor,
    Fp8Payload,
    Int4Payload,
    Int8Payload,
    LocalTopKPayload,
    TopKPayload,
)

__all__ = [
    "ChunkedTopKCompressor",
    "PallasInt8Compressor",
    "PallasInt4Compressor",
    "PallasFp8Compressor",
    "FusedBucketCodec",
    "fused_bucket_codec",
    "resolve_codec_impl",
    "describe_codec",
    "quantize_int8",
    "dequantize_int8",
    "quantize_int4",
    "dequantize_int4",
    "quantize_fp8",
    "dequantize_fp8",
    "chunked_topk",
]

_LANE = 128
_SUBLANE_F32 = 8
_SUBLANE_I8 = 32


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


# VMEM discipline: cap each block's widest f32 buffer at ~2 MiB
# (_BLOCK_ELEM_BUDGET f32 elements). The compressors permit chunk widths
# up to 65536 (the narrow-indices bound), where a fixed 256-row block
# would be a 64 MiB buffer that can never fit VMEM; deriving rows from
# the budget keeps wide chunks legal while leaving the measured 256-row
# blocking untouched at the shipped chunk sizes (256 rows only shrinks
# once chunk exceeds 2048). Floored at the sublane multiple — a hard
# layout constraint, so extreme widths may still exceed the budget by
# design rather than fail to tile.
_BLOCK_ELEM_BUDGET = 512 * 1024


def _block_rows(rows: int, width: int, sublane: int) -> int:
    cap = _BLOCK_ELEM_BUDGET // max(width, 1)
    cap = max((cap // sublane) * sublane, sublane)
    return min(rows, 256, cap)


# ---------------------------------------------------------------------------
# int8 quantize / dequantize
# ---------------------------------------------------------------------------


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q_ref[:] = jnp.clip(jnp.rint(x * inv), -127, 127).astype(jnp.int8)
    s_ref[:] = scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int8(chunks: jax.Array, *, interpret: bool = False):
    """Quantize ``(nchunks, chunk)`` f32 rows to int8 + per-row scales.

    Returns ``(q (nchunks, chunk) int8, scales (nchunks,) f32)``. ``chunk``
    must be a multiple of 128; rows are padded to the int8 sublane multiple
    internally and sliced back.
    """
    nchunks, chunk = chunks.shape
    rows = _round_up(max(nchunks, _SUBLANE_I8), _SUBLANE_I8)
    block_rows = _block_rows(rows, chunk, _SUBLANE_I8)
    rows = _round_up(rows, block_rows)
    if rows != nchunks:
        chunks = jnp.pad(chunks, ((0, rows - nchunks), (0, 0)))
    q, scales = pl.pallas_call(
        _quant_kernel,
        name="codec_quant_int8",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            out_struct((rows, chunk), jnp.int8, chunks),
            out_struct((rows, 1), jnp.float32, chunks),
        ],
        interpret=interpret_arg(interpret, chunks),
    )(chunks)
    return q[:nchunks], scales[:nchunks, 0]


def _dequant_kernel(q_ref, s_ref, out_ref):
    out_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_int8(q: jax.Array, scales: jax.Array, *, interpret: bool = False):
    """Inverse of :func:`quantize_int8`."""
    nchunks, chunk = q.shape
    rows = _round_up(max(nchunks, _SUBLANE_I8), _SUBLANE_I8)
    block_rows = _block_rows(rows, chunk, _SUBLANE_I8)
    rows = _round_up(rows, block_rows)
    if rows != nchunks:
        q = jnp.pad(q, ((0, rows - nchunks), (0, 0)))
        scales = jnp.pad(scales, (0, rows - nchunks))
    out = pl.pallas_call(
        _dequant_kernel,
        name="codec_dequant_int8",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=out_struct((rows, chunk), jnp.float32, q, scales),
        interpret=interpret_arg(interpret, q, scales),
    )(q, scales.reshape(-1, 1))
    return out[:nchunks]


# ---------------------------------------------------------------------------
# int4 quantize / dequantize (two values per byte, half-split pairing)
# ---------------------------------------------------------------------------


def _quant4_kernel(half: int, x_ref, p_ref, s_ref):
    x = x_ref[:]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / 7.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.rint(x * inv), -7, 7).astype(jnp.int32)
    lo = q[:, :half] & 0xF
    hi = (q[:, half:] & 0xF) << 4
    p_ref[:] = (lo | hi).astype(jnp.uint8)
    s_ref[:] = scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int4(chunks: jax.Array, *, interpret: bool = False):
    """Quantize ``(nchunks, chunk)`` f32 rows to packed int4 nibbles.

    Returns ``(packed (nchunks, chunk//2) uint8, scales (nchunks,) f32)``
    with byte ``j`` holding elements ``j`` (low nibble) and
    ``j + chunk//2`` (high) — one fused absmax→quantize→pack pass.
    ``chunk`` must be a multiple of 128.
    """
    nchunks, chunk = chunks.shape
    half = chunk // 2
    rows = _round_up(max(nchunks, _SUBLANE_I8), _SUBLANE_I8)
    block_rows = _block_rows(rows, chunk, _SUBLANE_I8)
    rows = _round_up(rows, block_rows)
    if rows != nchunks:
        chunks = jnp.pad(chunks, ((0, rows - nchunks), (0, 0)))
    packed, scales = pl.pallas_call(
        functools.partial(_quant4_kernel, half),
        name="codec_quant_int4",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((block_rows, half), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            out_struct((rows, half), jnp.uint8, chunks),
            out_struct((rows, 1), jnp.float32, chunks),
        ],
        interpret=interpret_arg(interpret, chunks),
    )(chunks)
    return packed[:nchunks], scales[:nchunks, 0]


def _dequant4_kernel(p_ref, s_ref, out_ref):
    b = p_ref[:].astype(jnp.int32)
    sext = lambda nib: jnp.where(nib > 7, nib - 16, nib)
    q = jnp.concatenate([sext(b & 0xF), sext(b >> 4)], axis=1)
    out_ref[:] = q.astype(jnp.float32) * s_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_int4(packed: jax.Array, scales: jax.Array, *, interpret: bool = False):
    """Inverse of :func:`quantize_int4`: ``(nchunks, half) uint8 ->
    (nchunks, 2*half) f32``."""
    nchunks, half = packed.shape
    rows = _round_up(max(nchunks, _SUBLANE_I8), _SUBLANE_I8)
    block_rows = _block_rows(rows, 2 * half, _SUBLANE_I8)
    rows = _round_up(rows, block_rows)
    if rows != nchunks:
        packed = jnp.pad(packed, ((0, rows - nchunks), (0, 0)))
        scales = jnp.pad(scales, (0, rows - nchunks))
    out = pl.pallas_call(
        _dequant4_kernel,
        name="codec_dequant_int4",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, half), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_rows, 2 * half), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=out_struct((rows, 2 * half), jnp.float32, packed, scales),
        interpret=interpret_arg(interpret, packed, scales),
    )(packed, scales.reshape(-1, 1))
    return out[:nchunks]


# ---------------------------------------------------------------------------
# fp8 (e4m3) quantize / dequantize
# ---------------------------------------------------------------------------


def _quant_fp8_kernel(x_ref, q_ref, s_ref):
    # ONE fp8 quantize definition: the fused wire's (bit-parity between
    # this standalone codec and FusedBucketCodec is a wire contract)
    q, scale, _ = _fused_quant(x_ref[:], "fp8")
    q_ref[:] = q
    s_ref[:] = scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_fp8(chunks: jax.Array, *, interpret: bool = False):
    """Quantize ``(nchunks, chunk)`` f32 rows to e4m3 + per-row scales:
    one fused absmax -> scale -> cast pass. ``chunk`` must be a multiple
    of 128. Returns ``(q (nchunks, chunk) f8e4m3, scales (nchunks,) f32)``."""
    nchunks, chunk = chunks.shape
    rows = _round_up(max(nchunks, _SUBLANE_I8), _SUBLANE_I8)
    block_rows = _block_rows(rows, chunk, _SUBLANE_I8)
    rows = _round_up(rows, block_rows)
    if rows != nchunks:
        chunks = jnp.pad(chunks, ((0, rows - nchunks), (0, 0)))
    q, scales = pl.pallas_call(
        _quant_fp8_kernel,
        name="codec_quant_fp8",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            out_struct((rows, chunk), jnp.float8_e4m3fn, chunks),
            out_struct((rows, 1), jnp.float32, chunks),
        ],
        interpret=interpret_arg(interpret, chunks),
    )(chunks)
    return q[:nchunks], scales[:nchunks, 0]


def dequantize_fp8(q: jax.Array, scales: jax.Array, *, interpret: bool = False):
    """Inverse of :func:`quantize_fp8`. The dequant math is dtype-driven
    (``q.astype(f32) * scale``), so this IS :func:`dequantize_int8`'s
    kernel fed e4m3 rows — one shared pad/grid/kernel definition."""
    return dequantize_int8(q, scales, interpret=interpret)


# ---------------------------------------------------------------------------
# chunked top-k
# ---------------------------------------------------------------------------


def _topk_kernel(k: int, kpad: int, x_ref, vals_ref, idx_ref):
    """Per row: k iterative max-|x| extractions (first index wins ties).

    Results accumulate in REGISTERS (a (R, kpad) carry written by masked
    selects) and are stored once as full aligned blocks at the end —
    Mosaic rejects per-iteration single-column VMEM stores because a
    dynamic lane offset can't be proven a multiple of the 128-lane tile
    (caught on real-TPU compile; the interpreter doesn't model it).
    """
    x = x_ref[:]  # (R, m) f32
    rows, m = x.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, m), 1)
    colk = jax.lax.broadcasted_iota(jnp.int32, (rows, kpad), 1)

    def body(j, carry):
        x_abs, vals, idxs = carry
        rowmax = jnp.max(x_abs, axis=1, keepdims=True)
        # first column index attaining the max
        hit = x_abs == rowmax
        idx = jnp.min(jnp.where(hit, col, m), axis=1, keepdims=True)  # (R,1)
        taken = col == idx
        val = jnp.sum(jnp.where(taken, x, 0.0), axis=1, keepdims=True)
        write = colk == j
        vals = jnp.where(write, val, vals)  # (R,1) broadcasts over kpad
        idxs = jnp.where(write, idx, idxs)
        # mask the taken column out for the next extraction
        return jnp.where(taken, -1.0, x_abs), vals, idxs

    # |x| as max(x, -x), not jnp.abs: inside shard_map a loaded block
    # is typed as varying over the manual axes, unary ops keep that type
    # and binary ops (the whole loop body) drop it, so a carry seeded by
    # abs() fails the loop's carry-type check at trace time. Same value.
    _, vals, idxs = jax.lax.fori_loop(
        0,
        k,
        body,
        (
            jnp.maximum(x, -x),
            jnp.zeros((rows, kpad), jnp.float32),
            jnp.zeros((rows, kpad), jnp.int32),
        ),
    )
    vals_ref[:] = vals
    idx_ref[:] = idxs


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def chunked_topk(chunks: jax.Array, k: int, *, interpret: bool = False):
    """Top-k by magnitude per row of ``(nchunks, chunk)``.

    Returns ``(values (nchunks, k), local_indices (nchunks, k) int32)``,
    ordered by decreasing magnitude, ties broken toward lower index —
    matching ``jax.lax.top_k`` on magnitudes.
    """
    nchunks, chunk = chunks.shape
    rows = _round_up(max(nchunks, _SUBLANE_F32), _SUBLANE_F32)
    # big row blocks: at full-model scale (~700k chunks) the grid-step
    # overhead dominates a small-block kernel; 256 rows x 512 lanes f32
    # is 512 KiB/buffer, comfortably inside VMEM with double buffering
    # (wider chunks shrink the block to honor the VMEM budget)
    block_rows = _block_rows(rows, chunk, _SUBLANE_F32)
    rows = _round_up(rows, block_rows)
    if rows != nchunks:
        chunks = jnp.pad(chunks, ((0, rows - nchunks), (0, 0)))
    kpad = _round_up(k, _LANE)
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k, kpad),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((block_rows, kpad), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, kpad), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            out_struct((rows, kpad), jnp.float32, chunks),
            out_struct((rows, kpad), jnp.int32, chunks),
        ],
        interpret=interpret_arg(interpret, chunks),
    )(chunks)
    return vals[:nchunks, :k], idx[:nchunks, :k]


# ---------------------------------------------------------------------------
# chunk-local scatter (decompress / decompress-accumulate)
# ---------------------------------------------------------------------------


def _scatter_kernel(k, has_acc, vals_ref, idx_ref, *rest):
    """Densify (R, k) chunk-local (value, index) pairs into (R, chunk).

    XLA's generic scatter-add costs ~69 ms for one full-model payload at
    GPT-2-medium scale (measured in-scan on a v5e) because it cannot see
    the structure: every chunk receives EXACTLY k values at in-chunk
    positions. Here each pass extracts pair j by masked reduction and
    places it by lane comparison — the same no-dynamic-lane-addressing
    trick as ``_topk_kernel``, so Mosaic never sees a data-dependent
    store offset. k passes over a VMEM-resident block, bandwidth-bound
    at the shipped k=8.
    """
    if has_acc:
        acc_ref, out_ref = rest
    else:
        (out_ref,) = rest
    vals = vals_ref[:]  # (R, kpad) f32
    idx = idx_ref[:]  # (R, kpad) i32
    rows, kpad = vals.shape
    c = out_ref.shape[1]
    colk = jax.lax.broadcasted_iota(jnp.int32, (rows, kpad), 1)
    colc = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 1)

    def body(j, dense):
        sel = colk == j
        v = jnp.sum(jnp.where(sel, vals, 0.0), axis=1, keepdims=True)
        i = jnp.sum(jnp.where(sel, idx, 0), axis=1, keepdims=True)
        # top-k emits distinct in-chunk indices; padded-tail pairs carry
        # value 0, so their (clamped) position adds nothing
        return dense + jnp.where(colc == i, v, 0.0)

    # the loop carries the densified payload alone, seeded by zeros, and
    # the accumulator joins after it: a carry seeded by a loaded block
    # fails the carry-type check inside shard_map (see _topk_kernel).
    # Every slot receives at most one non-zero term, so the sum is the
    # same bits in either order.
    dense = jax.lax.fori_loop(0, k, body, jnp.zeros((rows, c), jnp.float32))
    if has_acc:
        dense = acc_ref[:].astype(jnp.float32) + dense
    out_ref[:] = dense.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def chunk_scatter(
    vals: jax.Array,
    idx: jax.Array,
    chunk: int,
    acc: jax.Array | None = None,
    *,
    weight=1.0,
    interpret: bool = False,
) -> jax.Array:
    """``(nchunks, k)`` values + chunk-local indices -> dense
    ``(nchunks, chunk)`` f32, optionally ``acc + weight * dense``.

    ``weight`` is applied by pre-scaling the (tiny) values array, not
    inside the kernel: it stays traceable, costs one pass over
    ``nchunks*k`` elements, and never forces a per-weight recompile.
    """
    nchunks, k = vals.shape
    kpad = _round_up(k, _LANE)
    rows = _round_up(max(nchunks, _SUBLANE_F32), _SUBLANE_F32)
    block_rows = _block_rows(rows, chunk, _SUBLANE_F32)  # see chunked_topk
    rows = _round_up(rows, block_rows)
    vals = jnp.pad(
        jnp.asarray(vals, jnp.float32) * weight,
        ((0, rows - nchunks), (0, kpad - k)),
    )
    idx = jnp.pad(
        jnp.asarray(idx, jnp.int32), ((0, rows - nchunks), (0, kpad - k))
    )
    operands = [vals, idx]
    kspec = pl.BlockSpec(
        (block_rows, kpad), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    cspec = pl.BlockSpec(
        (block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    in_specs = [kspec, kspec]
    if acc is not None:
        operands.append(
            jnp.pad(
                jnp.asarray(acc, jnp.float32).reshape(nchunks, chunk),
                ((0, rows - nchunks), (0, 0)),
            )
        )
        in_specs.append(cspec)
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, k, acc is not None),
        grid=(rows // block_rows,),
        in_specs=in_specs,
        out_specs=cspec,
        out_shape=out_struct((rows, chunk), jnp.float32, *operands),
        interpret=interpret_arg(interpret, *operands),
    )(*operands)
    return out[:nchunks]


# ---------------------------------------------------------------------------
# codec classes (drop-in Compressor implementations)
# ---------------------------------------------------------------------------


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if on_tpu() else "jnp"
    return impl


def resolve_codec_impl(requested: str = "auto") -> str:
    """Resolve a CLI-level codec impl request to the KERNEL path: the
    compiled Pallas kernels on TPU, the Pallas interpreter elsewhere.

    This differs from the codecs' own ``impl="auto"`` (which falls back
    to the jnp reference off-TPU, the right default for the CPU test
    tier): ``train.py --codec int8/int4/fp8`` resolves through THIS so
    the selected codec always runs the kernel code path — previously
    "pallas auto" silently meant "jnp" on every non-TPU host and the
    reported codec never matched the executed one. Callers should log
    the resolved impl loudly (train.py prints one line)."""
    if requested != "auto":
        return requested
    return "pallas" if on_tpu() else "interpret"


@dataclasses.dataclass(frozen=True)
class PallasInt8Compressor(Compressor):
    """Per-chunk symmetric int8 codec backed by the Pallas kernels.

    ``impl``: "pallas" (compiled), "interpret" (Pallas interpreter — for
    CPU tests), "jnp" (reference math), or "auto" (pallas on TPU, jnp
    elsewhere). All produce identical payloads.
    """

    chunk: int = 512
    impl: str = "auto"

    def __post_init__(self):
        if self.chunk % _LANE:
            raise ValueError(f"chunk must be a multiple of {_LANE}, got {self.chunk}")

    def bucket_alignment(self) -> int | None:
        return self.chunk  # per-chunk scales decompose at chunk boundaries

    def fused_wire(self) -> str | None:
        return "int8"

    def compress(self, x: jax.Array) -> Int8Payload:
        n = x.size
        chunk = min(self.chunk, _round_up(n, _LANE))
        impl = _resolve_impl(self.impl)
        if impl == "jnp":
            from consensusml_tpu.compress.reference import Int8Compressor

            return Int8Compressor(chunk=chunk).compress(x)
        flat = jnp.asarray(x.reshape(-1), jnp.float32)
        pad = (-n) % chunk
        chunks = jnp.pad(flat, (0, pad)).reshape(-1, chunk)
        q, scales = quantize_int8(chunks, interpret=impl == "interpret")
        return Int8Payload(
            data=q.reshape(-1), scales=scales, shape=x.shape, dtype=x.dtype, chunk=chunk
        )

    def decompress(self, payload: Int8Payload) -> jax.Array:
        impl = _resolve_impl(self.impl)
        if impl == "jnp":
            from consensusml_tpu.compress.reference import Int8Compressor

            return Int8Compressor(chunk=payload.chunk).decompress(payload)
        q = payload.data.reshape(-1, payload.chunk)
        flat = dequantize_int8(
            q, payload.scales, interpret=impl == "interpret"
        ).reshape(-1)
        n = 1
        for d in payload.shape:
            n *= d
        return flat[:n].astype(payload.dtype).reshape(payload.shape)


@dataclasses.dataclass(frozen=True)
class PallasInt4Compressor(Compressor):
    """Per-chunk symmetric int4 codec backed by the fused Pallas kernels
    (same impl contract as :class:`PallasInt8Compressor`; payload format
    defined by :class:`~consensusml_tpu.compress.base.Int4Payload`)."""

    chunk: int = 512
    impl: str = "auto"

    def __post_init__(self):
        if self.chunk % _LANE:
            raise ValueError(f"chunk must be a multiple of {_LANE}, got {self.chunk}")

    def bucket_alignment(self) -> int | None:
        return self.chunk  # _LANE-multiple chunks are always even

    def fused_wire(self) -> str | None:
        return "int4"

    def compress(self, x: jax.Array) -> Int4Payload:
        n = x.size
        chunk = min(self.chunk, _round_up(n, _LANE))
        impl = _resolve_impl(self.impl)
        if impl == "jnp":
            from consensusml_tpu.compress.reference import Int4Compressor

            return Int4Compressor(chunk=chunk).compress(x)
        flat = jnp.asarray(x.reshape(-1), jnp.float32)
        pad = (-n) % chunk
        chunks = jnp.pad(flat, (0, pad)).reshape(-1, chunk)
        packed, scales = quantize_int4(chunks, interpret=impl == "interpret")
        return Int4Payload(
            data=packed.reshape(-1),
            scales=scales,
            shape=x.shape,
            dtype=x.dtype,
            chunk=chunk,
        )

    def decompress(self, payload: Int4Payload) -> jax.Array:
        impl = _resolve_impl(self.impl)
        if impl == "jnp":
            from consensusml_tpu.compress.reference import Int4Compressor

            return Int4Compressor(chunk=payload.chunk).decompress(payload)
        packed = payload.data.reshape(-1, payload.chunk // 2)
        flat = dequantize_int4(
            packed, payload.scales, interpret=impl == "interpret"
        ).reshape(-1)
        n = 1
        for d in payload.shape:
            n *= d
        return flat[:n].astype(payload.dtype).reshape(payload.shape)


@dataclasses.dataclass(frozen=True)
class PallasFp8Compressor(Compressor):
    """Per-chunk scaled e4m3 codec backed by the fused Pallas kernels
    (same impl contract as :class:`PallasInt8Compressor`; payload format
    defined by :class:`~consensusml_tpu.compress.base.Fp8Payload` and the
    reference semantics by :class:`~consensusml_tpu.compress.reference.
    Fp8Compressor`)."""

    chunk: int = 512
    impl: str = "auto"

    def __post_init__(self):
        if self.chunk % _LANE:
            raise ValueError(f"chunk must be a multiple of {_LANE}, got {self.chunk}")

    def bucket_alignment(self) -> int | None:
        return self.chunk  # per-chunk scales decompose at chunk boundaries

    def fused_wire(self) -> str | None:
        return "fp8"

    def compress(self, x: jax.Array) -> Fp8Payload:
        n = x.size
        chunk = min(self.chunk, _round_up(n, _LANE))
        impl = _resolve_impl(self.impl)
        if impl == "jnp":
            from consensusml_tpu.compress.reference import Fp8Compressor

            return Fp8Compressor(chunk=chunk).compress(x)
        flat = jnp.asarray(x.reshape(-1), jnp.float32)
        pad = (-n) % chunk
        chunks = jnp.pad(flat, (0, pad)).reshape(-1, chunk)
        q, scales = quantize_fp8(chunks, interpret=impl == "interpret")
        return Fp8Payload(
            data=q.reshape(-1), scales=scales, shape=x.shape, dtype=x.dtype, chunk=chunk
        )

    def decompress(self, payload: Fp8Payload) -> jax.Array:
        impl = _resolve_impl(self.impl)
        if impl == "jnp":
            from consensusml_tpu.compress.reference import Fp8Compressor

            return Fp8Compressor(chunk=payload.chunk).decompress(payload)
        q = payload.data.reshape(-1, payload.chunk)
        flat = dequantize_fp8(
            q, payload.scales, interpret=impl == "interpret"
        ).reshape(-1)
        n = 1
        for d in payload.shape:
            n *= d
        return flat[:n].astype(payload.dtype).reshape(payload.shape)


@dataclasses.dataclass(frozen=True)
class ChunkedTopKCompressor(Compressor):
    """Per-chunk (local) top-k sparsification.

    Unlike the global :class:`~consensusml_tpu.compress.TopKCompressor`
    (one exact top-k over the whole tensor via ``lax.top_k``), this selects
    ``k_per_chunk`` winners in every ``chunk``-sized block — the standard
    bandwidth/quality trade used by large-scale top-k systems, and the
    shape that maps onto a single-pass TPU kernel (each block's candidates
    never leave VMEM). Payload indices are global (chunk offset added), so
    decompression is the shared scatter.
    """

    chunk: int = 512
    k_per_chunk: int = 16
    impl: str = "auto"
    # uint16 chunk-local indices (LocalTopKPayload): halves the index
    # bytes, which dominate a small-k sparse payload's wire
    narrow_indices: bool = True

    # the kernel extracts one winner per pass (O(k) VMEM sweeps): great
    # for the small k sparsification uses, a loss past this point — fall
    # back to lax.top_k per chunk, which sorts once
    _KERNEL_MAX_K = 64

    def __post_init__(self):
        if self.chunk % _LANE:
            raise ValueError(f"chunk must be a multiple of {_LANE}, got {self.chunk}")
        if not 0 < self.k_per_chunk <= self.chunk:
            raise ValueError("k_per_chunk must be in (0, chunk]")
        if self.narrow_indices and self.chunk > 2**16:
            raise ValueError(
                f"narrow_indices stores chunk-local positions as uint16, so "
                f"chunk must be <= {2**16} (got {self.chunk}); pass "
                "narrow_indices=False for wider chunks"
            )

    def bucket_alignment(self) -> int | None:
        # selection is chunk-local: with every leaf chunk-aligned inside a
        # bucket, each chunk sees exactly one leaf's elements (plus inert
        # zero padding), so the decoded result matches the per-leaf path
        return self.chunk

    def select_impl(self) -> str:
        """What picks the winners: the k-pass kernel up to
        ``_KERNEL_MAX_K``, one ``lax.top_k`` sort per chunk ("jnp") past
        it — chosen from k, and named by :func:`describe_codec`."""
        impl = _resolve_impl(self.impl)
        if impl == "pallas" and self.k_per_chunk > self._KERNEL_MAX_K:
            return "jnp"
        return impl

    def compress(self, x: jax.Array) -> TopKPayload:
        flat = jnp.asarray(x.reshape(-1), jnp.float32)
        n = flat.size
        chunk = min(self.chunk, _round_up(n, _LANE))
        k = min(self.k_per_chunk, chunk)
        pad = (-n) % chunk
        chunks = jnp.pad(flat, (0, pad)).reshape(-1, chunk)
        impl = self.select_impl()
        if impl == "jnp":
            _, lidx = jax.lax.top_k(jnp.abs(chunks), k)
            lidx = jnp.asarray(lidx, jnp.int32)
            vals = jnp.take_along_axis(chunks, lidx, axis=1)
        else:
            vals, lidx = chunked_topk(chunks, k, interpret=impl == "interpret")
        offsets = (jnp.arange(chunks.shape[0], dtype=jnp.int32) * chunk)[:, None]
        gidx = (lidx + offsets).reshape(-1)
        # padded tail indices may point past n; clamp to a real slot and
        # zero their values so decompress scatters nothing
        valid = gidx < n
        values = jnp.where(valid, vals.reshape(-1), 0.0).astype(x.dtype)
        if self.narrow_indices:
            return LocalTopKPayload(
                values=values,
                indices=lidx.astype(jnp.uint16),
                shape=x.shape,
                dtype=x.dtype,
                chunk=chunk,
            )
        gidx = jnp.where(valid, gidx, 0)
        return TopKPayload(
            values=values, indices=gidx, shape=x.shape, dtype=x.dtype
        )

    @staticmethod
    def _global_indices(payload, n: int) -> jax.Array:
        """Flat int32 scatter targets for either payload form (padded-tail
        slots clamp to 0; their values are zero, so they add nothing)."""
        if isinstance(payload, LocalTopKPayload):
            lidx = payload.indices.astype(jnp.int32)
            offsets = (
                jnp.arange(lidx.shape[0], dtype=jnp.int32) * payload.chunk
            )[:, None]
            gidx = (lidx + offsets).reshape(-1)
            return jnp.where(gidx < n, gidx, 0)
        return payload.indices

    def _kernel_scatter(self, payload, acc, weight):
        """The Pallas chunk-scatter when its contract holds, else None.

        Contract: chunk-local payload (uint16 indices), f32 target. The
        generic ``.at[].add`` scatter costs ~69 ms per full-model payload
        at GPT-2-medium scale on a v5e; this kernel exploits the
        exactly-k-per-chunk structure (see :func:`chunk_scatter`).
        """
        impl = _resolve_impl(self.impl)
        if impl == "jnp" or not isinstance(payload, LocalTopKPayload):
            return None
        n = 1
        for d in payload.shape:
            n *= d
        rows = payload.indices.shape[0]
        chunk = payload.chunk
        # payload values are stored flat; indices carry the (rows, k) shape
        vals = jnp.asarray(payload.values, jnp.float32).reshape(rows, -1)
        # padded-tail entries already carry value 0 (compress zeroes them)
        if acc is not None:
            flat = jnp.asarray(acc.reshape(-1), jnp.float32)
            if rows * chunk != n:
                flat = jnp.pad(flat, (0, rows * chunk - n))
            dense = chunk_scatter(
                vals, payload.indices, chunk, flat.reshape(rows, chunk),
                weight=weight, interpret=impl == "interpret",
            )
        else:
            dense = chunk_scatter(
                vals, payload.indices, chunk,
                interpret=impl == "interpret",
            )
        out = dense.reshape(-1)[:n]
        shape = acc.shape if acc is not None else payload.shape
        dtype = acc.dtype if acc is not None else payload.dtype
        return out.astype(dtype).reshape(shape)

    def decompress(self, payload) -> jax.Array:
        out = self._kernel_scatter(payload, None, 1.0)
        if out is not None:
            return out
        n = 1
        for d in payload.shape:
            n *= d
        flat = jnp.zeros((n,), payload.dtype)
        flat = flat.at[self._global_indices(payload, n)].add(
            jnp.asarray(payload.values, payload.dtype)
        )
        return flat.reshape(payload.shape)

    def decompress_accumulate(self, payload, acc, weight):
        """Fused scatter-add receive (padded-tail slots carry zero values,
        so the duplicate index-0 entries add nothing — same semantics as
        :meth:`decompress` + axpy, without the dense temporary)."""
        if acc.dtype == jnp.float32:
            out = self._kernel_scatter(payload, acc, weight)
            if out is not None:
                return out
        flat = acc.reshape(-1)
        vals = weight * jnp.asarray(payload.values, flat.dtype)
        return flat.at[self._global_indices(payload, flat.size)].add(
            vals
        ).reshape(acc.shape)


# ---------------------------------------------------------------------------
# fused gossip wire: one-pass pack+quantize / dequantize+accumulate
# ---------------------------------------------------------------------------
#
# The bucketed CHOCO round's send side is, unfused, a chain of separate
# XLA programs per bucket: delta = x - xhat (materialized: XLA cannot fuse
# an elementwise producer INTO a Pallas custom call), the quantize kernel
# (read delta, write q), the dequantize kernel (read q, write dec_q), and
# xhat += dec_q — every stage a full HBM round-trip over the bucket. The
# fused ENCODE below is one kernel per bucket: read (x, xhat), write
# (q, scales, xhat') — the subtraction, absmax reduction, quantize, wire
# pack and CHOCO tracking update all happen on the VMEM-resident block.
# The receive side mirrors it: one DECODE kernel reads s plus every
# source's (q, scales) and writes s' = s + sum_j w_j dec(q_j), replacing
# the per-neighbor dequantize + axpy chain.
#
# The quantization math is the module-level `_fused_quant`/`_fused_dequant`
# pair, shared verbatim by the kernel bodies and the jnp impl, so
# "pallas", "interpret" and "jnp" produce bit-identical payloads — and
# identical to the UNFUSED codecs (`quantize_int8` / reference
# `chunk_for_quantization`), which is what lets the fused wire ship the
# exact same bytes as the two-step path (parity-pinned in
# tests/test_fused_wire.py).

_FUSED_LEVELS = {"int8": 127.0, "int4": 7.0, "fp8": FP8_E4M3_MAX}
_FUSED_WIRE_DTYPES = {
    "int8": jnp.int8,
    "int4": jnp.uint8,
    "fp8": jnp.float8_e4m3fn,
}
# elements per wire byte-lane: int4 packs two values per byte
_FUSED_WIRE_PACK = {"int8": 1, "int4": 2, "fp8": 1}


def _fused_quant(d: jax.Array, fmt: str):
    """``(R, chunk)`` f32 delta rows -> ``(wire_data, scales (R, 1),
    dec (R, chunk))`` — the ONE definition of the fused quantize math
    (identical to the per-codec reference formulas)."""
    half = d.shape[1] // 2
    absmax = jnp.max(jnp.abs(d), axis=1, keepdims=True)
    scale = absmax / _FUSED_LEVELS[fmt]
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    y = d * inv
    if fmt == "int8":
        q = jnp.clip(jnp.rint(y), -127, 127).astype(jnp.int8)
        return q, scale, q.astype(jnp.float32) * scale
    if fmt == "int4":
        qi = jnp.clip(jnp.rint(y), -7, 7).astype(jnp.int32)
        lo = qi[:, :half] & 0xF
        hi = (qi[:, half:] & 0xF) << 4
        return (lo | hi).astype(jnp.uint8), scale, qi.astype(jnp.float32) * scale
    q = y.astype(jnp.float8_e4m3fn)
    return q, scale, q.astype(jnp.float32) * scale


def _fused_dequant(data: jax.Array, scale: jax.Array, fmt: str) -> jax.Array:
    """``(R, wire_width)`` wire rows + ``(R, 1)`` scales -> ``(R, chunk)``
    f32 rows (the decode half of :func:`_fused_quant`)."""
    if fmt == "int4":
        b = data.astype(jnp.int32)
        sext = lambda nib: jnp.where(nib > 7, nib - 16, nib)
        q = jnp.concatenate([sext(b & 0xF), sext(b >> 4)], axis=1)
        return q.astype(jnp.float32) * scale
    return data.astype(jnp.float32) * scale


def _fused_encode_kernel(fmt, x_ref, h_ref, q_ref, s_ref, hat_ref):
    x = x_ref[:]
    h = h_ref[:]
    q, scale, dec = _fused_quant(x - h, fmt)
    q_ref[:] = q
    s_ref[:] = scale
    hat_ref[:] = h + dec


def _fused_decode_kernel(fmt, weights, s_ref, *rest):
    # recv accumulates weighted payloads FIRST, s joins last — the exact
    # float-addition order of the unfused receive (recv = w_self * dec,
    # then acc + w_j * dec per neighbor, then s + recv), so the fused
    # wire is bit-identical to the two-step path, not just close
    *payload_refs, out_ref = rest
    recv = weights[0] * _fused_dequant(
        payload_refs[0][:], payload_refs[1][:], fmt
    )
    for j, wgt in enumerate(weights[1:], start=1):
        data = payload_refs[2 * j][:]
        scale = payload_refs[2 * j + 1][:]
        recv = recv + wgt * _fused_dequant(data, scale, fmt)
    out_ref[:] = s_ref[:] + recv


def _fused_wire_width(fmt: str, chunk: int) -> int:
    return chunk // _FUSED_WIRE_PACK[fmt]


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def fused_pack_quantize(
    x: jax.Array, xhat: jax.Array, *, fmt: str, interpret: bool = False
):
    """Fused wire ENCODE: ``q = Q(x - xhat)`` plus the CHOCO tracking
    update ``xhat' = xhat + dec(q)`` in ONE kernel over ``(nchunks,
    chunk)`` f32 rows. Returns ``(data, scales (nchunks,), new_xhat)``.
    ``chunk`` must be a multiple of 128 (even suffices for the jnp impl
    via :class:`FusedBucketCodec`)."""
    nchunks, chunk = x.shape
    width = _fused_wire_width(fmt, chunk)
    rows = _round_up(max(nchunks, _SUBLANE_I8), _SUBLANE_I8)
    block_rows = _block_rows(rows, chunk, _SUBLANE_I8)
    rows = _round_up(rows, block_rows)
    if rows != nchunks:
        # zero rows quantize to zero with scale 0 and xhat' 0 — inert
        x = jnp.pad(x, ((0, rows - nchunks), (0, 0)))
        xhat = jnp.pad(xhat, ((0, rows - nchunks), (0, 0)))
    cspec = pl.BlockSpec(
        (block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    data, scales, hat = pl.pallas_call(
        functools.partial(_fused_encode_kernel, fmt),
        name="codec_fused_encode",
        grid=(rows // block_rows,),
        in_specs=[cspec, cspec],
        out_specs=[
            pl.BlockSpec(
                (block_rows, width), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            cspec,
        ],
        out_shape=[
            out_struct((rows, width), _FUSED_WIRE_DTYPES[fmt], x, xhat),
            out_struct((rows, 1), jnp.float32, x, xhat),
            out_struct((rows, chunk), jnp.float32, x, xhat),
        ],
        interpret=interpret_arg(interpret, x, xhat),
    )(x, xhat)
    return data[:nchunks], scales[:nchunks, 0], hat[:nchunks]


@functools.partial(jax.jit, static_argnames=("fmt", "weights", "interpret"))
def fused_dequantize_accumulate(
    s: jax.Array, *payload_rows, fmt: str, weights: tuple, interpret: bool = False
):
    """Fused wire DECODE: ``s' = s + sum_j weights[j] * dec(q_j)`` in ONE
    kernel. ``payload_rows`` interleaves ``data_j (nchunks, wire_width)``
    and ``scales_j (nchunks,)`` per source (self + one per neighbor);
    ``weights`` are the static mixing weights in the same order."""
    nchunks, chunk = s.shape
    width = _fused_wire_width(fmt, chunk)
    rows = _round_up(max(nchunks, _SUBLANE_I8), _SUBLANE_I8)
    block_rows = _block_rows(rows, chunk, _SUBLANE_I8)
    rows = _round_up(rows, block_rows)
    pad_r = rows - nchunks
    if pad_r:
        s = jnp.pad(s, ((0, pad_r), (0, 0)))
    cspec = pl.BlockSpec(
        (block_rows, chunk), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    wspec = pl.BlockSpec(
        (block_rows, width), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    sspec = pl.BlockSpec(
        (block_rows, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    operands = [s]
    in_specs = [cspec]
    if len(payload_rows) != 2 * len(weights):
        raise ValueError(
            f"expected (data, scales) per weight: {len(weights)} weights "
            f"but {len(payload_rows)} payload arrays"
        )
    for j in range(len(weights)):
        data = payload_rows[2 * j]
        scales = payload_rows[2 * j + 1].reshape(-1, 1)
        if pad_r:
            data = jnp.pad(data, ((0, pad_r), (0, 0)))
            scales = jnp.pad(scales, ((0, pad_r), (0, 0)))
        operands += [data, scales]
        in_specs += [wspec, sspec]
    out = pl.pallas_call(
        functools.partial(_fused_decode_kernel, fmt, weights),
        name="codec_fused_decode",
        grid=(rows // block_rows,),
        in_specs=in_specs,
        out_specs=cspec,
        out_shape=out_struct((rows, chunk), jnp.float32, *operands),
        interpret=interpret_arg(interpret, *operands),
    )(*operands)
    return out[:nchunks]


@dataclasses.dataclass(frozen=True)
class FusedBucketCodec:
    """One-pass pack+quantize wire for a chunk-decomposable quantizer.

    Built by :func:`fused_bucket_codec` from a codec advertising
    ``Compressor.fused_wire()``; consumed per-bucket by the consensus
    engine's :class:`~consensusml_tpu.consensus.bucketing.FusedWirePlan`.
    Operates on FLAT bucket buffers: ``(total,)`` per-worker, or stacked
    ``(W, total)`` — the buffer is reshaped to chunk rows either way, so
    the stacked worker axis just contributes more rows and no vmap
    batching rule is needed for the Pallas calls.

    ``impl`` follows the codec convention: "pallas" (compiled),
    "interpret" (Pallas interpreter — CPU tests and the jaxpr contract,
    which counts ``pallas_call`` equations), "jnp" (the same math as
    plain ops — XLA still fuses the chain, the right default off-TPU),
    or "auto" (pallas on TPU, jnp elsewhere). All bit-identical.
    """

    fmt: str  # "int8" | "int4" | "fp8"
    chunk: int
    impl: str = "auto"

    def __post_init__(self):
        if self.fmt not in _FUSED_LEVELS:
            raise ValueError(f"unknown fused wire format {self.fmt!r}")
        if self.fmt == "int4" and self.chunk % 2:
            raise ValueError("int4 fused wire needs an even chunk")

    @property
    def wire_width(self) -> int:
        return _fused_wire_width(self.fmt, self.chunk)

    def _payload(self, data, scales, total: int):
        cls = {"int8": Int8Payload, "int4": Int4Payload, "fp8": Fp8Payload}[
            self.fmt
        ]
        return cls(
            data=data,
            scales=scales,
            shape=(total,),
            dtype=jnp.dtype(jnp.float32),
            chunk=self.chunk,
        )

    def encode(self, x: jax.Array, xhat: jax.Array):
        """``(payload, new_xhat)`` for one bucket buffer: the codec's
        exact payload for ``x - xhat`` plus the tracking update
        ``xhat + dec(payload)``, one fused pass."""
        lead = x.shape[:-1]
        total = x.shape[-1]
        x2 = x.reshape(-1, self.chunk)
        h2 = xhat.reshape(-1, self.chunk)
        impl = _resolve_impl(self.impl)
        if impl == "jnp":
            data, scale, dec = _fused_quant(x2 - h2, self.fmt)
            scales, hat = scale[:, 0], h2 + dec
        else:
            data, scales, hat = fused_pack_quantize(
                x2, h2, fmt=self.fmt, interpret=impl == "interpret"
            )
        payload = self._payload(
            data.reshape(lead + (-1,)), scales.reshape(lead + (-1,)), total
        )
        return payload, hat.reshape(x.shape)

    def decode(self, payload) -> jax.Array:
        """Dense f32 decode (plain ops — elementwise, XLA fuses it into
        the consumer; used by the psum/dense receive and the simulated
        backend's mixing-matrix multiply)."""
        data = payload.data
        lead = data.shape[:-1]
        dec = _fused_dequant(
            data.reshape(-1, self.wire_width),
            payload.scales.reshape(-1, 1),
            self.fmt,
        )
        return dec.reshape(lead + (-1,))

    def decode_accumulate(self, s: jax.Array, payloads, weights) -> jax.Array:
        """``s + sum_j weights[j] * dec(payloads[j])`` in one fused pass
        — the receive half of the wire (self payload first, then one per
        neighbor, matching the unfused accumulate order bit-for-bit)."""
        weights = tuple(float(w) for w in weights)
        if len(payloads) != len(weights):
            raise ValueError(
                f"{len(payloads)} payloads vs {len(weights)} weights"
            )
        lead = s.shape[:-1]
        s2 = s.reshape(-1, self.chunk)
        impl = _resolve_impl(self.impl)
        if impl == "jnp":
            # same term order as the kernel (and the unfused receive):
            # weighted payload sum first, s last
            dec = lambda p: _fused_dequant(
                p.data.reshape(-1, self.wire_width),
                p.scales.reshape(-1, 1),
                self.fmt,
            )
            recv = weights[0] * dec(payloads[0])
            for wgt, p in zip(weights[1:], payloads[1:]):
                recv = recv + wgt * dec(p)
            return (s2 + recv).reshape(s.shape)
        flat = []
        for p in payloads:
            flat += [
                p.data.reshape(-1, self.wire_width),
                p.scales.reshape(-1),
            ]
        out = fused_dequantize_accumulate(
            s2, *flat, fmt=self.fmt, weights=weights,
            interpret=impl == "interpret",
        )
        return out.reshape(s.shape)


def describe_codec(comp) -> str:
    """One line naming the implementation every stage of ``comp`` resolves
    to on this backend — train.py prints it, so the log says what ran
    (``impl="auto"`` is the kernels on a TPU and jnp math elsewhere)."""
    parts = []
    stages = (
        (comp.inner, comp.outer)
        if isinstance(comp, ComposedCompressor)
        else (comp,)
    )
    for c in stages:
        name = type(c).__name__
        if isinstance(c, ChunkedTopKCompressor):
            parts.append(
                f"{name} {c.k_per_chunk}/{c.chunk} "
                f"select={c.select_impl()} scatter={_resolve_impl(c.impl)}"
            )
        elif hasattr(c, "impl"):
            parts.append(f"{name}/{c.chunk} {_resolve_impl(c.impl)}")
        else:
            parts.append(f"{name} jnp")
    return " + ".join(parts)


def fused_bucket_codec(comp) -> FusedBucketCodec | None:
    """The fused one-pass wire for ``comp``, or ``None`` when the codec
    cannot ride it (no ``fused_wire()`` tag — composed/sparse codecs —
    stochastic codecs, or a chunk geometry the kernel tiling rejects).
    ``None`` means the engine keeps the two-step bucketed path; it is
    never an error."""
    fmt = comp.fused_wire()
    if fmt is None or comp.stochastic:
        return None
    align = comp.bucket_alignment()
    if align is None or align < 2 or (fmt == "int4" and align % 2):
        return None
    impl = getattr(comp, "impl", "jnp")
    if _resolve_impl(impl) != "jnp" and align % _LANE:
        # a non-lane-multiple chunk cannot tile the kernel path; the jnp
        # impl has no such constraint
        return None
    return FusedBucketCodec(fmt=fmt, chunk=align, impl=impl)
