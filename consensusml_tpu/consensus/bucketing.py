"""Gossip bucketing: pack many tree leaves into few flat wire buffers.

A GPT-2-medium tree has 292 leaves; a per-leaf compressed gossip round
therefore dispatches hundreds of compress/``ppermute``/decompress ops per
consensus round — classic per-tensor launch overhead, the problem
DDP-style gradient bucketing was invented to kill. This module computes a
STATIC :class:`BucketPlan` from the gossiped leaves' shapes/dtypes: leaves
are grouped into dtype-homogeneous flat buffers ("buckets"), each capped
at roughly ``bucket_bytes`` of estimated WIRE footprint, and a gossip
round then runs O(#buckets) fused compress -> ppermute -> decompress
stages instead of O(#leaves).

Two properties make the packing semantics-preserving rather than a codec
switch (a plain back-to-back concatenation would let chunks span leaf
boundaries and pick other elements):

- **Per-leaf alignment.** Every leaf starts at a multiple of ``align``
  (the codec's chunk size, via ``Compressor.bucket_alignment()``) and is
  zero-padded up to it, so a chunked codec's chunk boundaries inside a
  bucket coincide exactly with the boundaries the per-leaf path produces.
  Chunk-local top-k selects among the same elements and per-chunk scales
  see the same absmax, so the DECODED round output matches the per-leaf
  path (bit-exactly for pure chunked codecs; composed codecs regroup
  their value-vector quantization, a quantization-noise-level change).
- **Zero padding is inert.** Padding slots hold zeros on every pack;
  chunked top-k never ships a nonzero value for them and symmetric
  quantizers decode 0 -> 0, so CHOCO's xhat/s tracking stays zero on
  padding and :meth:`BucketPlan.unpack` drops the slots losslessly.
  (Codecs whose decode of a zero is nonzero — e.g. sign codecs — must
  report ``bucket_alignment() = None`` and keep the per-leaf path.)

The cap is on estimated WIRE bytes (for exact gossip that is the dense
bytes; for compressed gossip the codec payload) because the bucket is the
unit in flight on the ICI: while bucket ``i`` rides the link, bucket
``i+1`` is being compressed, and the cap bounds that pipeline stage. A
leaf is never split, so a single leaf larger than the cap simply becomes
its own bucket and #buckets <= #leaves always holds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from consensusml_tpu.obs import get_registry
from consensusml_tpu.obs import span as _span

__all__ = [
    "BucketLeaf",
    "Bucket",
    "BucketPlan",
    "FusedWirePlan",
    "build_plan",
    "build_fused_plan",
]

# trace-time accounting for the fused wire (same convention as the
# traced-ppermute counter in comm/collectives.py: gossip programs compile
# once and replay, so the per-COMPILE kernel count IS the per-round count;
# zero steady-state cost). One encode and one decode kernel per bucket per
# innovation exchange is the fused wire's contract — the jaxpr pass
# (analysis/jaxpr_contracts.check_fused_wire) asserts it on the traced
# program; these counters surface it to the metrics plane
# (consensusml_wire_fused_* in docs/observability.md).
_TRACED_FUSED_ENCODES = get_registry().counter(
    "consensusml_wire_fused_encodes_traced_total",
    "fused pack+quantize kernels traced into gossip programs "
    "(one per bucket per innovation exchange, per XLA compile)",
)
_TRACED_FUSED_DECODES = get_registry().counter(
    "consensusml_wire_fused_decodes_traced_total",
    "fused dequantize+accumulate kernels traced into gossip programs "
    "(one per bucket per innovation exchange, per XLA compile)",
)


def _round_up(n: int, align: int) -> int:
    return -(-n // align) * align


@dataclasses.dataclass(frozen=True)
class BucketLeaf:
    """One leaf's slot inside a bucket (all positions are PER-WORKER:
    stacked backends carry the worker axis outside this accounting)."""

    index: int  # position in the caller's flat leaf list
    shape: tuple[int, ...]  # per-worker shape
    size: int  # per-worker element count
    padded: int  # size rounded up to the plan's alignment
    offset: int  # start offset inside the bucket's flat buffer


@dataclasses.dataclass(frozen=True)
class Bucket:
    dtype: Any  # the packed buffer's dtype (homogeneous per bucket)
    leaves: tuple[BucketLeaf, ...]
    total: int  # flat buffer length = sum of padded leaf sizes


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static packing layout; built once at trace time from leaf shapes.

    Both execution backends build the plan from the same PER-WORKER
    shapes in tree-flatten order, so they pack identically and stay
    cross-validated.
    """

    buckets: tuple[Bucket, ...]
    align: int
    n_leaves: int

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def total_elems(self) -> int:
        """Padded per-worker element count across all buckets."""
        return sum(b.total for b in self.buckets)

    def pack(self, leaves: list, stacked: bool = False) -> list[jax.Array]:
        """Concatenate ``leaves`` (tree-flatten order) into bucket buffers.

        ``stacked=True``: leaves carry a leading worker axis ``(W, ...)``
        and buckets come out ``(W, total)``.
        """
        if len(leaves) != self.n_leaves:
            raise ValueError(
                f"plan covers {self.n_leaves} leaves, got {len(leaves)}"
            )
        axis = 1 if stacked else 0
        out = []
        for bucket in self.buckets:
            parts = []
            for bl in bucket.leaves:
                x = leaves[bl.index]
                flat = x.reshape(x.shape[0], -1) if stacked else x.reshape(-1)
                if bl.padded != bl.size:
                    width = (0, bl.padded - bl.size)
                    pad = ((0, 0), width) if stacked else (width,)
                    flat = jnp.pad(flat, pad)
                parts.append(flat)
            out.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis))
        return out

    def unpack(self, bufs: list[jax.Array], stacked: bool = False) -> list:
        """Invert :meth:`pack`: bucket buffers -> leaves in original order
        (padding slots dropped). Dtype is the buffer's — callers that
        packed a cast view cast back themselves."""
        if len(bufs) != len(self.buckets):
            raise ValueError(
                f"plan has {len(self.buckets)} buckets, got {len(bufs)}"
            )
        leaves: list = [None] * self.n_leaves
        for bucket, buf in zip(self.buckets, bufs):
            for bl in bucket.leaves:
                piece = (
                    buf[:, bl.offset : bl.offset + bl.size]
                    if stacked
                    else buf[bl.offset : bl.offset + bl.size]
                )
                shape = (buf.shape[0],) + bl.shape if stacked else bl.shape
                leaves[bl.index] = piece.reshape(shape)
        return leaves


def build_plan(
    leaves: list[tuple[tuple[int, ...], Any]],
    *,
    bucket_bytes: int,
    align: int = 1,
    wire_bytes: Callable[[int, Any], float] | None = None,
) -> BucketPlan:
    """Greedy dtype-grouped packing of ``(per_worker_shape, dtype)`` pairs.

    ``wire_bytes(padded_elems, dtype)`` estimates a leaf's on-the-wire
    footprint (defaults to dense bytes); a bucket closes when adding the
    next leaf would push its estimate past ``bucket_bytes``. One bucket
    stays open PER DTYPE so interleaved dtypes (bf16 params between f32
    stats) coalesce instead of fragmenting; buckets are emitted in order
    of their first leaf, and leaves keep tree-flatten order within a
    dtype, so the layout is deterministic across processes and backends.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    if align < 1:
        raise ValueError(f"align must be >= 1, got {align}")
    if wire_bytes is None:
        wire_bytes = lambda n, dtype: n * jnp.dtype(dtype).itemsize

    open_buckets: dict = {}  # dtype -> (leaves list, total, est_bytes)
    done: list[Bucket] = []

    def close(dtype) -> None:
        leaves_, total, _ = open_buckets.pop(dtype)
        done.append(Bucket(dtype=dtype, leaves=tuple(leaves_), total=total))

    for index, (shape, dtype) in enumerate(leaves):
        dtype = jnp.dtype(dtype)
        size = 1
        for d in shape:
            size *= d
        padded = _round_up(max(size, 1), align)
        est = wire_bytes(padded, dtype)
        cur = open_buckets.get(dtype)
        if cur is not None and cur[2] + est > bucket_bytes:
            close(dtype)
            cur = None
        if cur is None:
            cur = ([], 0, 0.0)
        bl = BucketLeaf(
            index=index, shape=tuple(shape), size=size, padded=padded, offset=cur[1]
        )
        open_buckets[dtype] = (cur[0] + [bl], cur[1] + padded, cur[2] + est)
    for dtype in list(open_buckets):
        close(dtype)
    done.sort(key=lambda b: b.leaves[0].index)
    return BucketPlan(buckets=tuple(done), align=align, n_leaves=len(leaves))


@dataclasses.dataclass(frozen=True)
class FusedWirePlan:
    """The fused one-pass wire: a :class:`BucketPlan` married to the
    codec's :class:`~consensusml_tpu.compress.kernels.FusedBucketCodec`.

    Consumed by the consensus engine when ``GossipConfig.fused_wire``
    engages (bucketed transport + a codec advertising fused kernels):
    instead of pack -> compress -> decompress -> accumulate as separate
    XLA programs that each round-trip HBM over every bucket, a gossip
    round runs exactly ONE encode kernel per bucket on the send side
    (subtract + absmax + quantize + wire-pack + CHOCO xhat update, all on
    the VMEM-resident block) and ONE decode kernel per bucket on the
    receive side (dequantize every source + weighted accumulate into s).
    Payload bytes and layout are bit-identical to the two-step path —
    this is a transport fusion, not a codec change.

    All buffer arguments are lists parallel to ``plan.buckets``; each
    buffer is flat ``(total,)`` per-worker or stacked ``(W, total)`` —
    the codec reshapes to chunk rows either way (no vmap needed).
    """

    plan: BucketPlan
    codec: Any  # compress.kernels.FusedBucketCodec

    @property
    def num_buckets(self) -> int:
        return self.plan.num_buckets

    def _check(self, bufs: list, what: str) -> None:
        if len(bufs) != self.plan.num_buckets:
            raise ValueError(
                f"fused wire {what}: plan has {self.plan.num_buckets} "
                f"buckets, got {len(bufs)} buffers"
            )

    def encode(self, bufs: list, xhat_bufs: list):
        """Per bucket: ``(payload, xhat')`` — the codec payload of
        ``buf - xhat`` plus the tracking update, one kernel each.
        Returns ``(payloads, new_xhat_bufs)``."""
        self._check(bufs, "encode")
        payloads, new_hat = [], []
        with _span("wire.fused_encode", buckets=len(bufs)):
            for buf, hat in zip(bufs, xhat_bufs):
                _TRACED_FUSED_ENCODES.inc()
                q, h2 = self.codec.encode(buf, hat)
                payloads.append(q)
                new_hat.append(h2)
        return payloads, new_hat

    def decode(self, payloads: list) -> list:
        """Dense f32 decode per bucket (plain elementwise ops — for the
        psum receive and the simulated backend's mixing-matrix path)."""
        self._check(payloads, "decode")
        return [self.codec.decode(q) for q in payloads]

    def decode_accumulate(
        self, s_bufs: list, sources: list, weights
    ) -> list:
        """Per bucket: ``s + sum_j weights[j] * dec(sources[b][j])`` in
        one kernel. ``sources[b]`` lists bucket ``b``'s payloads in
        weight order (self first, then one per neighbor shift)."""
        self._check(s_bufs, "decode_accumulate")
        out = []
        with _span("wire.fused_decode", buckets=len(s_bufs)):
            for s, plist in zip(s_bufs, sources):
                _TRACED_FUSED_DECODES.inc()
                out.append(self.codec.decode_accumulate(s, plist, weights))
        return out


def build_fused_plan(plan: BucketPlan, compressor) -> FusedWirePlan | None:
    """``FusedWirePlan`` for ``plan`` under ``compressor``, or ``None``
    when the codec has no fused kernels (composed/sparse/stochastic
    codecs) — the engine then keeps the two-step bucketed path."""
    from consensusml_tpu.compress.kernels import fused_bucket_codec

    codec = fused_bucket_codec(compressor)
    if codec is None:
        return None
    if plan.align != codec.chunk:
        raise ValueError(
            f"bucket plan alignment {plan.align} != fused codec chunk "
            f"{codec.chunk}: the plan must be built from this codec's "
            "bucket_alignment()"
        )
    return FusedWirePlan(plan=plan, codec=codec)
