"""Row tiles of a causal depthwise short convolution, for fused Pallas kernels.

Both recurrent mixers convolve their input projection's float32 output over
the ``kw`` rows up to each token before their scans
(:func:`~consensusml_tpu.models.gated_delta.gated_delta_conv`, kernels
``gdn_conv_fwd`` / ``gdn_conv_bwd``; :func:`~consensusml_tpu.models.ssm.ssd_conv`,
kernels ``ssd_conv_fwd`` / ``ssd_conv_bwd``). Their kernels share what this
module holds: a grid step is a tile of :data:`ROWS` rows of some columns of that
output, read straight out of it through one BlockSpec, and the :data:`HALO`
rows before the tile (and, backward, after it) through a second BlockSpec on
the same array, since the taps reach ``kw - 1`` rows back. A shift along the
rows is a ``pltpu.roll`` of the halo and the tile stacked (Mosaic slices values
only along whole (8, 128) tiles). The taps are float32 and summed in the
mixers' order. What follows the taps (a bias, SiLU, a normalisation) is each
mixer's own.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 512  # rows of a grid step; 1,024 outgrows VMEM on the chip (PERF.md section 6, PR 36)
HALO = 8  # rows of a float32 tile: the taps reach at most this far past a tile


def tile_rows(rows: int) -> int:
    return min(ROWS, rows)


def tiles(rows: int, kw: int) -> bool:
    """Whether a row of ``rows`` tokens cuts into whole row tiles that the
    halos of a ``kw``-tap convolution reach across."""
    return rows % tile_rows(rows) == 0 and tile_rows(rows) % 16 == 0 and kw - 1 <= HALO


def row_block(kind: str, rows: int, tile: int, size: int = HALO):
    """Row tile ``i`` -> the index, along the rows, of its block of ``size``
    rows: the tile itself (``"tile"``, blocks of ``tile`` rows), the block just
    before it (``"before"``; the first tile reads the first block, which the
    kernels zero) or just after it (``"after"``; the last tile reads its own
    last block, which the kernels zero)."""
    if kind == "tile":
        return lambda i: i
    if kind == "before":
        return lambda i: jnp.maximum(i * (tile // size) - 1, 0)
    last = rows // size - 1
    return lambda i: jnp.minimum((i + 1) * (tile // size), last)


def piece_specs(pieces, rows: int, tile: int, edges: bool):
    """BlockSpecs, on a (b, rows, W) array and a grid (h, b, row tile), of the
    column pieces ``(start, stride, width)``: the columns ``h * stride +
    start`` on, ``width`` of them. The tile's rows, then the :data:`HALO` rows
    before it, then (``edges``) those after it, each kind for every piece."""
    where = {kind: row_block(kind, rows, tile) for kind in ("tile", "before", "after")}
    return [
        pl.BlockSpec(
            (None, tile if kind == "tile" else HALO, w),
            lambda h, b, i, o=o, s=s, w=w, at=where[kind]: (b, at(i), (h * s + o) // w))
        for kind in ("tile", "before", "after")[: 3 if edges else 2] for o, s, w in pieces]


def weight_specs(height: int, pieces):
    """BlockSpecs of a (height, W) array of per-column weights, on the same
    grid: ``(width, at)`` a piece, ``at(h)`` its block of ``width`` columns."""
    return [pl.BlockSpec((height, w), lambda h, b, i, at=at: (0, at(h))) for w, at in pieces]


def shifted(x, before, kw):
    """``x`` (tile, w) moved down by ``kw - 1 - j`` rows for each tap ``j``, the
    rows ``before`` (:data:`HALO`, w) it filling the top."""
    stacked = jnp.concatenate([before, x], axis=0)
    return [x if j == kw - 1 else pltpu.roll(stacked, kw - 1 - j, 0)[HALO:] for j in range(kw)]


def taps(w, shifted):
    """The causal depthwise convolution, float32, in the mixers' order of the
    taps: row ``j`` of ``w`` weighs tap ``j`` (rows past the taps are not read)."""
    return sum(s * w[j : j + 1, :] for j, s in enumerate(shifted))


def forward(x_ref, before_ref, w_ref, first, kw, lanes=slice(None)):
    """The taps' float32 sum over a tile (``lanes`` of its columns), the rows
    before a row's first token zero."""
    before = jnp.where(first, 0.0, before_ref[:, lanes])
    return taps(w_ref[:, lanes], shifted(x_ref[:, lanes], before, kw))


def backward(w, x, before_ref, after_ref, g, g_after, first, last, kw, grad, lanes=slice(None)):
    """A tile's share of the convolution's backward pass: ``w`` the weights'
    and ``x`` the tile's columns, ``g`` and ``g_after`` the cotangents of what
    followed the taps on the tile and on the rows after it, ``grad(a, g)`` the
    chain rule back through that (``a`` the taps' sum, ``g`` float32). Makes
    the taps again, for the tile and for the first rows of the next tile,
    which read this tile's last through them, and returns the input's
    cotangent (the anti-causal taps), the taps' sum's, and the shifted input
    that :func:`tap_sums` takes."""
    rows, f32 = x.shape[0], jnp.float32
    seen = shifted(x, jnp.where(first, 0.0, before_ref[:, lanes]), kw)
    da = grad(taps(w, seen), g.astype(f32))
    after = grad(taps(w, shifted(after_ref[:, lanes], x[rows - HALO :], kw)), g_after.astype(f32)[:HALO])
    stacked = jnp.concatenate([da, jnp.where(last, 0.0, after)], axis=0)
    back = [da if j == kw - 1 else pltpu.roll(stacked, rows + HALO - (kw - 1 - j), 0)[:rows] for j in range(kw)]
    return taps(w, back), da, seen


def tap_sums(da, seen, tap):
    """The weights' cotangent over a tile: row ``j`` (of ``tap``, a column
    iota) is tap ``j``'s input times the taps' sum's cotangent, summed over rows."""
    return sum(jnp.where(tap == j, jnp.sum(da * s, axis=0, keepdims=True), 0.0) for j, s in enumerate(seen))
