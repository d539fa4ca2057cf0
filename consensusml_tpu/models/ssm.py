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
take operands in the compute dtype and accumulate in float32.

On a TPU, at shapes that tile, the same four steps run as a pair of Pallas
kernels under a ``jax.custom_vjp`` (:func:`ssd_scan`; device events
``ssd_fwd`` / ``ssd_bwd``): a grid step is one chunk of one group of heads,
the chunk axis sequential, the group's float32 state carried across chunks
in VMEM, so the decay matrices, ``C B^T``, the chunk states and what a chunk
inherits never reach HBM. The arithmetic is :func:`ssd_chunked`'s to the
dtype. Which of the two runs is observed from the platform and the shapes
(:func:`_scan_impl`), never chosen: off a TPU :func:`ssd_chunked` runs as it
always did.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from consensusml_tpu.models import short_conv
from consensusml_tpu.obs import get_registry
from consensusml_tpu.obs import span as _span
from consensusml_tpu.pallas_util import call_once, interpret_arg, on_tpu, out_struct

__all__ = ["Mamba2Config", "Mamba2Mixer", "ssd_chunked", "ssd_scan", "ssd_conv", "carried_states"]


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


# -- the same scan as a fused kernel pair --------------------------------------
#
# A grid step is one chunk of ONE GROUP of heads (the group's B and C are
# shared, so ``C B^T``, ``C . state`` and the chunk states are one MXU call for
# all of its heads); the chunk axis is the grid's last and sequential, and the
# group's state, kept TRANSPOSED as (state, heads x head width) float32, stays
# in a VMEM scratch from chunk to chunk. The lane axis holds the group's heads
# side by side: a head narrower than the 128 lanes shares a lane tile with its
# neighbours, and the per-head matrices (the decay tile, ``L o C B^T``) meet a
# tile whose other heads' lanes are zeroed, which fills the MXU's pass as a
# 128-wide head would (PERF.md section 6, PR 26: a 64-wide operand by itself
# costs the same pass). The cumulative log-decays are a 2 MB float32 array that
# XLA makes beforehand (:func:`ssd_scan`) and hands in twice, time along the
# sublanes (``cumc``) and along the lanes (``cumr``): the kernels never
# transpose a vector, and the backward kernel hands back a cotangent for each
# that autodiff adds up and takes through the sum to ``dt`` and ``A``. The
# ``D x`` skip rides along: a multiply-add on a tile the kernels hold.

_LANE = 128
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_TRACED: dict = {}  # pallas_util.call_once keeps each kernel's one trace here


def _lane_tile(p: int) -> int:
    return _LANE if p <= _LANE else p


def _scan_impl(chunk: int, p: int, n: int, r: int) -> str:
    """Observed, never chosen: ``"pallas"`` on a TPU when the shapes tile
    (chunk and state multiples of the 128 lanes; a group's ``r`` heads of
    width ``p`` filling whole lane tiles), ``"xla"`` (:func:`ssd_chunked`)
    elsewhere. The tests make it ``"interpret"``."""
    heads_tile = (_LANE % p == 0 and (r * p) % _LANE == 0) if p <= _LANE else p % _LANE == 0
    tiles = chunk % _LANE == 0 and n % _LANE == 0 and heads_tile
    return "pallas" if on_tpu() and tiles else "xla"


def _carried(state):
    """What a chunk reads of the state carried to it (backward: of that
    state's cotangent). The identity; the planted-fault tests make it zeros,
    which is ``carried_states`` zeroed on :func:`ssd_chunked`'s path."""
    return state


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


class _Lanes:
    """The lane tiles of a group's (rows, r * p) arrays: which lanes are which
    head's, and per-head columns spread over their head's lanes."""

    def __init__(self, q: int, r: int, p: int):
        self.p, self.tile = p, _lane_tile(p)
        self.heads = self.tile // p  # heads a lane tile
        self.tiles = r * p // self.tile
        self.lane = {rows: jax.lax.broadcasted_iota(jnp.int32, (rows, self.tile), 1) for rows in (1, q)}

    def lanes(self, t: int) -> slice:
        return slice(t * self.tile, (t + 1) * self.tile)

    def spread(self, per_head, t: int):
        """(rows, r) per-head columns -> (rows, tile): head ``k`` of tile
        ``t``'s lanes all read its column."""
        rows, first = per_head.shape[0], t * self.heads
        out = per_head[:, first : first + 1]
        for k in range(1, self.heads):
            out = jnp.where(self.lane[rows] >= k * self.p, per_head[:, first + k : first + k + 1], out)
        return out

    def only(self, x, k: int):
        """``x`` (rows, tile) float32 with the lanes of the tile's other heads zeroed."""
        if self.heads == 1:
            return x
        lane = self.lane[x.shape[0]]
        return jnp.where((lane >= k * self.p) & (lane < (k + 1) * self.p), x, 0.0)


def _chunk_terms(q, cumc):
    """From a chunk's cumulative log-decays (q, r): ``exp(cum)``, ``exp(cum_last
    - cum)`` and ``exp(cum_last)`` (1, r), and the lower-triangular mask."""
    last = cumc[q - 1 : q, :]
    lower = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.exp(cumc), jnp.exp(last - cumc), jnp.exp(last), lower


def _decay(lower, cumc, cumr_ref, h):
    """Head ``h``'s decay tile ``exp(cum_i - cum_j)``, ``i >= j``, else 0; (q, q) float32."""
    return jnp.exp(jnp.where(lower, cumc[:, h : h + 1] - cumr_ref[h : h + 1, :], -jnp.inf))


def _ssd_fwd_kernel(p, save, x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, skip_ref, y_ref, *rest):
    state = rest[-1]  # (n, r * p) float32: the group's state, transposed
    q, r = dt_ref.shape
    dtype, f32 = x_ref.dtype, jnp.float32
    at = _Lanes(q, r, p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    entering = _carried(state[...])
    if save:
        rest[0][...] = entering
    bm, cm, cumc, dts, skip = b_ref[...], c_ref[...], cumc_ref[...], dt_ref[...], skip_ref[...]
    grow, to_end, across, lower = _chunk_terms(q, cumc)
    cb = _dot(cm, bm, _NT)  # (q, q)
    inherited = _dot(cm, entering.astype(dtype), _NN)  # (q, r * p)
    for t in range(at.tiles):
        sl = at.lanes(t)
        x32 = x_ref[:, sl].astype(f32)
        xdt32 = x32 * at.spread(dts, t)
        xdt = xdt32.astype(dtype)
        y = inherited[:, sl] * at.spread(grow, t)
        for k in range(at.heads):
            mixed = (_decay(lower, cumc, cumr_ref, t * at.heads + k) * cb).astype(dtype)
            y = y + _dot(mixed, at.only(xdt32, k).astype(dtype), _NN)
        y_ref[:, sl] = y + x32 * at.spread(skip, t)
        weighted = (xdt.astype(f32) * at.spread(to_end, t)).astype(dtype)
        state[:, sl] = state[:, sl] * at.spread(across, t) + _dot(bm, weighted, _TN)


def _ssd_bwd_kernel(
    p, x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, skip_ref, s_ref, dy_ref,
    dx_ref, ddt_ref, dcumc_ref, dcumr_ref, db_ref, dc_ref, dskip_ref, dstate,
):
    """One chunk of one group, the chunks in REVERSE: ``dstate`` carries the
    cotangent of the state that leaves the chunk."""
    q, r = dt_ref.shape
    dtype, f32 = x_ref.dtype, jnp.float32
    at = _Lanes(q, r, p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    entering = s_ref[...]  # float32, as the forward kernel read it
    leaving = _carried(dstate[...])
    s_lo, l_lo = entering.astype(dtype), leaving.astype(dtype)
    bm, cm, cumc, dts, skip = b_ref[...], c_ref[...], cumc_ref[...], dt_ref[...], skip_ref[...]
    grow, to_end, across, lower = _chunk_terms(q, cumc)
    cb = _dot(cm, bm, _NT)
    inherited = _dot(cm, s_lo, _NN)  # (q, r * p), before its decay
    dweighted = _dot(bm, l_lo, _NN)  # (q, r * p): the chunk state's cotangent, at each row
    head = jax.lax.broadcasted_iota(jnp.int32, (q, r), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcb = jnp.zeros((q, q), f32)
    db = jnp.zeros(b_ref.shape, f32)
    dc = jnp.zeros(c_ref.shape, f32)
    ddt = jnp.zeros((q, r), f32)
    dcum = jnp.zeros((q, r), f32)
    for t in range(at.tiles):
        sl = at.lanes(t)
        x32, dy = x_ref[:, sl].astype(f32), dy_ref[:, sl]
        dt_t, grow_t, to_end_t, across_t = (at.spread(v, t) for v in (dts, grow, to_end, across))
        xdt32 = x32 * dt_t
        xdt = xdt32.astype(dtype)
        weighted32 = xdt.astype(f32) * to_end_t
        dz = dy * grow_t  # cotangent of C . state
        dz_lo = dz.astype(dtype)
        dw_t = dweighted[:, sl]
        dxdt = dw_t * to_end_t
        # through exp(cum) and exp(cum_last - cum), lane by lane; and what
        # lands on cum_last: exp(cum_last - cum) again, and the state's decay
        through = dz * inherited[:, sl] - dw_t * weighted32
        at_last = jnp.sum(dw_t * weighted32, axis=0, keepdims=True) + across_t * jnp.sum(
            leaving[:, sl] * entering[:, sl], axis=0, keepdims=True
        )
        for k in range(at.heads):
            h = t * at.heads + k
            decay = _decay(lower, cumc, cumr_ref, h)
            mixed32 = decay * cb
            dy_k = at.only(dy, k).astype(dtype)
            dmixed = _dot(dy_k, xdt, _NT)  # (q, q)
            dxdt = dxdt + _dot(mixed32.astype(dtype), dy_k, _TN)  # head k's lanes alone
            dcb = dcb + dmixed * decay
            moved = dmixed * mixed32  # d(cum_i - cum_j)
            dcumr_ref[h : h + 1, :] = -jnp.sum(moved, axis=0, keepdims=True)
            col = jnp.sum(moved, axis=1, keepdims=True) + jnp.sum(at.only(through, k), axis=1, keepdims=True)
            col = col + jnp.where(is_last, jnp.sum(at.only(at_last, k), axis=1, keepdims=True), 0.0)
            dcum = jnp.where(head == h, col, dcum)
        dx_ref[:, sl] = (dxdt * dt_t + dy * at.spread(skip, t)).astype(dx_ref.dtype)
        dskip_ref[:, sl] = jnp.sum(dy * x32, axis=0, keepdims=True)  # this chunk's share, lane by lane
        through_dt = dxdt * x32
        for k in range(at.heads):
            col = jnp.sum(at.only(through_dt, k), axis=1, keepdims=True)
            ddt = jnp.where(head == t * at.heads + k, col, ddt)
        dc = dc + _dot(dz_lo, s_lo[:, sl], _NT)
        db = db + _dot(weighted32.astype(dtype), l_lo[:, sl], _NT)
        dstate[:, sl] = dstate[:, sl] * across_t + _dot(cm, dz_lo, _TN)
    dcb_lo = dcb.astype(dtype)
    dc_ref[...] = (dc + _dot(dcb_lo, bm, _NN)).astype(dc_ref.dtype)
    db_ref[...] = (db + _dot(dcb_lo, cm, _TN)).astype(db_ref.dtype)
    ddt_ref[...] = ddt
    dcumc_ref[...] = dcum


def _ssd_call(name, kernel, statics, kinds, operands, outs, chunk, carried, reverse, interpret):
    """One of the pair. ``kinds`` says of each operand and then of each output
    which of four arrays it is: ``w`` (b, T, g * width), cut into (chunk,
    width) blocks; ``c`` (b, g, T, r) per-head columns; ``r`` (b, g, r, T) rows;
    ``k`` (g, 1, r) the skip's ``D``; ``s`` (b, g, chunks, rows, r * p) a block
    a chunk: the states, the skip's cotangent. ``outs``: (shape, dtype) each;
    ``carried``: the shape of the float32 scratch that lives across chunks."""
    bsz, g, total, r = operands[1].shape  # dt, as columns
    nc = total // chunk
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)

    def spec(kind, shape):
        if kind == "w":
            return pl.BlockSpec((None, chunk, shape[2] // g), lambda b, i, c: (b, at(c), i))
        if kind == "c":
            return pl.BlockSpec((None, None, chunk, r), lambda b, i, c: (b, i, at(c), 0))
        if kind == "r":
            return pl.BlockSpec((None, None, r, chunk), lambda b, i, c: (b, i, 0, at(c)))
        if kind == "k":
            return pl.BlockSpec((None, 1, r), lambda b, i, c: (i, 0, 0))
        return pl.BlockSpec((None, None, None) + shape[3:], lambda b, i, c: (b, i, at(c), 0, 0))

    shapes = [x.shape for x in operands] + [shape for shape, _ in outs]
    specs = [spec(kind, shape) for kind, shape in zip(kinds, shapes, strict=True)]
    call = pl.pallas_call(
        functools.partial(kernel, *statics),
        grid=(bsz, g, nc),
        in_specs=specs[: len(operands)],
        out_specs=specs[len(operands) :],
        out_shape=[out_struct(shape, dtype, *operands) for shape, dtype in outs],
        scratch_shapes=[pltpu.VMEM(carried, jnp.float32)],
        interpret=interpret_arg(interpret, *operands),
    )
    # the device op takes the innermost scope's name; ``pallas_call(name=)``
    # would come out wrapped in the transforms' names (``vmap_jvp_ssd_fwd__``)
    with jax.named_scope(name):
        return call_once(_TRACED, (name, chunk, interpret, *statics), call, operands)


def _ssd_fwd(xs, dts, cumc, cumr, bs, cs, skip, p, chunk, save, interpret):
    bsz, g, total, r = dts.shape
    state = (bsz, g, total // chunk, bs.shape[2] // g, r * p)
    outs = [(xs.shape, jnp.float32)] + ([(state, jnp.float32)] if save else [])
    return _ssd_call(
        "ssd_fwd", _ssd_fwd_kernel, (p, save), "wccrwwk" + "ws"[: len(outs)],
        (xs, dts, cumc, cumr, bs, cs, skip), outs, chunk, state[3:], False, interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _ssd(xs, dts, cumc, cumr, bs, cs, skip, p, chunk, interpret):
    return _ssd_fwd(xs, dts, cumc, cumr, bs, cs, skip, p, chunk, False, interpret)[0]


def _ssd_vjp_fwd(xs, dts, cumc, cumr, bs, cs, skip, p, chunk, interpret):
    y, entering = _ssd_fwd(xs, dts, cumc, cumr, bs, cs, skip, p, chunk, True, interpret)
    return y, (xs, dts, cumc, cumr, bs, cs, skip, entering)


def _ssd_vjp_bwd(p, chunk, interpret, res, dy):
    *inputs, entering = res
    skip = inputs[-1]
    outs = [(x.shape, x.dtype) for x in inputs[:-1]]  # dts, cumc and cumr are float32
    outs.append((entering.shape[:3] + (1, entering.shape[4]), jnp.float32))
    *grads, dskip = _ssd_call(
        "ssd_bwd", _ssd_bwd_kernel, (p,), "wccrwwksw" + "wccrwws",
        (*inputs, entering, dy), outs, chunk, entering.shape[3:], True, interpret,
    )
    # a chunk's lanes -> the group's heads: (b, g, chunks, 1, r * p) -> (g, 1, r)
    g, _, r = skip.shape
    return (*grads, dskip.reshape(dskip.shape[:3] + (r, p)).sum((0, 2, 4)).reshape(g, 1, r))


_ssd.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


def ssd_scan(x, dt, a, b_in, c_in, *, chunk: int = 128, d_skip=None, interpret: bool = False) -> jax.Array:
    """:func:`ssd_chunked`, arguments and result alike, as the fused kernel
    pair; differentiable in every array. With ``d_skip`` (h,) float32 the
    result is ``S C + D x``: the skip costs the kernels one multiply-add on a
    tile they hold, and saves XLA a pass over the float32 ``y`` each way.
    ``interpret`` runs the kernels interpreted (the tests)."""
    bsz, t, h, p = x.shape
    g, n = b_in.shape[2:]
    r = h // g
    pad = (-t) % chunk
    if pad:
        x, dt, b_in, c_in = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b_in, c_in)
        )
    total, f32 = t + pad, jnp.float32
    dts = dt.astype(f32).reshape(bsz, total // chunk, chunk, g, r)
    # ssd_chunked's cumulative sum inside each chunk, (b, c, g, r, q), float32,
    # as a product with a triangle of ones at full float32 precision: on a TPU
    # ``jnp.cumsum`` is a reduce-window that costs this 2 MB array 0.75 ms a
    # call, as much as the forward kernel (PERF.md section 6, PR 30)
    steps = jnp.moveaxis(dts * a.astype(f32).reshape(g, r), 2, -1)
    cum = jnp.einsum(
        "bcgrj,ji->bcgri", steps, jnp.triu(jnp.ones((chunk, chunk), f32)),
        precision=jax.lax.Precision.HIGHEST,
    )
    cumr = jnp.moveaxis(cum, 1, 3).reshape(bsz, g, r, total)
    skip = jnp.zeros((h,), f32) if d_skip is None else d_skip.astype(f32)
    # inside a checked shard_map the custom VJP's cotangents vary as x does
    skip = jax.lax.pcast(skip, tuple(jax.typeof(x).vma - jax.typeof(skip).vma), to="varying")
    y = _ssd(
        x.reshape(bsz, total, h * p),
        jnp.moveaxis(dts.reshape(bsz, total, g, r), 1, 2),
        jnp.swapaxes(cumr, 2, 3),
        cumr,
        b_in.reshape(bsz, total, g * n),
        c_in.reshape(bsz, total, g * n),
        skip.reshape(g, 1, r),
        p, chunk, interpret,
    )
    return y.reshape(bsz, total, h, p)[:, :t]


# -- the short convolution as a fused kernel pair --------------------------------
#
# ``xBC`` is the columns [d_inner, d_inner + conv_dim) of the input projection's
# float32 output ``proj`` = [z | x | B | C | dt]. The forward kernel's grid step
# is a row tile (``short_conv``: its rows, halos and taps) of ONE GROUP: its heads'
# ``x`` columns and its ``B`` and ``C`` columns, read straight out of ``proj`` (a
# BlockSpec a piece), the taps, the bias and SiLU float32 as the mixer's XLA
# expression has them, ``x``, ``B`` and ``C`` out in the compute dtype in the
# layouts ``ssd_scan`` takes: no slice of ``xBC`` is made. A group's columns of
# ``proj`` are not contiguous, and the backward kernel writes ``proj``'s whole
# float32 cotangent, so its grid step is a row tile of one BLOCK of ``proj``'s
# columns (:func:`_conv_block`), the block axis last: ``z``'s blocks take their
# cotangent through the cast, ``xBC``'s the taps again and the anti-causal taps,
# and the last block, past the array's end but for ``dt``'s columns, their
# cotangent as it came (``z`` and ``dt``'s columns pass through the custom VJP,
# so autodiff adds no second (tokens, proj) array to it). An input whose block
# the step does not read holds the index of the block it read last or reads
# next, so nothing is fetched twice; the weights and the bias are one (kw + 1,
# conv_dim) array, and their cotangent a block a row tile, summed by XLA.


def _conv_block(c: Mamba2Config) -> int:
    """Columns of ``proj`` a backward grid step: the widest of 512, 256, 128
    that cuts ``z``, ``x``, ``B`` and ``C`` into whole blocks."""
    return next(w for w in (512, 256, _LANE) if c.d_inner % w == 0 and (c.groups * c.state) % w == 0)


def _conv_impl(rows: int, c: Mamba2Config) -> str:
    """Observed, never chosen: ``"pallas"`` on a TPU when the shapes tile (a
    group's ``x`` columns and the state multiples of the 128 lanes, ``dt``'s
    columns within one block, the rows a multiple of the row tile), ``"xla"``
    (the mixer's expression) elsewhere. The tests make it ``"interpret"``."""
    tiles = (
        (c.d_inner // c.groups) % _LANE == 0 and c.d_inner % c.groups == 0 and c.state % _LANE == 0
        and c.heads <= _conv_block(c) and short_conv.tiles(rows, c.conv_kernel))
    return "pallas" if on_tpu() and tiles else "xla"


def _silu_grad(a, g):
    """The cotangent of SiLU's input ``a`` from its output's float32 cotangent ``g``."""
    sig = jax.nn.sigmoid(a)
    return g * sig + g * a * sig * (1.0 - sig)


def _lane_tiles(width: int):
    return [slice(k * _LANE, (k + 1) * _LANE) for k in range(width // _LANE)]


def _conv_fwd_kernel(kw, *refs):
    """One row tile of one group: ``x``, ``B`` and ``C``, each from its own
    columns of ``proj``, a lane tile at a time."""
    xs, befores, ws, outs = refs[:3], refs[3:6], refs[6:9], refs[9:]
    first = pl.program_id(2) == 0
    for x_ref, before_ref, w_ref, out_ref in zip(xs, befores, ws, outs, strict=True):
        for lanes in _lane_tiles(x_ref.shape[1]):
            a = short_conv.forward(x_ref, before_ref, w_ref, first, kw, lanes) + w_ref[:, lanes][kw : kw + 1]
            out_ref[:, lanes] = jax.nn.silu(a).astype(out_ref.dtype)


def _conv_bwd_kernel(kw, kinds, *refs):
    """One row tile of one block of ``proj``'s columns. ``kinds``: the grid
    steps at which ``z``'s, ``x``'s, ``B``'s and ``C``'s blocks start, then
    ``dt``'s one block."""
    x_ref, before_ref, after_ref, w_ref, gz_ref, gx_ref, gb_ref, gc_ref, *rest = refs
    gx_after, gb_after, gc_after, gdt_ref, dx_ref, dw_ref = rest
    _, x_at, b_at, c_at, dt_at = kinds
    f32, j = jnp.float32, pl.program_id(2)
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    tap = jax.lax.broadcasted_iota(jnp.int32, (kw + 1, 1), 0)

    @pl.when(j < x_at)
    def _():
        for lanes in _lane_tiles(dx_ref.shape[1]):  # the gate's columns: its cotangent through the cast
            dx_ref[:, lanes] = gz_ref[:, lanes].astype(f32)

    def conv(g_ref, g_after):
        for lanes in _lane_tiles(dx_ref.shape[1]):
            w = w_ref[:, lanes]
            bias = w[kw : kw + 1]
            dx, da, seen = short_conv.backward(
                w, x_ref[:, lanes], before_ref, after_ref, g_ref[:, lanes], g_after[:, lanes], first, last, kw,
                lambda a, g, bias=bias: _silu_grad(a + bias, g), lanes)
            dx_ref[:, lanes] = dx
            dw_ref[:, lanes] = short_conv.tap_sums(da, seen, tap) + jnp.where(
                tap == kw, jnp.sum(da, axis=0, keepdims=True), 0.0)

    for lo, hi, g_ref, g_after in zip(
            (x_at, b_at, c_at), (b_at, c_at, dt_at), (gx_ref, gb_ref, gc_ref), (gx_after, gb_after, gc_after)):
        pl.when((j >= lo) & (j < hi))(functools.partial(conv, g_ref, g_after))

    @pl.when(j == dt_at)
    def _():  # dt's columns, at the head of a block that reaches past the array's end
        dx_ref[:, : gdt_ref.shape[1]] = gdt_ref[...]


def _conv_call(name, kernel, statics, c, grid, operands, in_specs, outs, out_specs, interpret):
    call = pl.pallas_call(
        functools.partial(kernel, c.conv_kernel, *statics),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[out_struct(shape, dtype, *operands) for shape, dtype in outs],
        interpret=interpret_arg(interpret, *operands),
    )
    with jax.named_scope(name):  # the device op takes the innermost scope's name, as the scan's do
        return call_once(_TRACED, (name, c, interpret, grid, *statics), call, operands)


def _conv_fwd(proj, wb, c, interpret):
    bsz, rows = proj.shape[:2]
    d_in, gn, n, width = c.d_inner, c.groups * c.state, c.state, c.d_inner // c.groups
    tile = short_conv.tile_rows(rows)
    pieces = [(d_in, width, width), (2 * d_in, n, n), (2 * d_in + gn, n, n)]  # a group's x, B, C in proj
    weights = [(w, lambda h, o=o - d_in, s=s, w=w: (h * s + o) // w) for o, s, w in pieces]
    out_spec = lambda w: pl.BlockSpec((None, tile, w), lambda h, b, i: (b, i, h))
    x, b_in, c_in = _conv_call(
        "ssd_conv_fwd", _conv_fwd_kernel, (), c, (c.groups, bsz, rows // tile), (proj,) * 6 + (wb,) * 3,
        short_conv.piece_specs(pieces, rows, tile, False) + short_conv.weight_specs(c.conv_kernel + 1, weights),
        [((bsz, rows, d_in), c.dtype), ((bsz, rows, gn), c.dtype), ((bsz, rows, gn), c.dtype)],
        [out_spec(width), out_spec(n), out_spec(n)], interpret)
    return proj[..., :d_in].astype(c.dtype), x, b_in, c_in, proj[..., d_in + c.conv_dim :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv(proj, wb, c, interpret):
    return _conv_fwd(proj, wb, c, interpret)


def _conv_vjp_fwd(proj, wb, c, interpret):
    return _conv_fwd(proj, wb, c, interpret), (proj, wb)


def _conv_vjp_bwd(c, interpret, res, cts):
    proj, wb = res
    bsz, rows, cols = proj.shape
    kw, tile, block = c.conv_kernel, short_conv.tile_rows(rows), _conv_block(c)
    xs, bs = c.d_inner // block, c.groups * c.state // block
    kinds = (0, xs, 2 * xs, 2 * xs + bs, 2 * xs + 2 * bs)  # the steps at which z, x, B, C and dt start
    conv_blocks = xs + 2 * bs
    # a block index among ``n`` from grid step ``lo``, held at its ends
    held = lambda j, lo, n: jnp.minimum(jnp.maximum(j - lo, 0), n - 1)
    cut = lambda size, at, col: pl.BlockSpec((None, size, block), lambda b, i, j: (b, at(i), col(j)))
    conv_col = lambda j: held(j, xs, conv_blocks)
    rows_at = {kind: short_conv.row_block(kind, rows, tile) for kind in ("tile", "before", "after")}
    after = short_conv.row_block("after", rows, tile, 16)  # a bfloat16 tile's rows
    ct = list(zip(kinds[:4], (xs, xs, bs, bs)))  # the cotangents of z, x, B and C: where each starts, its blocks
    in_specs = (
        [cut(tile if kind == "tile" else short_conv.HALO, rows_at[kind], lambda j: conv_col(j) + xs)
         for kind in ("tile", "before", "after")]
        + [pl.BlockSpec((kw + 1, block), lambda b, i, j: (0, conv_col(j)))]
        + [cut(tile, rows_at["tile"], lambda j, lo=lo, n=n: held(j, lo, n)) for lo, n in ct]
        + [cut(16, after, lambda j, lo=lo, n=n: held(j, lo, n)) for lo, n in ct[1:]]
        + [pl.BlockSpec((None, tile, c.heads), lambda b, i, j: (b, i, 0))])
    gz, gx, gb, gc, gdt = cts
    dproj, dwb = _conv_call(
        "ssd_conv_bwd", _conv_bwd_kernel, (kinds,), c, (bsz, rows // tile, kinds[-1] + 1),
        (proj,) * 3 + (wb, gz, gx, gb, gc, gx, gb, gc, gdt), in_specs,
        [((bsz, rows, cols), jnp.float32), ((bsz, rows // tile, kw + 1, c.conv_dim), jnp.float32)],
        [pl.BlockSpec((None, tile, block), lambda b, i, j: (b, i, j)),
         pl.BlockSpec((None, None, kw + 1, block), lambda b, i, j: (b, i, 0, conv_col(j)))], interpret)
    return dproj, dwb.sum((0, 1))


_conv.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


def ssd_conv(proj: jax.Array, conv_w: jax.Array, conv_b: jax.Array, c: Mamba2Config, *, interpret: bool = False):
    """The mixer's causal depthwise convolution over ``xBC``, its bias and
    SiLU, as the fused kernel pair; differentiable in all three arrays.
    ``proj`` (b, t, [z d_inner | xBC conv_dim | dt heads]) float32, as the
    input projection leaves it; ``conv_w`` (kw, conv_dim) and ``conv_b``
    (conv_dim,) float32. Returns ``z`` (b, t, d_inner), ``x`` (b, t, heads,
    head_dim), ``B`` and ``C`` (b, t, groups, state), all in ``c.dtype``, and
    ``dt``'s columns of ``proj`` (b, t, heads) float32 as they are."""
    bsz, t = proj.shape[:2]
    z, x, b_in, c_in, dt = _conv(proj, jnp.concatenate([conv_w, conv_b[None]], axis=0), c, interpret)
    groups = lambda a: a.reshape(bsz, t, c.groups, c.state)
    return z, x.reshape(bsz, t, c.heads, c.head_dim), groups(b_in), groups(c_in), dt


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

        conv_impl = _conv_impl(t, c)
        with _span("ssm.in_proj"):
            proj = jnp.dot(
                u.astype(c.dtype), w_in.astype(c.dtype), preferred_element_type=f32
            )
            if conv_impl == "xla":
                z = proj[..., :d_in].astype(c.dtype)
                xbc = proj[..., d_in : d_in + c.conv_dim]
                dt = jax.nn.softplus(proj[..., d_in + c.conv_dim :] + dt_bias)  # float32
        with _span("ssm.conv"):
            get_registry().counter(
                "consensusml_ssm_conv_impl_total",
                "short convolutions of the SSD layer traced, by layer and by who schedules them: "
                "the fused kernel pair or XLA",
                labels={"layer": str(self.layer), "impl": "xla" if conv_impl == "xla" else "kernel"},
            ).inc()
            if conv_impl == "xla":
                # depthwise, causal: conv[t] = sum_j w[j] xBC[t - (K-1) + j] + b
                padded = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
                conv = sum(padded[:, j : j + t] * conv_w[j] for j in range(kw)) + conv_b
                xbc = jax.nn.silu(conv).astype(c.dtype)
                x = xbc[..., :d_in].reshape(bsz, t, c.heads, c.head_dim)
                b_in = xbc[..., d_in : d_in + gn].reshape(bsz, t, c.groups, c.state)
                c_in = xbc[..., d_in + gn :].reshape(bsz, t, c.groups, c.state)
            else:  # the bias and SiLU too; z and dt's columns through the pair, for their cotangents' sake
                z, x, b_in, c_in, dt = ssd_conv(proj, conv_w, conv_b, c, interpret=conv_impl == "interpret")
                dt = jax.nn.softplus(dt + dt_bias)  # float32
        with _span("ssm.scan", chunk=c.chunk):
            get_registry().counter(
                "consensusml_ssm_chunks_total",
                "chunks of the SSD scan traced (rows x chunks a call), by layer",
                labels={"layer": str(self.layer)},
            ).inc(bsz * -(-t // c.chunk))
            impl = _scan_impl(c.chunk, c.head_dim, c.state, c.heads // c.groups)
            get_registry().counter(
                "consensusml_ssm_scan_impl_total",
                "SSD scans traced, by layer and by who schedules them: the fused kernel pair or XLA",
                labels={"layer": str(self.layer), "impl": "xla" if impl == "xla" else "kernel"},
            ).inc()
            if impl == "xla":
                y = ssd_chunked(x, dt, -jnp.exp(a_log), b_in, c_in, chunk=c.chunk)
                y = y + d_skip[:, None] * x.astype(f32)
            else:
                y = ssd_scan(
                    x, dt, -jnp.exp(a_log), b_in, c_in, chunk=c.chunk, d_skip=d_skip,
                    interpret=impl == "interpret",
                )
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
