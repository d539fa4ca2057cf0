"""Gated DeltaNet mixer: linear attention by the gated delta rule, in chunks.

The linear-attention layer of the decoders whose period is three of these to
one softmax attention (:mod:`consensusml_tpu.models.nemotron_h`, kind ``G``;
Yang, Kautz & Hatamizadeh 2024, "Gated Delta Networks"). Per value head the
layer keeps a (key width x value width) float32 state and, token by token,

    S <- exp(g_t) S;   S <- S + k_t (x) beta_t (v_t - S^T k_t);   o_t = S^T q_t

with ``g_t <= 0`` a log-decay and ``beta_t`` in (0, 1): the state forgets by
``exp(g_t)`` and then REPLACES what it held under key ``k_t`` by a step
``beta_t`` towards ``v_t``. Unlike the state-space dual of
:mod:`~consensusml_tpu.models.ssm` the update multiplies the state by
``(I - beta_t k_t k_t^T)``, so a chunk is not a decay triangle alone:
:func:`gated_delta_chunked` computes it ``chunk`` tokens at a time as batched
matrix products (forward, and backward by autodiff of the same products):

- within a chunk, with ``gamma`` the cumulative log-decay and ``Gamma[i, j] =
  exp(gamma_i - gamma_j)``: ``T = (I + tril(diag(beta) (K K^T o Gamma), -1))^-1``,
  ``W = T diag(beta) (K o e^gamma)``, ``U = T diag(beta) V``; the inverse of the
  unit lower-triangular matrix is a product of ``log2(chunk)`` factors
  (:func:`unit_lower_inverse`), float32 at ``Precision.HIGHEST``;
- across chunks a ``lax.scan`` carries ``S`` (float32): what the chunk's
  tokens really write is ``V' = U - W S``, their outputs ``(Q o e^gamma) S +
  (Q K^T o Gamma) V'``, and ``S <- e^{gamma_L} S + (K o e^{gamma_L - gamma})^T V'``.

The cumulative sums, the decays, ``T`` and the carried state are float32; the
other products take operands in the compute dtype and accumulate in float32.

On a TPU, at shapes that tile, the same rule runs as a pair of Pallas kernels
under a ``jax.custom_vjp`` (:func:`gated_delta_scan`; device events ``gdn_fwd``
/ ``gdn_bwd``): a grid step is a block of chunks of one key head and its value
heads, the block axis sequential, each value head's float32 state carried
across chunks in VMEM, ``T`` made by a blocked inverse
(:func:`blocked_unit_lower_inverse`) at full float32 precision, so nothing that
is (chunk x chunk) ever reaches HBM; the backward kernel walks the chunks in
reverse and makes each chunk's ``T``, ``W`` and ``U`` again from its operands and
the saved state that entered it. The arithmetic is :func:`gated_delta_chunked`'s
to the dtype. Which of the two runs is observed from the platform and the
shapes (:func:`_scan_impl`), never chosen: off a TPU
:func:`gated_delta_chunked` runs as it always did.
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

# the step's initialiser is Mamba-2's, and so are the kernels' products (a @ b, a @ b.T, a.T @ b; float32 accumulation)
from consensusml_tpu.models.ssm import _LANE, _NN, _NT, _TN, _dot, _dt_bias_init
from consensusml_tpu.obs import get_registry
from consensusml_tpu.obs import span as _span
from consensusml_tpu.pallas_util import call_once, interpret_arg, on_tpu, out_struct

__all__ = [
    "GatedDeltaConfig", "GatedDeltaNetMixer", "gated_delta_chunked", "gated_delta_scan", "unit_lower_inverse",
    "blocked_unit_lower_inverse",
]

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GatedDeltaConfig:
    hidden: int = 2048
    key_heads: int = 16
    value_heads: int = 32  # value head j reads key head j // (value_heads / key_heads)
    key_dim: int = 128
    value_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 64
    dt_min: float = 0.001  # the step is drawn log-uniform in [dt_min, dt_max], as Mamba-2's
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    norm_eps: float = 1e-6
    out_init_std: float = 0.02
    dtype: Any = jnp.bfloat16

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_heads * self.key_dim + self.value_heads * self.value_dim


def unit_lower_inverse(strict: jax.Array) -> jax.Array:
    """``(I - N)^-1`` for ``N = strict`` (..., c, c) STRICTLY lower triangular,
    float32: ``N`` is nilpotent (``N^c = 0``), so the Neumann series ends and
    factors as ``(I + N)(I + N^2)(I + N^4)...`` — ``log2(c)`` factors, each a
    squaring and a product at full float32 precision; no row-by-row loop."""
    c = strict.shape[-1]
    mm = lambda a, b: jnp.matmul(a, b, precision=_HIGHEST)
    out = jnp.eye(c, dtype=strict.dtype) + strict
    power, span = strict, 2
    while span < c:  # ``out`` holds the series up to N^(span - 1)
        power = mm(power, power)
        out = out + mm(out, power)
        span *= 2
    return out


def _carried(state):
    """What a chunk reads of the state carried to it. The identity; the
    planted-fault tests make it zeros (the state lost at every chunk boundary)."""
    return state


def gated_delta_chunked(
    q: jax.Array,  # (b, t, h, dk): normalised and scaled by the caller
    k: jax.Array,  # (b, t, h, dk): normalised
    v: jax.Array,  # (b, t, h, dv)
    g: jax.Array,  # (b, t, h) float32 log-decay, <= 0
    beta: jax.Array,  # (b, t, h) float32, in (0, 1)
    *,
    chunk: int = 64,
) -> jax.Array:
    """``o_t = S_t^T q_t`` of the recurrence above, float32, (b, t, h, dv), the
    state zero before each row's first token. ``t`` need not be a multiple of
    ``chunk``: the tail is padded with tokens of ``beta = 0`` and ``g = 0``,
    which leave the state as it is."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    dtype, f32 = q.dtype, jnp.float32
    pad = (-t) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta)
        )
    c = (t + pad) // chunk
    # (b, h, c, L, ...): heads beside the batch, a chunk's tokens last but one
    by_chunk = lambda x: jnp.moveaxis(x.reshape((bsz, c, chunk) + x.shape[2:]), 3, 1)
    qs, ks, vs = by_chunk(q), by_chunk(k), by_chunk(v)
    gs, betas = by_chunk(g.astype(f32)), by_chunk(beta.astype(f32))
    # cumulative log-decay inside each chunk, as a product with a triangle of
    # ones: on a TPU ``jnp.cumsum`` is a reduce-window (PERF.md section 6, PR 30)
    gamma = jnp.einsum(
        "bhcj,ji->bhci", gs, jnp.triu(jnp.ones((chunk, chunk), f32)), precision=_HIGHEST
    )
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # Gamma[i, j] = exp(gamma_i - gamma_j) for j <= i (the exponent never positive), else 0
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    kk = jnp.einsum("bhcid,bhcjd->bhcij", ks, ks, preferred_element_type=f32)
    strict = jnp.where(jnp.tril(lower, -1), betas[..., None] * kk * decay, 0.0)
    solve = unit_lower_inverse(-strict).astype(dtype)  # T
    up = jnp.exp(gamma)[..., None]  # e^gamma, (b, h, c, L, 1)
    k_beta = ks.astype(f32) * betas[..., None]
    w = jnp.einsum("bhcij,bhcjd->bhcid", solve, (k_beta * up).astype(dtype), preferred_element_type=f32)
    u = jnp.einsum(
        "bhcij,bhcjd->bhcid", solve, (vs.astype(f32) * betas[..., None]).astype(dtype),
        preferred_element_type=f32,
    )
    qk = jnp.einsum("bhcid,bhcjd->bhcij", qs, ks, preferred_element_type=f32)
    within = (qk * decay).astype(dtype)  # (Q K^T o Gamma), the diagonal kept
    q_up = (qs.astype(f32) * up).astype(dtype)
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None]  # e^{gamma_L - gamma}
    k_end = (ks.astype(f32) * to_end).astype(dtype)
    chunk_decay = jnp.exp(gamma[..., -1])  # (b, h, c)

    def step(state, inp):
        w_c, u_c, q_c, within_c, k_c, decay_c = inp
        seen = _carried(state).astype(dtype)
        written = u_c - jnp.einsum("bhid,bhde->bhie", w_c.astype(dtype), seen, preferred_element_type=f32)
        out = jnp.einsum("bhid,bhde->bhie", q_c, seen, preferred_element_type=f32) + jnp.einsum(
            "bhij,bhje->bhie", within_c, written.astype(dtype), preferred_element_type=f32
        )
        state = _carried(state) * decay_c[..., None, None] + jnp.einsum(
            "bhid,bhie->bhde", k_c, written.astype(dtype), preferred_element_type=f32
        )
        return state, out

    chunk_major = lambda x: jnp.moveaxis(x, 2, 0)
    # inside a checked shard_map the carried state varies as the inputs do
    state0 = jax.lax.pcast(jnp.zeros((bsz, h, dk, dv), f32), tuple(jax.typeof(w).vma), to="varying")
    _, out = jax.lax.scan(
        step,
        state0,
        tuple(chunk_major(x) for x in (w, u, q_up, within, k_end, chunk_decay)),
    )
    # (c, b, h, L, dv) -> (b, t, h, dv)
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(bsz, c * chunk, h, dv)[:, :t]


# -- the same rule as a fused kernel pair ----------------------------------------
#
# A grid step is a BLOCK of chunks (``_STEP_CHUNKS``, an inner loop) of ONE KEY
# HEAD and its ``r`` value heads: ``K K^T`` and ``Q K^T`` are made once a key
# head (the mixer's repeat of ``q`` and ``k`` over ``r`` and its cotangent's sum
# are the BlockSpec's and the kernel's), the block axis is the grid's last and
# sequential, and each value head's (key width x value width) float32 state stays
# in a VMEM scratch from chunk to chunk. ``Gamma``, ``A``, ``T``, ``W``, ``U`` and
# ``V'`` never reach HBM. What is (chunk x chunk) a value head is held for the key
# head's ``r`` heads SIDE BY SIDE along the lanes, (chunk, r x chunk): the TPU
# compiler schedules a chunk's chain of dependent steps as it is written and fills
# no slot from the other head's chain (PERF.md section 6, PR 32), so every
# instruction has to serve both. The cumulative log-decays are a small float32
# array that XLA makes beforehand (:func:`gated_delta_scan`) and hands in twice,
# time along the sublanes (``cols``, beside ``beta``) and along the lanes
# (``rows``, a key head's value heads side by side): the kernels never transpose
# a vector, and the backward kernel hands back a cotangent for each that autodiff
# adds up and takes through the sum to ``g``. Every rounding point is
# :func:`gated_delta_chunked`'s: operands in the compute dtype, float32
# accumulation, float32 ``gamma``, decays, ``T`` and carried state.

_BASE = 16  # the blocked inverse's diagonal blocks
_STEP_CHUNKS = 8  # chunks a grid step: a step costs ~0.4 us that no schedule shows (PERF.md section 6, PR 30)
_TRACED: dict = {}  # pallas_util.call_once keeps each kernel's one trace here


def _scan_impl(chunk: int, key_dim: int, value_dim: int) -> str:
    """Observed, never chosen: ``"pallas"`` on a TPU when the shapes tile (key
    and value widths multiples of the 128 lanes, the chunk a multiple of the
    bfloat16 sublane tile), ``"xla"`` (:func:`gated_delta_chunked`) elsewhere.
    The tests make it ``"interpret"``."""
    tiles = key_dim % _LANE == 0 and value_dim % _LANE == 0 and chunk % 16 == 0
    return "pallas" if on_tpu() and tiles else "xla"


def _dot32(a, b, dims=_NN):
    """A float32 product at full precision (six bfloat16 passes on the MXU)."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _lanes(rows: int, problems: int, size: int):
    """Of a (rows, problems x size) array that holds ``problems`` matrices side
    by side: each entry's row, the matrix it belongs to, and its column there."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, problems * size), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, problems * size), 1)
    which = sum(((col >= p * size).astype(jnp.int32) for p in range(1, problems)), jnp.zeros_like(col))
    return row, which, col - size * which


def _block_diagonal(side_by_side, which, problems: int):
    """(c, P x c) matrices side by side -> (P x c, P x c), each on the diagonal."""
    return jnp.concatenate([jnp.where(which == p, side_by_side, 0.0) for p in range(problems)], axis=0)


def _stacked(side_by_side, problems: int):
    """(c, P x c) matrices side by side -> (P x c, c), one below the other."""
    size = side_by_side.shape[1] // problems
    return jnp.concatenate([side_by_side[:, p * size : (p + 1) * size] for p in range(problems)], axis=0)


def blocked_unit_lower_inverse(strict: jax.Array) -> jax.Array:
    """``(I + N)^-1`` for ``N`` (c, c) STRICTLY lower triangular, float32, and
    for several such side by side (``strict`` (c, P x c), the result alike: the
    kernels invert a key head's value heads together, 128 lanes wide, every
    instruction serving all of them). Blocked: the ``_BASE``-wide diagonal
    blocks by forward substitution (elementwise float32, every block a step),
    then the blocks below them level by level, ``T_ij = -T_ii A_ij T_jj`` for
    every pair of neighbours at once as two products at full float32 precision
    with the block-diagonal of what is already inverted. ``(I - N)^-1`` of
    :func:`unit_lower_inverse` is this of ``-N``, at a tenth of its
    multiply-adds, and where that series' powers outgrow float32 (keys that
    repeat) this stays exact."""
    size, width = strict.shape
    problems = width // size
    row, which, col = _lanes(size, problems, size)
    times = lambda a, b: _dot32(a, _block_diagonal(b, which, problems))  # matrix by matrix
    blocks = size // _BASE
    if size % _BASE or blocks & (blocks - 1) or blocks < 2:  # nothing to block: the series, matrix by matrix
        out, power, span = (row == col).astype(strict.dtype) - strict, -strict, 2
        while span < size:
            power = times(power, power)
            out = out + times(out, power)
            span *= 2
        return out
    out = []
    in_block, mine, at_col = _lanes(_BASE, problems, size)  # (Mosaic slices no iota: a block of rows has its own)
    for m in range(blocks):  # the diagonal blocks, a block of rows each: rows below row j lose their multiple of it
        a, x = strict[m * _BASE : (m + 1) * _BASE], (in_block + m * _BASE == at_col).astype(strict.dtype)
        for j in range(_BASE - 1):
            # column j of the block, each matrix's over its own lanes: one gather along the lanes
            factor = jnp.take_along_axis(a, mine * size + (m * _BASE + j), axis=1)
            x = x - factor * x[j : j + 1, :]
        out.append(x)
    out = jnp.concatenate(out, axis=0)
    span = _BASE
    while span < size:  # blocks (2m+1, 2m) of width ``span``: -D A D lands where A's block is
        block = lambda index: index >> (span.bit_length() - 1)
        below = (block(row) == block(col) + 1) & (block(row) & 1 == 1)
        out = out - times(times(out, jnp.where(below, strict, 0.0)), out)
        span *= 2
    return out


class _Chunk:
    """One chunk of one key head and its ``r`` value heads, as
    :func:`gated_delta_chunked` makes it: every term the forward pass reads and
    the backward pass reads again, from the chunk's operands and the states that
    enter it. What is (chunk x chunk) a value head — ``Gamma``, ``K K^T``,
    ``Q K^T``, ``A``, ``T`` — is held for all ``r`` side by side, (chunk, r x
    chunk): one instruction serves every head, and ``K K^T`` and ``Q K^T`` are
    one product a key head."""

    def __init__(self, dtype, q, k, v, cols, gam_r, entering):
        f32, size, self.r = jnp.float32, q.shape[0], len(entering)
        r, dv = self.r, v.shape[1] // self.r
        self.row, self.head, col = _lanes(size, r, size)
        self.q32, self.k32 = q.astype(f32), k.astype(f32)
        self.v32 = [v[:, h * dv : (h + 1) * dv].astype(f32) for h in range(r)]
        self.gam_c, self.beta_c = ([cols[:, j + h : j + h + 1] for h in range(r)] for j in (0, r))
        self.beta = self.side_by_side(self.beta_c)
        self.decay = jnp.exp(jnp.where(self.row >= col, self.side_by_side(self.gam_c) - gam_r, -jnp.inf))  # Gamma
        self.k_again = jnp.concatenate([k] * r, axis=0)
        self.kk, self.qk = _dot(k, self.k_again, _NT), _dot(q, self.k_again, _NT)
        self.strict = self.row > col
        self.solve32 = blocked_unit_lower_inverse(jnp.where(self.strict, self.beta * self.kk * self.decay, 0.0))
        solve, within = self.solve32.astype(dtype), (self.qk * self.decay).astype(dtype)
        self.solve, self.within = self.each(solve), self.each(within)  # T; Q K^T o Gamma, the diagonal kept
        # a chunk's last gamma as a masked sum: Mosaic spreads a (1, 1) over lanes, then over sublanes
        last = [jnp.sum(jnp.where(self.row[:, :1] == size - 1, g, 0.0), axis=0, keepdims=True) for g in self.gam_c]
        self.up = [jnp.exp(g) for g in self.gam_c]
        self.to_end = [jnp.exp(l - g) for l, g in zip(last, self.gam_c)]
        self.across_one = [jnp.exp(l) for l in last]  # (1, 1)
        self.across = [jnp.exp(jnp.broadcast_to(l, (1, dv))) for l in last]
        self.k_up = [(self.k32 * b * u).astype(dtype) for b, u in zip(self.beta_c, self.up)]
        self.v_beta = [(x * b).astype(dtype) for x, b in zip(self.v32, self.beta_c)]
        self.q_up = [(self.q32 * u).astype(dtype) for u in self.up]
        self.k_end = [(self.k32 * e).astype(dtype) for e in self.to_end]
        self.seen = [s.astype(dtype) for s in entering]
        self.w = [_dot(t, x, _NN).astype(dtype) for t, x in zip(self.solve, self.k_up)]
        self.written = [  # V'
            (_dot(t, x, _NN) - _dot(w, s, _NN)).astype(dtype)
            for t, x, w, s in zip(self.solve, self.v_beta, self.w, self.seen)]

    def side_by_side(self, per_head):
        """Per-head arrays, (chunk, 1) columns or (chunk, r x chunk) with their
        own head's lanes right -> (chunk, r x chunk): each over its head's lanes."""
        out = per_head[0]
        for h in range(1, self.r):
            out = jnp.where(self.head >= h, per_head[h], out)
        return out

    def each(self, side_by_side):
        size = side_by_side.shape[1] // self.r
        return [side_by_side[:, h * size : (h + 1) * size] for h in range(self.r)]

    def out(self, h):
        return _dot(self.q_up[h], self.seen[h], _NN) + _dot(self.within[h], self.written[h], _NN)

    def leaving(self, h, entering):
        return entering * self.across[h] + _dot(self.k_end[h], self.written[h], _TN)


def _chunk_operands(chunk, c, q_ref, k_ref, v_ref, cols_ref, rows_ref):
    at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    return at, q_ref[at, :], k_ref[at, :], v_ref[at, :], cols_ref[at, :], rows_ref[c]


def _gdn_fwd_kernel(chunk, save, carried, q_ref, k_ref, v_ref, cols_ref, rows_ref, o_ref, *rest):
    state = rest[-1]  # (r, dk, dv) float32: the key head's value heads' states
    steps, r = rows_ref.shape[0], state.shape[0]
    dv = v_ref.shape[1] // r

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one_chunk(c, _):
        at, *operands = _chunk_operands(chunk, c, q_ref, k_ref, v_ref, cols_ref, rows_ref)
        entering = [carried(state[h]) for h in range(r)]
        term = _Chunk(q_ref.dtype, *operands, entering)
        for h in range(r):
            if save:
                rest[0][c, h] = entering[h]
            o_ref[at, h * dv : (h + 1) * dv] = term.out(h)
            state[h] = term.leaving(h, entering[h])
        return 0

    jax.lax.fori_loop(0, steps, one_chunk, 0)


def _gdn_bwd_kernel(
    chunk, carried, q_ref, k_ref, v_ref, cols_ref, rows_ref, s_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dcols_ref, drows_ref, dstate,
):
    """A block of chunks of one key head, the blocks and the chunks in REVERSE:
    ``dstate`` carries the cotangent of the state that leaves a chunk. A chunk's
    ``T``, ``W``, ``U`` and ``V'`` are made again from its operands and the saved
    states that entered it; through the inverse, ``dA = -T^T dT T^T``."""
    steps, r = rows_ref.shape[0], dstate.shape[0]
    dv = v_ref.shape[1] // r
    dtype, f32 = q_ref.dtype, jnp.float32
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, 2 * r), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    heads_summed = lambda stacked: sum(stacked[h * chunk : (h + 1) * chunk] for h in range(r))

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def one_chunk(i, _):
        c = steps - 1 - i
        at, q, k, v, cols, gam_r = _chunk_operands(chunk, c, q_ref, k_ref, v_ref, cols_ref, rows_ref)
        entering = [s_ref[c, h] for h in range(r)]  # float32, as the forward kernel read them
        leaving = [carried(dstate[h]) for h in range(r)]
        t = _Chunk(dtype, q, k, v, cols, gam_r, entering)
        written_all = jnp.concatenate(t.written, axis=0)  # (r x chunk, dv), and likewise below
        k_up_all, v_beta_all = jnp.concatenate(t.k_up, axis=0), jnp.concatenate(t.v_beta, axis=0)
        dq, dk = jnp.zeros(q.shape, f32), jnp.zeros(k.shape, f32)
        dwithin, dsolve, dk_up, dv_beta, dq_up, dk_end = [], [], [], [], [], []
        for h in range(r):
            do_lo, leaving_lo = do_ref[at, h * dv : (h + 1) * dv].astype(dtype), leaving[h].astype(dtype)
            # out = q_up seen + within V';  leaving = across entering + k_end^T V'
            dq_up.append(_dot(do_lo, t.seen[h], _NT))
            dwithin.append(_dot(do_lo, written_all, _NT))  # head h's lanes are its own
            dwritten = (_dot(t.within[h], do_lo, _TN) + _dot(t.k_end[h], leaving_lo, _NN)).astype(dtype)
            dk_end.append(_dot(t.written[h], leaving_lo, _NT))
            # V' = U - W seen;  W = T k_up;  U = T v_beta
            dw = (-_dot(dwritten, t.seen[h], _NT)).astype(dtype)
            dstate[h] = leaving[h] * t.across[h] + _dot(t.q_up[h], do_lo, _TN) - _dot(t.w[h], dwritten, _TN)
            dsolve.append(_dot(dw, k_up_all, _NT) + _dot(dwritten, v_beta_all, _NT))
            dk_up.append(_dot(t.solve[h], dw, _TN))
            dv_beta.append(_dot(t.solve[h], dwritten, _TN))
        dwithin, dsolve = t.side_by_side(dwithin), t.side_by_side(dsolve)  # (chunk, r x chunk), as T and Gamma are
        # T = (I + A)^-1;  A = strict(beta K K^T o Gamma);  within = Q K^T o Gamma
        solve_bd = _block_diagonal(t.solve32, t.head, r)
        first = _dot32(_stacked(t.solve32, r), _block_diagonal(dsolve, t.head, r), _TN)  # T^T dT, head by head
        da_decay = jnp.where(t.strict, -_dot32(first, solve_bd, _NT), 0.0) * t.decay
        dkk, dqk = (da_decay * t.beta).astype(dtype), (dwithin * t.decay).astype(dtype)
        moved = da_decay * t.beta * t.kk + dwithin * t.qk * t.decay  # d(gamma_i - gamma_j)
        drows_ref[c] = -jnp.sum(moved, axis=0, keepdims=True)
        through_a = da_decay * t.kk
        dcols = jnp.zeros((chunk, 2 * r), f32)
        for h in range(r):
            mine = t.head == h
            dq = dq + dq_up[h] * t.up[h]
            dk = dk + dk_up[h] * (t.beta_c[h] * t.up[h]) + dk_end[h] * t.to_end[h]
            dv_ref[at, h * dv : (h + 1) * dv] = (dv_beta[h] * t.beta_c[h]).astype(dv_ref.dtype)
            k_side = rowsum(dk_up[h] * t.k32)
            dbeta = rowsum(jnp.where(mine, through_a, 0.0)) + k_side * t.up[h] + rowsum(dv_beta[h] * t.v32[h])
            at_end = rowsum(dk_end[h] * t.k32) * t.to_end[h]
            through_up = (rowsum(dq_up[h] * t.q32) + k_side * t.beta_c[h]) * t.up[h]
            dgam = rowsum(jnp.where(mine, moved, 0.0)) + through_up - at_end
            at_last = jnp.sum(at_end, axis=0, keepdims=True) + t.across_one[h] * jnp.sum(
                rowsum(leaving[h] * entering[h]), axis=0, keepdims=True)
            dgam = dgam + jnp.where(is_last, at_last, 0.0)
            dcols = jnp.where(lane == h, dgam, jnp.where(lane == r + h, dbeta, dcols))
        # K K^T and Q K^T are the key head's: their cotangents' products add the value heads up
        dq_ref[at, :] = (dq + _dot(dqk, t.k_again, _NN)).astype(dq_ref.dtype)
        dk = dk + _dot(dkk, t.k_again, _NN) + heads_summed(_dot(dkk, k, _TN) + _dot(dqk, q, _TN))
        dk_ref[at, :] = dk.astype(dk_ref.dtype)
        dcols_ref[at, :] = dcols
        return 0

    jax.lax.fori_loop(0, steps, one_chunk, 0)


def _gdn_call(name, kernel, statics, kinds, operands, outs, chunk, reverse, interpret):
    """One of the pair; its first five operands are ``q``, ``k``, ``v``, ``cols``
    and ``rows``. ``kinds`` says of each operand and then of each output which
    of four arrays it is: ``w`` (b, T, key heads x width), cut into (block of
    tokens, width); ``c`` (b, key heads, T, 2r) per-token columns, ``gamma`` then
    ``beta`` of the key head's r value heads; ``r`` (b, key heads, chunks, 1, r x
    chunk) a chunk's ``gamma`` as a row, the value heads side by side; ``s`` (b,
    key heads, chunks, r, dk, dv) a state a chunk and value head. ``outs``:
    (shape, dtype) each."""
    q, _, v, cols, rows = operands[:5]
    bsz, heads, chunks = rows.shape[:3]
    r = cols.shape[3] // 2
    steps = min(_STEP_CHUNKS, chunks)
    block, blocks = steps * chunk, chunks // steps
    at = (lambda c: blocks - 1 - c) if reverse else (lambda c: c)

    def spec(kind, shape):
        if kind == "w":
            return pl.BlockSpec((None, block, shape[2] // heads), lambda b, i, c: (b, at(c), i))
        if kind == "c":
            return pl.BlockSpec((None, None, block, shape[3]), lambda b, i, c: (b, i, at(c), 0))
        if kind == "r":
            return pl.BlockSpec((None, None, steps) + shape[3:], lambda b, i, c: (b, i, at(c), 0, 0))
        return pl.BlockSpec((None, None, steps) + shape[3:], lambda b, i, c: (b, i, at(c), 0, 0, 0))

    shapes = [x.shape for x in operands] + [shape for shape, _ in outs]
    specs = [spec(kind, shape) for kind, shape in zip(kinds, shapes, strict=True)]
    call = pl.pallas_call(
        functools.partial(kernel, chunk, *statics, _carried),
        grid=(bsz, heads, blocks),
        in_specs=specs[: len(operands)],
        out_specs=specs[len(operands) :],
        out_shape=[out_struct(shape, dtype, *operands) for shape, dtype in outs],
        scratch_shapes=[pltpu.VMEM(_saved_shape(q, v, cols, rows)[3:], jnp.float32)],  # (r, dk, dv)
        interpret=interpret_arg(interpret, *operands),
    )
    # the device op takes the innermost scope's name; ``pallas_call(name=)``
    # would come out wrapped in the transforms' names (``vmap_jvp_gdn_fwd__``)
    with jax.named_scope(name):
        return call_once(_TRACED, (name, chunk, interpret, _carried, *statics), call, operands)


def _saved_shape(q, v, cols, rows):
    """(b, key heads, chunks, r, dk, dv): the float32 state that enters each chunk of each value head."""
    bsz, heads, chunks = rows.shape[:3]
    r = cols.shape[3] // 2
    return (bsz, heads, chunks, r, q.shape[2] // heads, v.shape[2] // heads // r)


def _gdn_fwd(q, k, v, cols, rows, chunk, save, interpret):
    outs = [(v.shape, jnp.float32)] + ([(_saved_shape(q, v, cols, rows), jnp.float32)] if save else [])
    return _gdn_call(
        "gdn_fwd", _gdn_fwd_kernel, (save,), "wwwcr" + "ws"[: len(outs)], (q, k, v, cols, rows), outs,
        chunk, False, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn(q, k, v, cols, rows, chunk, interpret):
    return _gdn_fwd(q, k, v, cols, rows, chunk, False, interpret)[0]


def _gdn_vjp_fwd(q, k, v, cols, rows, chunk, interpret):
    out, entering = _gdn_fwd(q, k, v, cols, rows, chunk, True, interpret)
    return out, (q, k, v, cols, rows, entering)


def _gdn_vjp_bwd(chunk, interpret, res, dout):
    inputs = res[:-1]
    return tuple(_gdn_call(
        "gdn_bwd", _gdn_bwd_kernel, (), "wwwcrsw" + "wwwcr", (*res, dout),
        [(x.shape, x.dtype) for x in inputs], chunk, True, interpret))


_gdn.defvjp(_gdn_vjp_fwd, _gdn_vjp_bwd)


def gated_delta_scan(q, k, v, g, beta, *, chunk: int = 64, interpret: bool = False) -> jax.Array:
    """:func:`gated_delta_chunked`, arguments and result alike, as the fused
    kernel pair; differentiable in all five arrays. ``q`` and ``k`` may come
    with fewer heads than ``v``, ``g`` and ``beta``: value head ``j`` reads key
    head ``j // (value heads / key heads)``, and no copy is made of them.
    ``interpret`` runs the kernels interpreted (the tests)."""
    bsz, t, kh, dk = q.shape
    vh, dv = v.shape[2:]
    r, f32 = vh // kh, jnp.float32
    chunks = -(-t // chunk)
    chunks += (-chunks) % min(_STEP_CHUNKS, chunks)  # whole grid steps
    pad = chunks * chunk - t
    if pad:  # tokens of beta = 0 and g = 0, which leave the state as it is
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta)
        )
    total = t + pad
    # gated_delta_chunked's cumulative log-decay inside each chunk, float32, as a
    # product with a triangle of ones: on a TPU ``jnp.cumsum`` is a reduce-window
    gamma = jnp.einsum(
        "bcjh,ji->bcih", g.astype(f32).reshape(bsz, chunks, chunk, vh),
        jnp.triu(jnp.ones((chunk, chunk), f32)), precision=_HIGHEST,
    )
    by_key_head = lambda x: jnp.moveaxis(x.reshape(bsz, total, kh, r), 1, 2)  # (b, kh, T, r)
    cols = jnp.concatenate([by_key_head(gamma), by_key_head(beta.astype(f32))], axis=-1)
    # (b, kh, chunks, 1, r x chunk): a key head's value heads side by side, as the kernels hold Gamma
    rows = jnp.transpose(gamma.reshape(bsz, chunks, chunk, kh, r), (0, 3, 1, 4, 2))
    rows = rows.reshape(bsz, kh, chunks, 1, r * chunk)
    out = _gdn(
        q.reshape(bsz, total, kh * dk), k.reshape(bsz, total, kh * dk), v.reshape(bsz, total, vh * dv),
        cols, rows, chunk, interpret,
    )
    return out.reshape(bsz, total, vh, dv)[:, :t]


class GatedDeltaNetMixer(nn.Module):
    """``u (b, t, hidden) -> ((b, t, hidden), out_rms (b, value_heads))``;
    parameters float32, products in ``config.dtype``. ``out_rms`` is the root
    mean square, per value head, of what the delta rule put out (``o``): a
    device value that a comparison with the plain recurrence reads. ``layer``
    labels the trace-time chunk counter."""

    config: GatedDeltaConfig
    layer: int = 0

    @nn.compact
    def __call__(self, u: jax.Array) -> tuple[jax.Array, jax.Array]:
        c = self.config
        bsz, t, _ = u.shape
        kh, vh, dk, dv, kw = c.key_heads, c.value_heads, c.key_dim, c.value_dim, c.conv_kernel
        r = vh // kh
        f32 = jnp.float32
        normal = nn.initializers.normal
        # per key head [q dk | k dk | v r x dv | z r x dv]; per key head [b r | a r]
        w_qkvz = self.param("in_proj_qkvz", normal(0.02), (c.hidden, 2 * kh * dk + 2 * vh * dv), f32)
        w_ba = self.param("in_proj_ba", normal(0.02), (c.hidden, 2 * vh), f32)
        conv_w = self.param(
            "conv_kernel", lambda key, s, d=f32: jax.random.uniform(key, s, d, -0.5, 0.5),
            (kw, c.conv_dim), f32,
        )
        dt_bias = self.param("dt_bias", _dt_bias_init(c), (vh,), f32)
        a_log = self.param(
            "A_log", lambda key, s, d=f32: jnp.log(jax.random.uniform(key, s, d, 1.0, 16.0)),
            (vh,), f32,
        )
        norm_w = self.param("norm", nn.initializers.ones_init(), (dv,), f32)
        w_out = self.param("out_proj", normal(c.out_init_std), (vh * dv, c.hidden), f32)

        with _span("gdn.in_proj"):
            x = u.astype(c.dtype)
            qkvz = jnp.dot(x, w_qkvz.astype(c.dtype), preferred_element_type=f32)
            qkvz = qkvz.reshape(bsz, t, kh, 2 * dk + 2 * r * dv)
            ba = jnp.dot(x, w_ba.astype(c.dtype), preferred_element_type=f32).reshape(bsz, t, kh, 2 * r)
            z = qkvz[..., 2 * dk + r * dv :].reshape(bsz, t, vh, dv).astype(c.dtype)
            beta = jax.nn.sigmoid(ba[..., :r]).reshape(bsz, t, vh)
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., r:].reshape(bsz, t, vh) + dt_bias)
        with _span("gdn.conv"):
            # depthwise, causal, no bias, over [q | k | v] each flattened over its heads
            mixed = jnp.concatenate(
                [
                    qkvz[..., :dk].reshape(bsz, t, kh * dk),
                    qkvz[..., dk : 2 * dk].reshape(bsz, t, kh * dk),
                    qkvz[..., 2 * dk : 2 * dk + r * dv].reshape(bsz, t, vh * dv),
                ],
                axis=-1,
            )
            padded = jnp.pad(mixed, ((0, 0), (kw - 1, 0), (0, 0)))
            mixed = jax.nn.silu(sum(padded[:, j : j + t] * conv_w[j] for j in range(kw)))
        with _span("gdn.scan", chunk=c.chunk):
            get_registry().counter(
                "consensusml_gdn_chunks_total",
                "chunks of the gated delta rule traced (rows x chunks a call), by layer",
                labels={"layer": str(self.layer)},
            ).inc(bsz * -(-t // c.chunk))
            q = mixed[..., : kh * dk].reshape(bsz, t, kh, dk)
            k = mixed[..., kh * dk : 2 * kh * dk].reshape(bsz, t, kh, dk)
            v = mixed[..., 2 * kh * dk :].reshape(bsz, t, vh, dv).astype(c.dtype)
            unit = lambda y: y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
            q, k = (unit(q) * dk**-0.5).astype(c.dtype), unit(k).astype(c.dtype)
            impl = _scan_impl(c.chunk, dk, dv)
            get_registry().counter(
                "consensusml_gdn_scan_impl_total",
                "gated delta rules traced, by layer and by who schedules them: the fused kernel pair or XLA",
                labels={"layer": str(self.layer), "impl": "xla" if impl == "xla" else "kernel"},
            ).inc()
            if impl == "xla":
                o = gated_delta_chunked(
                    jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta, chunk=c.chunk)
            else:  # the kernels read a key head once for its r value heads
                o = gated_delta_scan(q, k, v, g, beta, chunk=c.chunk, interpret=impl == "interpret")
            out_rms = jnp.sqrt(jnp.mean(o * o, axis=(1, 3)))
        with _span("gdn.gate_norm"):
            # RMSNorm over each head's value_dim first, the gate second
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.norm_eps)
            y = (o * norm_w * jax.nn.silu(z.astype(f32))).astype(c.dtype)
        with _span("gdn.out_proj"):
            out = jnp.dot(
                y.reshape(bsz, t, vh * dv), w_out.astype(c.dtype), preferred_element_type=f32
            ).astype(c.dtype)
        return out, out_rms
