"""Mixture-of-Experts decoder with expert-parallel (EP) sharding support.

The reference's model zoo is dense-only (BASELINE.json configs; SURVEY.md
L5 — mount empty, no MoE evidence), but its decentralized-bandwidth story
(compress what rides the wire) extends naturally to sparse models, and EP
completes the framework's parallelism axes (gossip-DP x {TP, SP, EP}).

TPU-first routing design: capacity-based top-k dispatch with STATIC shapes
throughout — every token is routed via one-hot dispatch/combine tensors and
the expert FFN is one batched einsum over a leading expert axis ``(E, d,
f)``, so XLA tiles it onto the MXU and, when ``E`` is sharded over an
``ep`` mesh axis (:func:`consensusml_tpu.parallel.moe_ep_rules`), inserts
the dispatch all-to-alls itself. No sorting, no ragged buffers, no
host-side routing — the GShard/Switch recipe expressed as pure einsums.

:class:`HeldExpertsMLP` is the second expert layer, for the expert counts
open models use (128 experts, top-6): it is TOLD which experts it holds
(one chip's share of an expert-parallel deployment), routes over all of
them, sorts the tokens routed to its own experts by expert and multiplies
them group by group (:func:`grouped_matmul`), and drops no token whatever
the imbalance: its row buffers have room for every (token, choice) pair,
and on a TPU two Pallas kernels (:func:`moe_rows_gather`,
:func:`moe_rows_combine`) move only the rows that are live, whatever the
buffers' size. What absent experts would add is left out; nothing stands
in for the other chips or their all-to-all. ``MoEMLP``'s capacity dispatch
stays for the 8-expert recipe (folding it into the new layer: ROADMAP C).
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

from consensusml_tpu.models.attention import (
    apply_rope,
    dot_product_attention,
    rope_frequencies,
)
from consensusml_tpu.models.losses import masked_lm_loss
from consensusml_tpu.obs import span as _span
from consensusml_tpu.pallas_util import call_once, interpret_arg, on_tpu, out_struct, varying

__all__ = [
    "MoEConfig", "MoELM", "moe_tiny", "moe_loss_fn", "top_k_routing",
    "HeldExpertsConfig", "HeldExpertsMLP", "grouped_matmul", "route_top_k",
    "record_expert_counts",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    hidden: int = 1024
    layers: int = 8
    heads: int = 8
    mlp_dim: int = 4096
    n_experts: int = 8
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_every: int = 2  # every Nth block is MoE (GShard interleave); 1 = all
    max_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def moe_tiny(**overrides) -> "MoELM":
    """Test-scale MoE (same code path, tiny dims)."""
    defaults = dict(
        vocab_size=256,
        hidden=32,
        layers=2,
        heads=2,
        mlp_dim=64,
        n_experts=4,
        expert_top_k=2,
        moe_every=1,
        max_len=64,
    )
    defaults.update(overrides)
    return MoELM(config=MoEConfig(**defaults))


def top_k_routing(
    probs: jax.Array, k: int, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """Static-shape top-k token-choice routing with expert capacity.

    ``probs``: router softmax ``(B, S, E)`` (f32). Returns
    ``(dispatch, combine)``, both ``(B, S, E, C)``: ``dispatch`` is the 0/1
    token->(expert, slot) assignment, ``combine`` carries the (renormalized)
    gate weights. Assignment priority is slot-major — every token's first
    choice claims capacity before any second choice — and within a slot,
    sequence order (the deterministic GShard tie-break). Tokens overflowing
    an expert's capacity are dropped from that expert (their combine weight
    is zero), the standard capacity-factor contract.
    """
    b, s, e = probs.shape
    p = probs
    masks, gates = [], []
    for _ in range(k):
        idx = jnp.argmax(p, axis=-1)
        m = jax.nn.one_hot(idx, e, dtype=probs.dtype)  # (B, S, E)
        gates.append(jnp.sum(p * m, axis=-1))  # (B, S)
        masks.append(m)
        p = p * (1.0 - m)
    denom = sum(gates) + 1e-9  # renormalize the k kept gates per token
    pos, offset = [], jnp.zeros((b, 1, e), probs.dtype)
    for m in masks:
        pos.append(jnp.cumsum(m, axis=1) - m + offset)  # tokens ahead of me
        offset = offset + jnp.sum(m, axis=1, keepdims=True)
    dispatch = jnp.zeros((b, s, e, capacity), probs.dtype)
    combine = jnp.zeros((b, s, e, capacity), probs.dtype)
    for m, g, pp in zip(masks, gates, pos):
        keep = m * (pp < capacity)  # (B, S, E)
        slot = keep[..., None] * jax.nn.one_hot(
            pp.astype(jnp.int32), capacity, dtype=probs.dtype
        )  # (B, S, E, C)
        dispatch = dispatch + slot
        combine = combine + (g / denom)[..., None, None] * slot
    return dispatch, combine


class MoEMLP(nn.Module):
    """Top-k routed expert FFN; returns ``(y, aux_loss)``.

    Expert weights are stacked on a leading expert axis — ``wi (E, d, f)``,
    ``wo (E, f, d)`` — the layout :func:`~consensusml_tpu.parallel.
    moe_ep_rules` shards over the ``ep`` mesh axis. Router runs in f32.
    ``aux_loss`` is the Switch/GShard load-balance term: ``E * sum_e
    (token_fraction_e * mean_router_prob_e)`` — 1.0 at perfect balance.
    """

    config: MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        c = self.config
        b, s, d = x.shape
        e, k = c.n_experts, c.expert_top_k
        capacity = max(1, int(-(-s * k * c.capacity_factor // e)))
        logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
            name="router",
        )(jnp.asarray(x, jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)  # (B, S, E)
        dispatch, combine = top_k_routing(probs, k, capacity)

        me = jnp.mean(probs, axis=(0, 1))  # mean router prob per expert
        ce = jnp.mean(jnp.sum(dispatch, axis=-1), axis=(0, 1)) / k  # tok frac
        aux = e * jnp.sum(me * ce)

        wi = self.param(
            "wi", nn.initializers.lecun_normal(), (e, d, c.mlp_dim), jnp.float32
        )
        wo = self.param(
            "wo", nn.initializers.lecun_normal(), (e, c.mlp_dim, d), jnp.float32
        )
        xin = jnp.einsum(
            "bsec,bsd->ebcd", dispatch.astype(c.dtype), jnp.asarray(x, c.dtype)
        )
        h = nn.gelu(
            jnp.einsum(
                "ebcd,edf->ebcf", xin, wi.astype(c.dtype),
                preferred_element_type=jnp.float32,
            ).astype(c.dtype)
        )
        out = jnp.einsum(
            "ebcf,efd->ebcd", h, wo.astype(c.dtype),
            preferred_element_type=jnp.float32,
        )
        y = jnp.einsum("bsec,ebcd->bsd", combine.astype(jnp.float32), out)
        return y.astype(x.dtype), aux


class _MoEBlock(nn.Module):
    config: MoEConfig
    use_moe: bool

    @nn.compact
    def __call__(self, x, rope_table):
        c = self.config
        d = c.head_dim
        y = nn.LayerNorm(epsilon=c.norm_eps, dtype=jnp.float32, name="attn_norm")(x)
        y = jnp.asarray(y, c.dtype)
        b, s, _ = y.shape
        qkv = nn.Dense(3 * c.heads * d, use_bias=False, dtype=c.dtype, name="qkv")(y)
        q, k, v = jnp.split(qkv.reshape(b, s, c.heads, 3 * d), 3, axis=-1)
        q = apply_rope(q, rope_table)
        k = apply_rope(k, rope_table)
        attn = dot_product_attention(q, k, v, causal=True, dtype=c.dtype)
        x = x + nn.Dense(c.hidden, use_bias=False, dtype=c.dtype, name="out")(
            attn.reshape(b, s, c.heads * d)
        )
        y = nn.LayerNorm(epsilon=c.norm_eps, dtype=jnp.float32, name="mlp_norm")(x)
        y = jnp.asarray(y, c.dtype)
        if self.use_moe:
            y, aux = MoEMLP(c, name="moe")(y)
        else:
            h = nn.gelu(nn.Dense(c.mlp_dim, dtype=c.dtype, name="mlp_in")(y))
            y = nn.Dense(c.hidden, dtype=c.dtype, name="mlp_out")(h)
            aux = jnp.zeros((), jnp.float32)
        return x + y, aux


class MoELM(nn.Module):
    """Decoder-only LM with interleaved MoE blocks.

    ``apply`` returns ``(logits (B, S, V) f32, aux_loss scalar f32)`` —
    ``aux_loss`` is the mean load-balance loss over MoE blocks, to be added
    to the task loss with weight ``config.router_aux_weight`` (done by
    :func:`moe_loss_fn`).
    """

    config: MoEConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> tuple[jax.Array, jax.Array]:
        c = self.config
        x = nn.Embed(c.vocab_size, c.hidden, dtype=c.dtype, name="tok_emb")(input_ids)
        rope_table = rope_frequencies(c.head_dim, c.max_len, c.rope_theta)
        aux_total, n_moe = jnp.zeros((), jnp.float32), 0
        for i in range(c.layers):
            use_moe = (i % c.moe_every) == (c.moe_every - 1)
            x, aux = _MoEBlock(c, use_moe, name=f"layer_{i}")(x, rope_table)
            aux_total, n_moe = aux_total + aux, n_moe + int(use_moe)
        x = nn.LayerNorm(epsilon=c.norm_eps, dtype=jnp.float32, name="final_norm")(x)
        logits = nn.Dense(
            c.vocab_size, use_bias=False, dtype=c.dtype, name="lm_head"
        )(jnp.asarray(x, c.dtype))
        return jnp.asarray(logits, jnp.float32), aux_total / max(n_moe, 1)


def moe_loss_fn(model: MoELM):
    """Causal LM loss + weighted router load-balance aux loss."""

    def loss_fn(params, model_state, batch, rng):
        ids = batch["input_ids"]
        logits, aux = model.apply({"params": params}, ids)
        mask = batch.get("loss_mask")
        mask = jnp.ones_like(ids[:, 1:], jnp.float32) if mask is None else mask[:, 1:]
        lm = masked_lm_loss(logits[:, :-1], ids[:, 1:], mask)
        return lm + model.config.router_aux_weight * aux, model_state

    return loss_fn


# ---------------------------------------------------------------------------
# the share-aware expert layer: sorted tokens, grouped products, no drops
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeldExpertsConfig:
    hidden: int = 2688
    experts: int = 128  # the router's width: every routed expert of the layer
    held: int = 128  # how many of them live here ...
    held_start: int = 0  # ... from this one on (rank * held)
    top_k: int = 6
    route_scale: float = 2.5
    expert_width: int = 1856
    shared_width: int = 3712  # 0 = no shared expert
    out_init_std: float = 0.02  # the caller scales it by 1/sqrt(2 * depth)
    # the published score-correction bias, added to the scores for the CHOICE
    # alone: "zeros" (the buffer as the source's code creates it), or
    # "centred": minus each expert's mean score over the step's tokens, which
    # is about where the family's load-balancing update holds it (it takes
    # away what makes an expert every token's favourite); no gradient
    score_correction: str = "zeros"
    # what DESCRIBES an architecture's expert layer (the defaults: the
    # nemotron_h family's): the router's scores, "sigmoid" or "softmax" over
    # all ``experts``; the experts' (and the shared expert's) activation,
    # "relu2" = W2 relu(W1 x)^2, two matrices, or "swiglu" = W2 (silu(W1 x) *
    # W3 x), three; a scalar sigmoid gate ``sigmoid(x . w)`` on the shared expert
    scores: str = "sigmoid"
    activation: str = "relu2"
    shared_gate: bool = False
    dtype: Any = jnp.bfloat16


def route_top_k(
    scores: jax.Array, k: int, scale: float, bias: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """``scores`` (T, E) float32 scores (sigmoid or softmax) -> the ``k`` chosen experts
    (T, k), those with the largest ``scores + bias`` (``bias`` (E,): the
    published score-correction bias, for the choice alone), and their
    weights, from the scores themselves, normalised over the CHOSEN experts
    (held here or not) and scaled."""
    if bias is None:
        picked, idx = jax.lax.top_k(scores, k)
    else:
        idx = jax.lax.top_k(scores + bias, k)[1]
        picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * scale
    return idx, weights


def _tile(dim: int) -> int:
    """The largest multiple of 128 up to 1024 that divides ``dim``, else 512
    (the kernel masks a ragged last tile)."""
    return max((t for t in range(128, 1025, 128) if dim % t == 0), default=512)


# rows a tile: about a held LARGE expert's share of an 8k-token step (8 of 128 held: 384 rows); with
# many small experts (32 of 512 held: 160 rows) a group is smaller than a tile and the product's
# tiles run ~40% full (``gmm_visited_tiles``; PERF.md section 5)
_GMM_ROWS = 256


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    return _GMM_ROWS, _tile(k), _tile(n)


def _kernels():
    """megablox's two kernels WITHOUT their ``jax.jit`` wrappers: a jitted
    function's name is a scope of its own, and a kernel's device op is named
    after the innermost scope — here it has to be ours."""
    import importlib

    # the package rebinds the name ``gmm`` to its differentiable wrapper
    backend = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
    return backend.gmm.__wrapped__, backend.tgmm.__wrapped__


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, sizes, interpret):
    """``sizes`` has one entry more than ``rhs`` has matrices: the rows that
    belong to no matrix here. The kernel skips their tiles and zeroes them."""
    with jax.named_scope("moe_gmm"):
        return _kernels()[0](lhs, rhs, sizes, lhs.dtype, _gmm_tiling, interpret=interpret)


def _gmm_fwd(lhs, rhs, sizes, interpret):
    return _gmm(lhs, rhs, sizes, interpret), (lhs, rhs, sizes)


def _gmm_bwd(interpret, res, g):
    lhs, rhs, sizes = res
    gmm, tgmm = _kernels()
    with jax.named_scope("moe_gmm_dlhs"):
        d_lhs = gmm(
            g, rhs, sizes, lhs.dtype, _gmm_tiling, transpose_rhs=True, interpret=interpret
        )
    with jax.named_scope("moe_gmm_drhs"):
        d_rhs = tgmm(
            lhs.swapaxes(0, 1), g, sizes, rhs.dtype, _gmm_tiling,
            num_actual_groups=rhs.shape[0], interpret=interpret,
        )
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, impl: str = "auto"
) -> jax.Array:
    """``lhs`` (m, k) holds rows sorted by group, ``rhs`` (g, k, n) one matrix
    a group, ``group_sizes`` (g,) int32: rows of group ``i`` times ``rhs[i]``,
    (m, n) in ``lhs``'s dtype. Rows past the groups' total come out zero.

    ``impl``: ``"auto"`` is, on a TPU, the Pallas grouped product (megablox
    ``gmm``; its backward ``gmm`` + ``tgmm``) under the scopes ``moe_gmm``,
    ``moe_gmm_dlhs`` and ``moe_gmm_drhs``, and elsewhere ``lax.ragged_dot``.
    Two paths, because each backend of the train step refuses one of them on
    a TPU: megablox builds its own ``out_shape`` without ``vma`` and cannot
    be traced inside a checked ``shard_map`` (the collective backend), and
    the TPU compiler takes no batched ragged product, which is what the
    stacked backend's ``vmap`` makes of ``ragged_dot``; there XLA's own
    grouped kernel is also the slower one (forward + backward at 49,152 x
    2688 x 1856 x 8, 3,072 live rows: 16.8 ms against megablox's 5.8,
    PERF.md). ``"interpret"`` runs the kernels interpreted (the tests)."""
    m = lhs.shape[0]
    total = jnp.sum(group_sizes)
    kernels = impl == "auto" and on_tpu() and not varying(lhs, rhs, group_sizes)
    if impl == "interpret" or kernels:
        pad = (-m) % _GMM_ROWS
        padded = jnp.pad(lhs, ((0, pad), (0, 0))) if pad else lhs
        sizes = jnp.concatenate([group_sizes, (m + pad - total)[None]]).astype(jnp.int32)
        out = _gmm(padded, rhs, sizes, impl == "interpret")
        return out[:m] if pad else out
    # rows past the groups' total: ragged_dot leaves them undefined, in the
    # product and in the cotangent it hands back, so they are masked both ways
    live = (jnp.arange(m) < total)[:, None]
    with jax.named_scope("moe_gmm"):
        out = jax.lax.ragged_dot(jnp.where(live, lhs, 0), rhs, group_sizes.astype(jnp.int32))
    return jnp.where(live, out, 0).astype(lhs.dtype)


# -- the row movement around the grouped products ---------------------------------
#
# The row buffers keep their worst-case shapes (every pair may land here: no
# drop), but only ``total`` sorted rows are live (one chip of 16 holds 6% of
# them). Off a TPU the movement is XLA's gathers over the whole buffers, the
# definition the kernels are held to; on a TPU two Pallas kernels take the
# sorted order and the live count as prefetched scalars and touch live rows
# only, each also the other's backward pass:
#
# - ``moe_rows_gather``: grid over tiles of ``_GMM_ROWS`` sorted rows, a tile
#   wholly past ``total`` skipped (its index maps point at the last live tile,
#   so nothing is fetched or written for it and it stays UNWRITTEN: every
#   reader of the sorted rows skips or masks rows past ``total``); the dead
#   rows of the boundary tile are zeroed;
# - ``moe_rows_combine``: grid over tiles of tokens; a pair whose expert is
#   absent fetches nothing.
#
# A row is fetched by a DMA from the array left in HBM. Mosaic slices a tiled
# array only along whole tiles (8 rows of 32 bits, 16 of bfloat16), so the DMA
# brings the row's aligned GROUP into a ring of slots and the row is read out
# of it at a dynamic sublane; a bfloat16 row is half of a 32-bit sublane and
# is taken apart with shifts.

_ROW_SLOTS = 16  # DMAs in flight; 8, 16 and 32 read the same on the chip (PERF.md section 6, PR 28)
_TOKEN_TILE = 256  # tokens a step of the combine's grid: its float32 accumulator is 2.75 MB of VMEM
_TRACED: dict = {}  # pallas_util.call_once keeps each kernel's one trace here


def _rows_impl() -> str:
    """Observed, never chosen: the kernels on a TPU (inside a checked
    ``shard_map`` too: they are the repo's own and say where their outputs
    vary), XLA's gathers elsewhere. The tests make it ``"interpret"``."""
    return "pallas" if on_tpu() else "xla"


def _row_group(dtype) -> int:
    size = jnp.dtype(dtype).itemsize
    if size not in (2, 4):
        raise ValueError(f"rows of {dtype} are neither 16 nor 32 bits wide")
    return 32 // size


def _pad_rows(x, multiple):
    pad = (-x.shape[0]) % multiple
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


def _ring_rows(src_ref, ring, sem, count, position, use):
    """For ``c`` in ``[0, count)``: row ``position(c)`` of ``src_ref`` (in HBM)
    as a (1, H) float32 value, handed to ``use(c, row)``; ``_ROW_SLOTS`` DMAs
    of aligned groups stay in flight."""
    group = ring.shape[1]

    def copy(c, slot):
        start = pl.multiple_of(position(c) // group * group, group)
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(start, group)], ring.at[slot], sem.at[slot]
        )

    for c in range(_ROW_SLOTS):
        @pl.when(c < count)
        def _():
            copy(c, c).start()

    def step(c, carry):
        slot = c % _ROW_SLOTS
        copy(c, slot).wait()
        sub = position(c) % group
        if ring.dtype.itemsize == 4:
            row = ring[slot, pl.ds(sub, 1), :].astype(jnp.float32)
        else:  # sublane s of a bfloat16 tile holds rows 2s (low half) and 2s + 1
            words = ring.at[slot].bitcast(jnp.uint32)[pl.ds(sub // 2, 1), :]
            shift = jnp.full(words.shape, (sub % 2) * 16, jnp.uint32)
            row = jax.lax.bitcast_convert_type((words >> shift) << 16, jnp.float32)
        use(c, row)

        @pl.when(c + _ROW_SLOTS < count)
        def _():
            copy(c + _ROW_SLOTS, slot).start()

        return carry

    jax.lax.fori_loop(0, count, step, None)


def _gather_kernel(k, scaled, dotted, order_ref, total_ref, src_ref, *refs):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    mate_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    stage, ring, sem = refs
    tile = out_ref.shape[0]
    base = pl.program_id(0) * tile
    live = jnp.clip(total_ref[0] - base, 0, tile)

    @pl.when(live > 0)
    def _():
        def place(r, row):
            stage[pl.ds(r, 1), :] = row

        _ring_rows(src_ref, ring, sem, live, lambda r: order_ref[base + r] // k, place)
        alive = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < live
        rows = jnp.where(alive, stage[...], 0.0)  # past ``live``: an earlier tile's
        scaled_rows = rows * scale_ref[...] if scaled else rows
        out_ref[...] = scaled_rows.astype(out_ref.dtype)
        if dotted:
            dot_ref[...] = jnp.sum(
                rows * mate_ref[...].astype(jnp.float32), axis=1, keepdims=True
            )


def moe_rows_gather(src, order, total, k, *, scale=None, mate=None, dtype=None, interpret=False):
    """Sorted rows out of a token-major array: ``out[i] = scale[i] *
    src[order[i] // k]`` for ``i < total`` (the product in float32),
    (len(order), H) in ``dtype`` (default ``src``'s). Rows past ``total`` are
    zero up to the next multiple of ``_GMM_ROWS`` and UNWRITTEN beyond it.
    With ``mate`` (len(order), H) also ``dots[i] = <src[order[i] // k],
    mate[i]>`` in float32 (the unscaled rows), ``(out, dots)``."""
    dtype = src.dtype if dtype is None else dtype
    n, width = order.shape[0], src.shape[1]
    tile, group = _GMM_ROWS, _row_group(src.dtype)
    tiles = -(-n // tile)

    def last_live(total_ref):  # a dead tile's blocks are the last live tile's: nothing moves
        return jnp.maximum((total_ref[0] + tile - 1) // tile - 1, 0)

    def rows_of(cols):
        return pl.BlockSpec(
            (tile, cols), lambda i, order_ref, total_ref: (jnp.minimum(i, last_live(total_ref)), 0)
        )

    operands = [
        _pad_rows(order.astype(jnp.int32), tile),
        jnp.reshape(total, (1,)).astype(jnp.int32),
        _pad_rows(src, group),
    ]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    if scale is not None:
        operands.append(_pad_rows(scale.astype(jnp.float32)[:, None], tile))
        in_specs.append(rows_of(1))
    if mate is not None:
        operands.append(_pad_rows(mate, tile))
        in_specs.append(rows_of(width))
    out_shape = [out_struct((tiles * tile, width), dtype, *operands)]
    out_specs = [rows_of(width)]
    if mate is not None:
        out_shape.append(out_struct((tiles * tile, 1), jnp.float32, *operands))
        out_specs.append(rows_of(1))
    call = pl.pallas_call(
        functools.partial(_gather_kernel, k, scale is not None, mate is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((tile, width), jnp.float32),
                pltpu.VMEM((_ROW_SLOTS, group, width), src.dtype),
                pltpu.SemaphoreType.DMA((_ROW_SLOTS,)),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret_arg(interpret, *operands),
    )
    with jax.named_scope("moe_rows_gather"):
        outs = call_once(_TRACED, ("gather", k, tile, dtype, interpret), call, operands)
    out = outs[0][:n]
    return out if mate is None else (out, outs[1][:n, 0])


def _combine_kernel(k, weighted, inv_ref, total_ref, *refs):
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    rows_ref, out_ref, acc, queue, ring, sem = refs
    pairs = out_ref.shape[0] * k
    base = pl.program_id(0) * pairs
    total = total_ref[0]

    def scan(step, n):  # the tile's live pairs, in (token, choice) order
        for p in range(8):  # unrolled by hand: Mosaic takes no ``unroll=8``
            queue[n] = step * 8 + p
            n = n + (inv_ref[base + step * 8 + p] < total).astype(jnp.int32)
        return n

    count = jax.lax.fori_loop(0, pairs // 8, scan, 0)
    acc[...] = jnp.zeros_like(acc)

    def add(c, row):
        p = queue[c]
        if weighted:
            row = row * w_ref[base + p]
        acc[pl.ds(p // k, 1), :] += row

    _ring_rows(rows_ref, ring, sem, count, lambda c: inv_ref[base + queue[c]], add)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def moe_rows_combine(rows, inv, total, k, *, weights=None, dtype=None, interpret=False):
    """Per token the weighted sum of its LIVE sorted rows: ``y[t] = sum_j
    weights[t, j] * rows[inv[t * k + j]]`` over the pairs with ``inv[t * k +
    j] < total`` alone (``weights`` None: ones), accumulated in float32,
    (len(inv) // k, H) in ``dtype`` (default ``rows``'s). A pair whose expert
    is absent fetches nothing, whatever its weight."""
    dtype = rows.dtype if dtype is None else dtype
    tokens, width = inv.shape[0] // k, rows.shape[1]
    tile = min(_TOKEN_TILE, -(-tokens // 8) * 8)
    tiles, group = -(-tokens // tile), _row_group(rows.dtype)
    far = jnp.iinfo(jnp.int32).max  # a padded token's pairs are dead
    flat = lambda x, fill: jnp.pad(x.reshape(-1), (0, (tiles * tile - tokens) * k), constant_values=fill)
    operands = [flat(inv.astype(jnp.int32), far), jnp.reshape(total, (1,)).astype(jnp.int32)]
    if weights is not None:
        operands.append(flat(weights.astype(jnp.float32), 0.0))
    scalars = len(operands)
    operands.append(_pad_rows(rows, group))
    call = pl.pallas_call(
        functools.partial(_combine_kernel, k, weights is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=scalars,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, width), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile, width), jnp.float32),
                pltpu.SMEM((tile * k,), jnp.int32),
                pltpu.VMEM((_ROW_SLOTS, group, width), rows.dtype),
                pltpu.SemaphoreType.DMA((_ROW_SLOTS,)),
            ],
        ),
        out_shape=out_struct((tiles * tile, width), dtype, *operands),
        interpret=interpret_arg(interpret, *operands),
    )
    with jax.named_scope("moe_rows_combine"):
        (out,) = call_once(_TRACED, ("combine", k, tile, dtype, interpret), call, operands)
    return out[:tokens]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_out(x, order, inv, k):
    """Off a TPU: row ``order[i] // k`` of ``x`` (T, H) for every sorted pair
    ``i`` (``k`` pairs a token), all ``T * k`` of them; the backward pass is
    a gather too, by ``inv``. On one: :func:`_live_rows_out`."""
    return x[order // k]


def _rows_out_fwd(x, order, inv, k):
    return x[order // k], inv


def _rows_out_bwd(k, inv, g):
    return g[inv].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@jax.custom_vjp
def _rows_back(y, order, inv):
    """Off a TPU: sorted rows back in (token, choice) order, a permutation of
    the whole buffer, so both directions are gathers (XLA's own transpose
    would be a scatter). On one: :func:`_live_rows_combine`, which never
    builds the (T, k, H) tensor."""
    return y[inv]


def _rows_back_fwd(y, order, inv):
    return y[inv], order


def _rows_back_bwd(order, g):
    return g[order], None, None


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _live_rows_out(x, order, inv, total, k, interpret):
    """:func:`_rows_out` through the kernels: the live rows alone, and its
    backward pass is the other kernel (each token sums its live rows)."""
    return moe_rows_gather(x, order, total, k, interpret=interpret)


def _live_rows_out_fwd(x, order, inv, total, k, interpret):
    return _live_rows_out(x, order, inv, total, k, interpret), (inv, total)


def _live_rows_out_bwd(k, interpret, res, g):
    inv, total = res
    return moe_rows_combine(g, inv, total, k, interpret=interpret), None, None, None


_live_rows_out.defvjp(_live_rows_out_fwd, _live_rows_out_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _live_rows_combine(ys, held_w, order, inv, total, interpret):
    """``y[t] = sum_j held_w[t, j] * ys[inv[t * k + j]]`` (T, H) float32 over
    the live pairs; the (T, k, H) float32 tensor is never built. Backward,
    ONE gather makes both cotangents: ``d_ys[i] = held_w[order[i]] *
    dy[order[i] // k]`` and, from the same gathered rows, ``<dy[order[i] //
    k], ys[i]>``, which is ``d_held_w`` at pair ``order[i]``."""
    return moe_rows_combine(
        ys, inv, total, held_w.shape[1], weights=held_w, dtype=jnp.float32, interpret=interpret
    )


def _live_rows_combine_fwd(ys, held_w, order, inv, total, interpret):
    y = _live_rows_combine(ys, held_w, order, inv, total, interpret)
    return y, (ys, held_w, order, inv, total)


def _live_rows_combine_bwd(interpret, res, dy):
    ys, held_w, order, inv, total = res
    d_ys, dots = moe_rows_gather(
        dy, order, total, held_w.shape[1], scale=held_w.reshape(-1)[order], mate=ys,
        dtype=ys.dtype, interpret=interpret,
    )
    d_held_w = jnp.where(inv < total, dots[jnp.minimum(inv, dots.shape[0] - 1)], 0.0)
    return d_ys, d_held_w.reshape(held_w.shape), None, None, None


_live_rows_combine.defvjp(_live_rows_combine_fwd, _live_rows_combine_bwd)


def _relu2(x, dtype):
    return jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(dtype)


def _swiglu(gate, up, dtype):
    return (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(dtype)


class HeldExpertsMLP(nn.Module):
    """``x (..., hidden) -> (y, counts)``: ``y = sum over the chosen experts
    held here of w_e f_e(x) + f_shared(x)`` with ``f(x) = W2 relu(W1 x)^2``
    or, ``config.activation`` ``"swiglu"``, ``W2 (silu(W1 x) * W3 x)`` (a third
    stacked matrix through the same grouped product), the shared expert then
    optionally behind a scalar gate ``sigmoid(x . w)`` (``config.shared_gate``);
    ``counts`` = ``{"rows": (held,) rows routed to each held expert,
    "absent_pairs": () (token, choice) pairs routed to experts elsewhere,
    "chosen": (T, k) the experts each token chose, of all ``experts``}``,
    int32 device values. The router is float32.

    The sorted buffers ``xs`` and ``ys`` hold ``T * k`` rows, one for every
    pair, so none is ever dropped; the first ``sum(rows)`` are live. Off a TPU
    the rows move by XLA's gathers over the whole buffers (:func:`_rows_out`,
    :func:`_rows_back`); on one by the row kernels, which skip what is not
    live: rows of ``xs`` past the live ones are then zero up to the next
    tile and unwritten beyond it, which the grouped products never read."""

    config: HeldExpertsConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> tuple[jax.Array, dict]:
        c = self.config
        f32 = jnp.float32
        normal = nn.initializers.normal
        lead, hdim = x.shape[:-1], x.shape[-1]
        x2 = x.reshape(-1, hdim)
        tokens, k = x2.shape[0], c.top_k
        w_r = self.param("router", normal(0.02), (hdim, c.experts), f32)
        w1 = self.param("w1", normal(0.02), (c.held, hdim, c.expert_width), f32)
        w2 = self.param("w2", normal(c.out_init_std), (c.held, c.expert_width, hdim), f32)
        if c.activation not in ("relu2", "swiglu") or c.scores not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown activation {c.activation!r} or scores {c.scores!r}")
        gated = c.activation == "swiglu"
        if gated:
            w3 = self.param("w3", normal(0.02), (c.held, hdim, c.expert_width), f32)

        with _span("moe.route"):
            squash = jax.nn.sigmoid if c.scores == "sigmoid" else jax.nn.softmax
            scores = squash(
                jnp.dot(x2.astype(f32), w_r, precision=jax.lax.Precision.HIGHEST)
            )
            bias = None
            if c.score_correction == "centred":
                bias = -jax.lax.stop_gradient(jnp.mean(scores, axis=0))
            elif c.score_correction != "zeros":
                raise ValueError(f"unknown score_correction {c.score_correction!r}")
            idx, weights = route_top_k(scores, k, c.route_scale, bias)
        with _span("moe.sort"):
            local = idx.reshape(-1) - c.held_start
            here = (local >= 0) & (local < c.held)
            key = jnp.where(here, local, c.held)  # pairs for absent experts sort last
            order = jnp.argsort(key, stable=True)  # sorted position -> (token, choice) pair
            inv = jnp.argsort(order)
            rows = jnp.sum(
                key[:, None] == jnp.arange(c.held, dtype=key.dtype)[None, :], axis=0,
                dtype=jnp.int32,
            )
            kernels = _rows_impl()
            if kernels == "xla":
                xs = _rows_out(x2.astype(c.dtype), order, inv, k)
            else:
                total = jnp.sum(rows)  # the live rows: the sorted buffers' first
                xs = _live_rows_out(x2.astype(c.dtype), order, inv, total, k, kernels == "interpret")
        with _span("moe.experts"):
            hid = grouped_matmul(xs, w1.astype(c.dtype), rows)
            if gated:
                hid = _swiglu(hid, grouped_matmul(xs, w3.astype(c.dtype), rows), c.dtype)
            else:
                hid = _relu2(hid, c.dtype)
            ys = grouped_matmul(hid, w2.astype(c.dtype), rows)
        with _span("moe.combine"):
            if kernels == "xla":  # in the order it always had: the same program, byte for byte
                pairs = _rows_back(ys, order, inv).reshape(tokens, k, hdim)
            held_w = jnp.where(here.reshape(tokens, k), weights, 0.0)
            if kernels == "xla":
                y = jnp.einsum("tk,tkh->th", held_w, pairs.astype(f32))
            else:
                y = _live_rows_combine(ys, held_w, order, inv, total, kernels == "interpret")
        if c.shared_width:
            sw1 = self.param("shared_w1", normal(0.02), (hdim, c.shared_width), f32)
            sw2 = self.param("shared_w2", normal(c.out_init_std), (c.shared_width, hdim), f32)
            if gated:
                sw3 = self.param("shared_w3", normal(0.02), (hdim, c.shared_width), f32)
            if c.shared_gate:
                gate_w = self.param("shared_gate", normal(0.02), (hdim,), f32)
            with _span("moe.shared"):
                hid = jnp.dot(x2.astype(c.dtype), sw1.astype(c.dtype), preferred_element_type=f32)
                if gated:
                    up = jnp.dot(x2.astype(c.dtype), sw3.astype(c.dtype), preferred_element_type=f32)
                    hid = _swiglu(hid, up, c.dtype)
                else:
                    hid = _relu2(hid, c.dtype)
                shared = jnp.dot(hid, sw2.astype(c.dtype), preferred_element_type=f32)
                if c.shared_gate:
                    shared = shared * jax.nn.sigmoid(
                        jnp.dot(x2.astype(f32), gate_w, precision=jax.lax.Precision.HIGHEST)
                    )[:, None]
                y = y + shared
        counts = {
            "rows": rows, "absent_pairs": jnp.int32(tokens * k) - jnp.sum(rows), "chosen": idx,
        }
        return y.astype(x.dtype).reshape(*lead, hdim), counts


def gmm_visited_tiles(rows_per_group, tile: int = _GMM_ROWS) -> int:
    """How many (group, row tile) pairs a grouped product over sorted rows
    visits: group ``i``'s ``rows_per_group[i]`` rows follow group ``i - 1``'s,
    and it meets every tile of ``tile`` rows that holds one of them. A group
    smaller than a tile still costs a whole pass over it: live rows over
    ``visited * tile`` is how full the product's tiles are."""
    visited, start = 0, 0.0
    for n in rows_per_group:
        if n > 0:
            visited += -int(-(start + n) // tile) - int(start // tile)
        start += n
    return visited


def record_expert_counts(rows, absent, layers, held_start: int = 0, calls: int = 1) -> None:
    """Add one round's expert counters to the registry. ``rows`` (E blocks,
    held) and ``absent`` (E blocks,) are HOST arrays: the caller pops
    ``moe_rows`` / ``moe_absent_pairs`` off the round's ``metrics`` (arrays,
    not the scalars the logger prints) and fetches them with the loss.
    ``layers`` names the ``E`` blocks in the counters' order; ``calls``: how
    many calls of a layer the counts sum over (inner steps x workers), for
    the row kernels' tiles: of a call's ``tokens * top_k`` buffer rows in tiles
    of ``_GMM_ROWS``, those that hold a live row and those the kernels skip,
    and for the (group, row tile) pairs the grouped product visits, both as
    if the round's calls carried the same load."""
    from consensusml_tpu.obs import get_registry

    registry = get_registry()
    for layer, per_expert, elsewhere in zip(layers, rows, absent):
        for e, n in enumerate(per_expert):
            registry.counter(
                "consensusml_moe_rows_total",
                "rows (token, choice pairs) routed to each expert held here",
                labels={"layer": str(layer), "expert": str(held_start + e)},
            ).inc(int(n))
        registry.counter(
            "consensusml_moe_absent_pairs_total",
            "(token, choice) pairs routed to experts held on other chips",
            labels={"layer": str(layer)},
        ).inc(int(elsewhere))
        registry.counter(
            "consensusml_moe_gmm_tiles_total",
            "(group, row tile) pairs the grouped product visits: a held expert's rows, "
            "sorted one expert after another, by the tiles of _GMM_ROWS rows they lie in",
            labels={"layer": str(layer), "kind": "visited"},
        ).inc(calls * gmm_visited_tiles([int(n) / calls for n in per_expert]))
        held_rows = sum(int(n) for n in per_expert)
        tiles = calls * -(-(held_rows + int(elsewhere)) // (calls * _GMM_ROWS))
        live = calls * -(-held_rows // (calls * _GMM_ROWS))
        for kind, n in (("live", live), ("skipped", tiles - live)):
            registry.counter(
                "consensusml_moe_row_tiles_total",
                "tiles of the expert layer's worst-case row buffers that hold a live row "
                "(moved by moe_rows_gather) and that the kernel skips",
                labels={"layer": str(layer), "kind": kind},
            ).inc(n)
