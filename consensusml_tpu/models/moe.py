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
the imbalance. What absent experts would add is left out; nothing stands
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

from consensusml_tpu.models.attention import (
    apply_rope,
    dot_product_attention,
    rope_frequencies,
)
from consensusml_tpu.models.losses import masked_lm_loss
from consensusml_tpu.obs import span as _span
from consensusml_tpu.pallas_util import on_tpu, varying

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
    dtype: Any = jnp.bfloat16


def route_top_k(
    scores: jax.Array, k: int, scale: float, bias: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """``scores`` (T, E) float32 sigmoid scores -> the ``k`` chosen experts
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


_GMM_ROWS = 256  # rows a tile: about a held expert's share of an 8k-token step


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_out(x, order, inv, k):
    """Row ``order[i] // k`` of ``x`` (T, H) for every sorted pair ``i``
    (``k`` pairs a token); the backward pass is a gather too, by ``inv``."""
    return x[order // k]


def _rows_out_fwd(x, order, inv, k):
    return x[order // k], inv


def _rows_out_bwd(k, inv, g):
    return g[inv].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@jax.custom_vjp
def _rows_back(y, order, inv):
    """Sorted rows back in (token, choice) order: a permutation, so both
    directions are gathers (XLA's own transpose would be a scatter)."""
    return y[inv]


def _rows_back_fwd(y, order, inv):
    return y[inv], order


def _rows_back_bwd(order, g):
    return g[order], None, None


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


def _relu2(x, dtype):
    return jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(dtype)


class HeldExpertsMLP(nn.Module):
    """``x (..., hidden) -> (y, counts)``: ``y = sum over the chosen experts
    held here of w_e f_e(x) + f_shared(x)`` with ``f(x) = W2 relu(W1 x)^2``;
    ``counts`` = ``{"rows": (held,) rows routed to each held expert,
    "absent_pairs": () (token, choice) pairs routed to experts elsewhere,
    "chosen": (T, k) the experts each token chose, of all ``experts``}``,
    int32 device values. The router is float32."""

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

        with _span("moe.route"):
            scores = jax.nn.sigmoid(
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
            xs = _rows_out(x2.astype(c.dtype), order, inv, k)
        with _span("moe.experts"):
            hid = _relu2(grouped_matmul(xs, w1.astype(c.dtype), rows), c.dtype)
            ys = grouped_matmul(hid, w2.astype(c.dtype), rows)
        with _span("moe.combine"):
            pairs = _rows_back(ys, order, inv).reshape(tokens, k, hdim)
            held_w = jnp.where(here.reshape(tokens, k), weights, 0.0)
            y = jnp.einsum("tk,tkh->th", held_w, pairs.astype(f32))
        if c.shared_width:
            sw1 = self.param("shared_w1", normal(0.02), (hdim, c.shared_width), f32)
            sw2 = self.param("shared_w2", normal(c.out_init_std), (c.shared_width, hdim), f32)
            with _span("moe.shared"):
                hid = jnp.dot(x2.astype(c.dtype), sw1.astype(c.dtype), preferred_element_type=f32)
                y = y + jnp.dot(
                    _relu2(hid, c.dtype), sw2.astype(c.dtype), preferred_element_type=f32
                )
        counts = {
            "rows": rows, "absent_pairs": jnp.int32(tokens * k) - jnp.sum(rows), "chosen": idx,
        }
        return y.astype(x.dtype).reshape(*lead, hdim), counts


def record_expert_counts(rows, absent, layers, held_start: int = 0) -> None:
    """Add one round's expert counters to the registry. ``rows`` (E blocks,
    held) and ``absent`` (E blocks,) are HOST arrays: the caller pops
    ``moe_rows`` / ``moe_absent_pairs`` off the round's ``metrics`` (arrays,
    not the scalars the logger prints) and fetches them with the loss.
    ``layers`` names the ``E`` blocks in the counters' order."""
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
