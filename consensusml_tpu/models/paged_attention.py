"""Fused pallas paged-attention for the serving hot path.

The paged decode step (and its k+1 spec-verify widening) previously ran
as three separate XLA ops with full HBM round-trips between them:
``gather_paged_kv`` materializes every slot's logical (S, T, H, D) KV
view in HBM, ``cached_attention`` reads it back, and the gathered view
is thrown away — the cost ledger attributes most of the decode step's
~6x-over-roofline gap to exactly that traffic. The kernels here do
block-table lookup + paged KV read + length-masked attention in ONE
VMEM-resident pass per layer: the block table rides in as a scalar-
prefetch operand (SMEM), each grid instance assembles its slot's KV
directly from the pool pages, and the gathered view never exists in HBM.

Bit-exactness is the contract, not a goal: every impl reproduces the
two-step gather path to the last bit (the PR 9 fused-wire playbook).
The kernel body mirrors the dense reference op-for-op — same bf16-in /
f32-accumulate dots with the same batch/contracting dims, same
``1/sqrt(d)`` f32 scale, same where-to-(-1e30) mask, same f32 softmax,
same probs-in-compute-dtype output matmul — so interpret mode, the
compiled TPU kernel, and the jnp reference are pinned against the
gather path across both model families (tests/test_fused_paged_attention.py).

Impl selection mirrors ``compress.kernels.resolve_codec_impl``:
``resolve_attention_impl("auto")`` is the KERNEL path — compiled pallas
on TPU, the pallas interpreter elsewhere — never silently the gather
reference. "gather"/"jnp" remain available as explicit requests (the
two-step baseline the parity tests use).

Status on the chip (PR 21; TPU v5 lite, jax 0.9.0, libtpu 0.0.34): the
COMPILED kernel is refused by Mosaic at every pool size — ``'tpu.matmul'
op Not implemented: Up to 1 batch dim supported`` — because the body's
rank-4 einsums batch over (slot, head) with the head axis not leading
(the form that is bit-identical to the reference on the CPU backend).
``impl="pallas"`` (what ``"auto"`` resolves to on a TPU) therefore
raises the compiler's error at warmup; it never gives way to the gather
path. Making it lower is a redesign (head-major rank-3 dots, and a pool
that is streamed rather than mapped whole: the full-array BlockSpecs
below put ``num_blocks * block_size * kv_heads * head_dim * 2`` bytes
per page array in VMEM, 16 MiB at 8 slots x 1024 tokens of GPT-2-medium
before lane padding) and is ROADMAP A1's to do. Interpreted, the kernel
is pinned against the gather path (tests/test_fused_paged_attention.py);
tests/test_kernels_tpu.py holds the strict xfail that must be removed
when it compiles.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from consensusml_tpu.models.attention import (
    cached_attention,
    cached_attention_window,
    gather_paged_kv,
)
from consensusml_tpu.pallas_util import interpret_arg, on_tpu, out_struct

__all__ = [
    "resolve_attention_impl",
    "fused_paged_attention",
    "fused_paged_attention_window",
    "ATTENTION_IMPLS",
]

_NEG_INF = -1e30

# "gather" and "jnp" are both the two-step reference composition (gather
# then dense attention) — "gather" is the serving default's name for it,
# "jnp" the parity suite's. "interpret"/"pallas" are the fused kernel.
ATTENTION_IMPLS = ("gather", "jnp", "interpret", "pallas")


def resolve_attention_impl(requested: str = "auto") -> str:
    """Resolve a serving-level attention impl request.

    ``auto`` is the KERNEL path: the compiled pallas kernel on TPU, the
    pallas interpreter elsewhere — never silently the gather reference
    (requesting the kernel tier and silently getting the two-step path
    would un-measure exactly what the floor-ratio gates watch). The
    gather baseline stays reachable, but only by asking for it by name.
    Callers should log the resolved impl loudly (the engine exposes it
    in ``stats()``; serve CLI prints one line).
    """
    if requested == "auto":
        return "pallas" if on_tpu() else "interpret"
    if requested not in ATTENTION_IMPLS:
        raise ValueError(
            f"unknown attention impl {requested!r} "
            f"(auto|{'|'.join(ATTENTION_IMPLS)})"
        )
    return requested


def _make_kernel(w: int, nb: int, rep: int):
    """One grid instance = one slot: gather the slot's pages from VMEM,
    run the dense-reference attention math on them.

    The body is deliberately NOT an online softmax: it replays the dense
    reference's exact op sequence (dot f32-accum -> scale -> where
    mask -> f32 softmax -> dtype-cast probs dot) with the same
    batch/contracting dimension numbers, which is what makes the fused
    output bit-identical to the gather path instead of merely close.
    """
    from jax.experimental import pallas as pl  # noqa: F401  (idiom anchor)

    def kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref):
        s = pl.program_id(0)
        q = q_ref[0]  # (W, H, D), compute dtype
        d = q.shape[-1]
        # in-VMEM gather: static loop over this slot's table row, one
        # dynamic leading-dim slice per block — the (S, T, H, D) view
        # the two-step path materializes in HBM never exists here
        ks = [k_ref[table_ref[s, j]] for j in range(nb)]  # (bs, Hkv, D)
        vs = [v_ref[table_ref[s, j]] for j in range(nb)]
        k = jnp.concatenate(ks, axis=0)  # (T, Hkv, D)
        v = jnp.concatenate(vs, axis=0)
        if rep != 1:  # GQA: expand on the read, pages stay pre-repeat
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        t = k.shape[0]
        # unit-slot rank-4 einsums with the reference's exact dimension
        # numbers (batch (b, h), contracting d / t): rank-3 dots give
        # 1-ulp f32 drift on the CPU backend, the unit-batch rank-4
        # form is bit-identical to the batched reference in every dtype
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
        logits = (
            jnp.einsum(
                "bshd,bthd->bhst", q[None], k[None],
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (1, H, W, T) f32
        # per-window-row length mask as a WHERE on the logits — the
        # reference's exact masking arithmetic (attention.py applies
        # padding masks with where, not an additive bias, so extreme
        # garbage in excluded trash-block keys cannot ride an additive
        # mask through; excluded columns contribute exactly zero)
        t_row = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
        keep = jnp.concatenate(
            [t_row <= pos_ref[s, i] for i in range(w)], axis=0
        )  # (W, T) bool
        logits = jnp.where(
            keep[None, None], logits, jnp.asarray(_NEG_INF, jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum(
            "bhst,bthd->bshd", probs.astype(o_ref.dtype), v[None],
            preferred_element_type=jnp.float32,
        )  # (1, W, H, D) f32
        o_ref[0] = out[0].astype(o_ref.dtype)

    return kernel


def _fused_call(
    q: jax.Array,  # (S, W, H, D)
    k_pages: jax.Array,  # (N, bs, Hkv, D)
    v_pages: jax.Array,
    block_table: jax.Array,  # (S, nb) int32
    positions: jax.Array,  # (S, W) int32 — last attendable position per row
    dtype: Any,
    interpret: bool,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, w, h, d = q.shape
    n, bs, hkv, _ = k_pages.shape
    nb = block_table.shape[1]
    if h % hkv:
        raise ValueError(
            f"query heads {h} not a multiple of kv heads {hkv}"
        )
    rep = h // hkv
    if positions.shape != (s, w):
        raise ValueError(
            f"positions must be {(s, w)} (one last-attendable index per "
            f"window row), got {positions.shape}"
        )
    pages_spec = pl.BlockSpec(
        (n, bs, hkv, d), lambda i, tbl, pos: (0, 0, 0, 0)
    )
    row_spec = pl.BlockSpec((1, w, h, d), lambda i, tbl, pos: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table + positions ride in SMEM
        grid=(s,),
        in_specs=[row_spec, pages_spec, pages_spec],
        out_specs=row_spec,
    )
    operands = (
        jnp.asarray(block_table, jnp.int32),
        jnp.asarray(positions, jnp.int32),
        q,
        k_pages,
        v_pages,
    )
    return pl.pallas_call(
        _make_kernel(w, nb, rep),
        # one distinct xprof family per window width (w1 = decode,
        # w{k+1} = spec verify), no '.' so the profiler's .N
        # duplicate-suffix folding can never merge them
        name=f"fused_paged_attn_w{w}",
        grid_spec=grid_spec,
        out_shape=out_struct((s, w, h, d), dtype, *operands),
        interpret=interpret_arg(interpret, *operands),
    )(*operands)


def fused_paged_attention(
    q: jax.Array,  # (S, 1, H, D) — the decode step's single query per slot
    k_pages: jax.Array,  # (N, bs, Hkv, D)
    v_pages: jax.Array,
    block_table: jax.Array,  # (S, nb)
    *,
    lengths: jax.Array,  # (S,) valid tokens per slot (write position + 1)
    dtype: Any = jnp.bfloat16,
    impl: str = "interpret",
) -> jax.Array:
    """Single-token paged decode attention, fused or two-step.

    ``impl`` "gather"/"jnp" run the reference composition —
    :func:`gather_paged_kv` + GQA repeat + :func:`cached_attention`,
    the exact ops the model blocks ran before the kernel tier existed;
    "interpret"/"pallas" run the fused kernel ("auto" resolves via
    :func:`resolve_attention_impl`). All impls are bit-identical.
    """
    impl = resolve_attention_impl(impl)
    if impl in ("gather", "jnp"):
        kg, vg = _gather_expanded(q, k_pages, v_pages, block_table)
        return cached_attention(q, kg, vg, lengths=lengths, dtype=dtype)
    # the decode mask `t < lengths` is the window mask `t <= lengths-1`
    pos = (jnp.asarray(lengths, jnp.int32) - 1)[:, None]
    return _fused_call(
        q, k_pages, v_pages, block_table, pos, dtype,
        interpret=impl == "interpret",
    )


def fused_paged_attention_window(
    q: jax.Array,  # (S, W, H, D) — the k+1 spec-verify window per slot
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,  # (S, cols) — trash-padded in spec mode
    *,
    positions: jax.Array,  # (S, W) absolute position of each query token
    dtype: Any = jnp.bfloat16,
    impl: str = "interpret",
) -> jax.Array:
    """W-token verify-window paged attention — :func:`fused_paged_attention`
    widened exactly like :func:`~consensusml_tpu.models.attention.
    cached_attention_window` widens the single-token mask: window row
    ``w`` attends cache rows ``<= positions[s, w]``, which encodes both
    in-window causality and the stale-garbage exclusion."""
    impl = resolve_attention_impl(impl)
    if impl in ("gather", "jnp"):
        kg, vg = _gather_expanded(q, k_pages, v_pages, block_table)
        return cached_attention_window(
            q, kg, vg, positions=positions, dtype=dtype
        )
    return _fused_call(
        q, k_pages, v_pages, block_table,
        jnp.asarray(positions, jnp.int32), dtype,
        interpret=impl == "interpret",
    )


def _gather_expanded(q, k_pages, v_pages, block_table):
    """The two-step path's gather + GQA expansion, verbatim."""
    kg, vg = gather_paged_kv(k_pages, v_pages, block_table)
    rep = q.shape[2] // k_pages.shape[2]
    if rep != 1:
        kg = jnp.repeat(kg, rep, axis=2)
        vg = jnp.repeat(vg, rep, axis=2)
    return kg, vg
