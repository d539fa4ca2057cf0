"""Disaggregated prefill/decode stages over the paged block pool.

The PR 5 engine ran ONE fused step family per admission pattern: when
several requests arrived together, `_admit_waiting` prefilled every free
slot back-to-back before the next decode step, so one long prompt — or a
burst of them — stalled every in-flight stream (inter-token latency
spikes exactly when traffic peaks). This module splits the two phases
into separately-jitted, separately-scheduled stages:

- :func:`make_paged_prefill_fn` — one executable per prompt bucket, full
  causal forward, K/V scattered into the slot's OWNED pool blocks (pad
  blocks beyond the owned prefix land in the trash block);
- :func:`make_paged_decode_fn` — ONE executable for all slots at every
  occupancy/length mix, block indices computed inside the jit from the
  block table (no host sync, no recompile — contract-pinned per stage by
  ``analysis/jaxpr_contracts.py``);
- :class:`AdmissionScheduler` — the host-side policy between them: every
  engine tick runs AT MOST ``prefill_budget`` tokens of prefill, and the
  decode step runs every tick regardless, so decode never waits behind
  more than one budget's worth of prefill. (On one host the stages share
  a device; a multi-replica deployment would place them on disjoint
  replicas — the program split here is the prerequisite either way.)

TTFT p99 (``consensusml_serve_ttft_seconds``) is the target metric; no
benchmark cell serves yet (PERF.md section 7), so the staged path's gain
over the fused baseline is not measured.
"""

from __future__ import annotations

from typing import Any, Callable

__all__ = [
    "make_paged_prefill_fn",
    "make_paged_decode_fn",
    "make_prefix_prefill_fn",
    "prefill_cost_args",
    "decode_cost_args",
    "prefix_prefill_cost_args",
    "AdmissionScheduler",
]


def prefill_cost_args(bucket: int, block_size: int) -> tuple:
    """Abstract non-tree arguments of one paged-prefill invocation at
    ``bucket`` tokens — ``(ids, length, block_row, temperature, top_p,
    seed)`` shape structs for the cost ledger's AOT lowering
    (``Engine.register_costs``). Shapes mirror exactly what the live
    path passes, so the ledger's compiled row IS the serving
    executable's cost, not a lookalike's."""
    import jax
    import jax.numpy as jnp

    return (
        jax.ShapeDtypeStruct((1, bucket), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((bucket // block_size,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.uint32),
    )


def decode_cost_args(num_slots: int, blocks_per_slot: int) -> tuple:
    """Abstract ``(block_table, tokens, positions, temperature, top_p,
    seeds)`` shape structs of the ONE paged-decode executable (every
    occupancy/length/sampling mix runs this same program — one ledger
    row covers all of serving decode)."""
    import jax
    import jax.numpy as jnp

    return (
        jax.ShapeDtypeStruct((num_slots, blocks_per_slot), jnp.int32),
        jax.ShapeDtypeStruct((num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((num_slots,), jnp.int32),
        jax.ShapeDtypeStruct((num_slots,), jnp.float32),
        jax.ShapeDtypeStruct((num_slots,), jnp.float32),
        jax.ShapeDtypeStruct((num_slots,), jnp.uint32),
    )


def make_paged_prefill_fn(dm: Any) -> Callable:
    """``prefill(params, pages, ids (1, L), length, block_row (L//bs,),
    temperature, top_p, seed)`` -> ``(first_token, last_logits (V,),
    new_pages)``.

    One executable per padded bucket length ``L`` (block-aligned by
    construction: the engine's paged buckets start at the block size).
    The forward is the SAME ``return_kv`` trace the per-slot prefill
    uses; only the cache insertion differs — each ``block_size`` chunk of
    the prompt's K/V scatters to the physical block its table row names.
    ``block_row`` entries past the owned prefix are the trash block, so
    pad chunks never touch pages another slot owns; duplicate trash
    indices are benign (last-write-wins over garbage). The first token
    samples in-jit at fold position ``length - 1``
    (:mod:`consensusml_tpu.serve.sampling`; ``temperature = 0`` = the
    original greedy argmax).
    """
    import jax
    import jax.numpy as jnp

    from consensusml_tpu.serve.decode import _donate_cache
    from consensusml_tpu.serve.sampling import sample_token

    model = dm.model

    def prefill(params, pages, ids, length, block_row, temperature, top_p, seed):
        logits, kvs = model.apply(
            {"params": params}, ids, deterministic=True, return_kv=True
        )
        last = logits[0, length - 1]  # (V,) — last REAL token's logits
        bs = pages[0]["k"].shape[1]
        nblk = ids.shape[1] // bs
        new_pages = []
        for pg, (k, v) in zip(pages, kvs):
            # (1, L, H, D) -> (nblk, bs, H, D): chunk per physical block
            kr = jnp.asarray(k[0], pg["k"].dtype).reshape(
                nblk, bs, *k.shape[2:]
            )
            vr = jnp.asarray(v[0], pg["v"].dtype).reshape(
                nblk, bs, *v.shape[2:]
            )
            new_pages.append(
                {
                    "k": pg["k"].at[block_row].set(kr),
                    "v": pg["v"].at[block_row].set(vr),
                }
            )
        tok = sample_token(
            last[None], temperature[None], top_p[None], seed[None],
            (length - 1)[None],
        )[0]
        return tok, last, new_pages

    return jax.jit(prefill, donate_argnums=_donate_cache())


def prefix_prefill_cost_args(
    bucket: int, block_size: int, blocks_per_slot: int
) -> tuple:
    """Abstract non-tree arguments of one prefix-prefill invocation at
    suffix bucket ``bucket`` — ``(ids, suffix_len, start_pos, block_row,
    cow_src, cow_dst, temperature, top_p, seed)`` shape structs for the
    cost ledger's AOT lowering. The block row spans the slot's full
    table width plus ``bucket // block_size`` trash overflow columns
    (see :func:`make_prefix_prefill_fn`)."""
    import jax
    import jax.numpy as jnp

    cols = blocks_per_slot + bucket // block_size
    return (
        jax.ShapeDtypeStruct((1, bucket), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((cols,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.uint32),
    )


def make_prefix_prefill_fn(dm: Any, attn_impl: str = "gather") -> Callable:
    """``prefix_prefill(params, pages, ids (1, B), suffix_len,
    start_pos, block_row (cols,), cow_src, cow_dst, temperature, top_p,
    seed)`` -> ``(first_token, last_logits (V,), new_pages)``.

    The prefix-cache admission stage: the matched prefix is ALREADY in
    the slot's pool blocks (adopted from the index), so only the
    unshared suffix — ``ids[0, :suffix_len]`` at absolute positions
    ``start_pos + i``, right-padded to bucket ``B`` — runs the forward.
    This reuses the speculative verify's window machinery
    (2-D positions → ``paged_update_kv_cache_window`` +
    windowed paged attention): each suffix token's K/V scatters to
    ``block_row[pos // bs]`` and its query attends the gathered pages
    under the mask ``key_pos <= pos``, which reads the adopted prefix
    KV bit-exactly as the full causal prefill would have recomputed it.

    Where the split is mid-prefix (a FULL-match hit recomputing only the
    last token, or a future partial-block split), the slot's first write
    would land in a block other streams still share; ``cow_src`` /
    ``cow_dst`` resolve that copy-on-write INSIDE the jit
    (:func:`consensusml_tpu.models.attention.paged_cow_copy`): the shared
    source block's rows copy to the slot's fresh block BEFORE the window
    scatter, and ``block_row`` already names the fresh block — no host
    sync, no cache read-back. Passing ``cow_src == cow_dst == 0`` (the
    trash block) disables the copy (a trash self-copy is a benign no-op
    lane, same trick as the decode scatter's free lanes).

    One executable per SUFFIX bucket ``B`` — the same bucket ladder the
    full prefill compiles, so prefix splits change which executable runs,
    never its shape (zero-recompile contract). ``block_row`` carries
    ``B // block_size`` extra trash columns beyond ``blocks_per_slot``:
    bucket pad positions past the real suffix can reach
    ``start_pos + B - 1``, and ``pos // bs`` must resolve past-the-row
    chunks to trash instead of index-clamping into the slot's last owned
    block (same overflow guard as ``spec_table_cols``).
    """
    import jax
    import jax.numpy as jnp

    from consensusml_tpu.models.attention import paged_cow_copy
    from consensusml_tpu.serve.decode import _donate_cache
    from consensusml_tpu.serve.sampling import sample_token

    model = dm.model

    def prefix_prefill(
        params, pages, ids, suffix_len, start_pos, block_row,
        cow_src, cow_dst, temperature, top_p, seed,
    ):
        pages = [paged_cow_copy(pg, cow_src, cow_dst) for pg in pages]
        b = ids.shape[1]
        pos = start_pos + jnp.arange(b, dtype=jnp.int32)[None, :]
        logits, new_pages = model.apply(
            {"params": params},
            ids,
            deterministic=True,
            positions=pos,
            kv_cache=pages,
            block_table=block_row[None, :],
            attn_impl=attn_impl,
        )
        last = logits[0, suffix_len - 1]  # (V,) — last REAL suffix token
        fold = start_pos + suffix_len - 1  # absolute position n - 1:
        # the SAME fold key the full prefill derives, so sampled streams
        # stay bit-identical whichever admission path ran
        tok = sample_token(
            last[None], temperature[None], top_p[None], seed[None],
            fold[None],
        )[0]
        return tok, last, new_pages

    return jax.jit(prefix_prefill, donate_argnums=_donate_cache())


def make_paged_decode_fn(dm: Any, attn_impl: str = "gather") -> Callable:
    """``decode(params, pages, block_table (S, nb), tokens (S,),
    positions (S,), temperature (S,), top_p (S,), seeds (S,))`` ->
    ``(next_tokens (S,), new_pages)``.

    One token for ALL slots; each lane's write/read indices derive from
    its block-table row inside the jit
    (:func:`consensusml_tpu.models.attention.paged_update_kv_cache`),
    and each lane samples under its own ``(seed, position)`` fold key
    (:mod:`consensusml_tpu.serve.sampling`). Occupancy, lengths, block
    assignments, AND sampling parameters are all DATA — one executable
    serves every greedy/sampled mix, the zero-recompile contract. Only
    the pages donate; the block table is reused across steps.

    ``attn_impl`` is a construction-time static: "gather" keeps the
    two-step gather + dense attention; "jnp"/"interpret"/"pallas" run
    the fused paged-attention kernel tier
    (:mod:`consensusml_tpu.models.paged_attention`) — one pallas pass
    per layer, bit-exact vs gather, same zero-recompile contract.
    """
    import jax
    import jax.numpy as jnp

    from consensusml_tpu.serve.decode import _donate_cache
    from consensusml_tpu.serve.sampling import sample_token

    model = dm.model

    def decode(params, pages, block_table, tokens, positions, temperature, top_p, seeds):
        logits, new_pages = model.apply(
            {"params": params},
            tokens[:, None],
            deterministic=True,
            positions=positions,
            kv_cache=pages,
            block_table=block_table,
            attn_impl=attn_impl,
        )
        toks = sample_token(
            logits[:, 0], temperature, top_p, seeds, positions
        )
        return toks, new_pages

    return jax.jit(decode, donate_argnums=_donate_cache())


class AdmissionScheduler:
    """Per-tick prefill admission budget (host ints only, no device).

    One engine tick = one decode step + whatever prefills fit the token
    budget. ``try_admit`` charges a candidate's BUCKET length (what the
    device actually computes) against the tick's remaining budget:

    - the first admission of a tick always fits (otherwise a prompt
      longer than the budget would starve forever);
    - later admissions must fit the remaining budget, so a burst of
      arrivals spreads over several ticks instead of stalling decode for
      the whole burst — bounded added TTFT for the tail of the burst,
      bounded inter-token latency for everyone already decoding.
    """

    def __init__(self, prefill_budget: int):
        if prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be positive, got {prefill_budget}"
            )
        self.prefill_budget = prefill_budget
        self._remaining = prefill_budget
        self._admitted_this_tick = 0

    def start_tick(self) -> None:
        self._remaining = self.prefill_budget
        self._admitted_this_tick = 0

    def try_admit(self, bucket_tokens: int) -> bool:
        """Charge one prefill of ``bucket_tokens`` against this tick;
        False = defer the request to the next tick."""
        if self._admitted_this_tick and bucket_tokens > self._remaining:
            return False
        self._remaining = max(0, self._remaining - bucket_tokens)
        self._admitted_this_tick += 1
        return True
