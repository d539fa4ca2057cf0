"""Paged KV-cache block pool: fixed-shape pages + host block accounting.

The PR 5 engine gives every slot a private ``(max_len, H, D)`` cache row,
so HBM occupancy is bounded by ``num_slots * max_len`` tokens whether the
streams are long or short. The pool replaces the rows with a SHARED set
of fixed-shape physical blocks:

- **Pages** (device): per-layer ``{"k", "v"}`` arrays of shape
  ``(num_blocks, block_size, kv_heads, head_dim)``. One allocation for
  the life of the engine; never reshaped, so the zero-recompile contract
  the per-slot path pins (``analysis/jaxpr_contracts.py``) carries over
  unchanged — the paged stages get their own contracts.
- **Block table** (device): ``(num_slots, blocks_per_slot)`` int32 —
  slot-logical block index → physical block id. All gather/scatter
  indices derive from it INSIDE the jit
  (:func:`consensusml_tpu.models.attention.paged_update_kv_cache` /
  :func:`~consensusml_tpu.models.attention.gather_paged_kv`); the decode
  hot loop performs zero host syncs on pool state.
- **Free list** (host-authoritative, device-mirrored): allocation
  decisions happen at admission / block-boundary crossings — host events
  on host ints, off the per-token path. :class:`BlockPool` enforces the
  invariants the tests pin: no double-allocate, no double-free, no leak
  (free ∪ Σ-owned always partitions the physical blocks exactly,
  counting multiplicity now that blocks are shareable).

These invariants are also proven over EVERY interleaving of admissions,
adoptions, pins and releases — not just the schedules the tests run —
by the ``pool-refcount`` abstract model in
:mod:`consensusml_tpu.analysis.protocol_models` (cml-check pass 8),
with recorded-trace conformance tying the model to this class
block-id-exactly (:mod:`consensusml_tpu.analysis.conformance`). Change
the ownership protocol here and the model must change with it, or
replay fails in ``tests/test_model_check.py``.

**Refcounted sharing (prefix cache).** A physical block may appear in
MORE than one slot's owned list: the prefix cache (``prefix.py``) maps a
matched block-aligned prompt prefix straight into a new slot's table via
:meth:`adopt`, bumping the per-block refcount instead of popping fresh
blocks. Shared blocks are read-only by construction — every holder's
writes land at positions ≥ its own prompt length, past the shared
prefix — except the copy-on-write divergence case, which the engine
resolves INSIDE the jit (``paged_cow_copy``) after re-pointing the
diverging slot's table entry at a fresh page. :meth:`shrink` and
:meth:`release` decrement; a block returns to the free list only when
its last holder lets go. Freed blocks whose bytes are still referenced
by the prefix index re-enter the LIFO free stack at the BOTTOM
(``cached_hook``), so cached prefixes survive as long as pool pressure
allows and a preempted stream usually re-admits for free.

**The trash block.** Physical block 0 is reserved and never allocated.
Freed slots' table rows reset to 0, so the decode step's fixed-shape
scatter (every lane writes every step, free lanes included) lands free
lanes' garbage in the trash block instead of in pages another slot now
owns. Garbage gathered from trash (or from an owned block's
not-yet-written tail) sits beyond the length mask, which zeroes its
probability exactly — same argument the per-slot path makes for stale
rows, so slot/block reuse needs no cache clearing.

Occupancy is bounded by total LIVE tokens (``(num_blocks - 1) *
block_size``), not by ``num_slots * max_len``: with a heavy-tail length
mix, a pool sized for the MEAN length serves far more concurrent streams
than per-slot rows sized for the max. Prefix sharing tightens the bound further: N streams over
a shared prompt hold its blocks once, not N times.
"""

from __future__ import annotations

import time
from collections import Counter
from typing import Any, Callable, Iterable

import numpy as np

__all__ = ["BlockPool", "NoFreeBlocks", "init_pages", "blocks_for_tokens"]

TRASH_BLOCK = 0  # reserved physical block; free lanes scatter here


class NoFreeBlocks(RuntimeError):
    """The pool cannot satisfy an allocation; callers evict or defer."""


def blocks_for_tokens(tokens: int, block_size: int) -> int:
    """Physical blocks needed to hold ``tokens`` logical positions."""
    return -(-tokens // block_size)


def init_pages(dm: Any, num_blocks: int, block_size: int) -> list[dict]:
    """Per-layer ``{"k", "v"}`` page pools,
    ``(num_blocks, block_size, kv_heads, head_dim)`` in the model's
    compute dtype (Llama-GQA pages pre-repeat heads, like the slot
    cache). ~2 * layers * num_blocks * block_size * kv_heads * d *
    itemsize bytes total — sized by live tokens, not slots * max_len."""
    import jax.numpy as jnp

    shape = (num_blocks, block_size, dm.kv_heads, dm.head_dim)
    return [
        {
            "k": jnp.zeros(shape, dm.cache_dtype),
            "v": jnp.zeros(shape, dm.cache_dtype),
        }
        for _ in range(dm.layers)
    ]


class BlockPool:
    """Host-side block accounting for one engine (engine-thread only).

    LIFO free list (hot blocks reuse hot HBM lines), per-slot owned
    lists, per-block refcounts, and the host-authoritative block table
    mirrored to device on mutation. All methods raise on invariant
    violations rather than corrupting silently — a double-free here
    would hand one physical block to two live slots WITHOUT the
    refcount knowing, the paged equivalent of a use-after-free.

    Two optional hooks wire the prefix index in without a dependency
    cycle:

    - ``reuse_hook(block)`` fires when a FRESH pop is about to recycle a
      physical block (extend): the index drops any entries still naming
      it, before new content overwrites the bytes.
    - ``cached_hook(block) -> bool`` is consulted when a block's
      refcount hits zero: ``True`` parks it at the BOTTOM of the LIFO
      free stack (reused last, so indexed prefix bytes stay resident as
      long as pressure allows), ``False`` keeps the plain hot-reuse LIFO
      order.
    """

    def __init__(
        self,
        num_slots: int,
        max_len: int,
        block_size: int,
        num_blocks: int = 0,
        clock: Callable[[], float] | None = None,
    ):
        if block_size < 1:
            raise ValueError(f"block_size must be positive, got {block_size}")
        if max_len % block_size != 0:
            raise ValueError(
                f"block_size {block_size} must divide max_len {max_len} "
                "(keeps the gathered view bit-identical to the per-slot "
                "cache layout and prompt buckets block-aligned)"
            )
        self.num_slots = num_slots
        self.max_len = max_len
        self.block_size = block_size
        self.blocks_per_slot = max_len // block_size
        auto = num_slots * self.blocks_per_slot + 1
        self.num_blocks = num_blocks or auto
        if self.num_blocks < self.blocks_per_slot + 1:
            raise ValueError(
                f"num_blocks {self.num_blocks} cannot hold even one "
                f"max-length stream ({self.blocks_per_slot} blocks "
                "+ the trash block); the engine could never admit"
            )
        # LIFO stack of free physical ids; block 0 (trash) never enters
        self._free: list[int] = list(range(self.num_blocks - 1, 0, -1))
        self._owned: dict[int, list[int]] = {}
        # holders per physical block: Σ slot-owned multiplicity + pins.
        # refcnt == 0 <=> on the free list (check() proves it).
        self._refcnt = np.zeros((self.num_blocks,), np.int32)
        # per-slot subset of owned blocks acquired via adopt() (prefix
        # hits) — drives the shared/unshared block-second split
        self._adopted: dict[int, set[int]] = {}
        # pin multiset: blocks held alive with no slot owner (the COW
        # source for the duration of one prefill dispatch)
        self._pinned: Counter = Counter()
        self._table = np.zeros((num_slots, self.blocks_per_slot), np.int32)
        self._dev_table = None  # invalidated on mutation, rebuilt lazily
        self.reuse_hook: Callable[[int], Any] | None = None
        self.cached_hook: Callable[[int], bool] | None = None
        # block-second accounting (docs/observability.md "Wide events &
        # tenant accounting"): per-slot ∫ held_blocks dt, integrated at
        # every mutation — each alloc/extend/shrink/release first adds
        # held × elapsed at the OLD holding, then mutates, so the
        # integral is exact piecewise-constant occupancy over hold time.
        # Adopted (prefix-shared) blocks integrate into a SEPARATE
        # accumulator so the engine charges only unshared block-seconds.
        # The clock is injectable so tests pin the math deterministically.
        self._clock = clock if clock is not None else time.monotonic
        self._bs_acc: dict[int, float] = {}
        self._bs_sh_acc: dict[int, float] = {}
        self._bs_t: dict[int, float] = {}

    # -- introspection ------------------------------------------------------

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # trash excluded

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """DISTINCT physical blocks held (shared blocks count once) —
        the honest occupancy number under prefix sharing."""
        return self.usable_blocks - len(self._free)

    @property
    def shared_blocks(self) -> int:
        """Physical blocks currently held by more than one holder."""
        return int(np.count_nonzero(self._refcnt > 1))

    def owned(self, slot: int) -> list[int]:
        return list(self._owned.get(slot, ()))

    def refcount(self, block: int) -> int:
        return int(self._refcnt[block])

    def can_admit(self, n_blocks: int) -> bool:
        return len(self._free) >= n_blocks

    def _integrate(self, slot: int) -> None:
        """Advance ``slot``'s block-second integrals to now at its
        CURRENT holding (call before any mutation of the holding)."""
        t = self._bs_t.get(slot)
        if t is None:
            return
        now = self._clock()
        dt = now - t
        self._bs_acc[slot] += len(self._owned.get(slot, ())) * dt
        self._bs_sh_acc[slot] += len(self._adopted.get(slot, ())) * dt
        self._bs_t[slot] = now

    def block_seconds(self, slot: int) -> float:
        """``slot``'s TOTAL block-seconds held so far (∫ owned_blocks dt
        since its alloc, integrated to now, shared holds included). 0.0
        for a slot that owns nothing — the engine reads this immediately
        BEFORE :meth:`release` and accumulates it onto the request, so
        the total survives recompute-preemption and re-admission."""
        if slot not in self._owned:
            return 0.0
        self._integrate(slot)
        return self._bs_acc.get(slot, 0.0)

    def block_seconds_split(self, slot: int) -> tuple[float, float]:
        """``(unshared, shared)`` block-seconds for ``slot``: ``shared``
        integrates only blocks the slot ADOPTED from the prefix index
        (held jointly with other streams / the cache), ``unshared`` the
        rest. ``unshared + shared == block_seconds()``. Wide events
        charge the request only the unshared part."""
        if slot not in self._owned:
            return 0.0, 0.0
        self._integrate(slot)
        total = self._bs_acc.get(slot, 0.0)
        shared = self._bs_sh_acc.get(slot, 0.0)
        return total - shared, shared

    # -- mutation -----------------------------------------------------------

    def begin(self, slot: int) -> None:
        """Open ``slot``'s holding without allocating anything yet —
        the prefix-hit admission path adopts matched blocks first, then
        extends with fresh ones. :meth:`alloc` = begin + extend."""
        if slot in self._owned:
            raise RuntimeError(
                f"slot {slot} already owns blocks (double-alloc); "
                "release before re-admitting"
            )
        self._owned[slot] = []
        self._adopted[slot] = set()
        self._bs_acc[slot] = 0.0
        self._bs_sh_acc[slot] = 0.0
        self._bs_t[slot] = self._clock()

    def alloc(self, slot: int, n_blocks: int) -> list[int]:
        """Give ``slot`` its first ``n_blocks`` blocks (admission)."""
        if slot in self._owned:
            raise RuntimeError(
                f"slot {slot} already owns blocks (double-alloc); "
                "release before re-admitting"
            )
        if n_blocks > self.blocks_per_slot:
            raise ValueError(
                f"slot {slot} asked for {n_blocks} blocks "
                f"> blocks_per_slot {self.blocks_per_slot}"
            )
        if len(self._free) < n_blocks:
            raise NoFreeBlocks(
                f"need {n_blocks} blocks, {len(self._free)} free"
            )
        self.begin(slot)
        return self.extend(slot, n_blocks)

    def _acquire_ref(self, b: int) -> None:
        """Bump ``b``'s refcount, reviving it off the free list if it
        currently has no holder (a cached prefix block being re-shared)."""
        if self._refcnt[b] == 0:
            try:
                self._free.remove(b)
            except ValueError:
                raise RuntimeError(
                    f"corrupt refcount: block {b} has no holder "
                    "but is not on the free list"
                ) from None
        self._refcnt[b] += 1

    def _release_ref(self, b: int) -> bool:
        """Drop one reference to ``b``; returns True when the LAST
        holder let go and the block went back on the free list."""
        if self._refcnt[b] < 1:
            raise RuntimeError(
                f"corrupt refcount: block {b} released below zero"
            )
        self._refcnt[b] -= 1
        if self._refcnt[b] != 0:
            return False
        if b in self._free:
            raise RuntimeError(f"corrupt free list: block {b}")
        if self.cached_hook is not None and self.cached_hook(b):
            # indexed prefix bytes: park at the BOTTOM of the LIFO
            # stack so fresh pops recycle this block LAST
            self._free.insert(0, b)
        else:
            self._free.append(b)
        return True

    def adopt(self, slot: int, blocks: Iterable[int]) -> list[int]:
        """Map already-materialized physical blocks (a prefix-index
        match) into ``slot``'s table, bumping refcounts instead of
        popping fresh blocks. The slot must have been opened with
        :meth:`begin`; adopted blocks precede any extend in the row."""
        owned = self._owned.get(slot)
        if owned is None:
            raise RuntimeError(f"slot {slot} owns nothing; begin first")
        blocks = [int(b) for b in blocks]
        if len(owned) + len(blocks) > self.blocks_per_slot:
            raise ValueError(
                f"slot {slot} would exceed blocks_per_slot "
                f"({len(owned)} + {len(blocks)} > {self.blocks_per_slot})"
            )
        self._integrate(slot)
        adopted = self._adopted.setdefault(slot, set())
        for b in blocks:
            if b == TRASH_BLOCK or not 0 < b < self.num_blocks:
                raise ValueError(f"cannot adopt physical block {b}")
            if b in adopted or b in owned:
                raise RuntimeError(
                    f"slot {slot} already holds block {b} (double-adopt)"
                )
            self._acquire_ref(b)
            self._table[slot, len(owned)] = b
            owned.append(b)
            adopted.add(b)
        if blocks:
            self._dev_table = None
        return blocks

    def pin(self, block: int) -> None:
        """Hold ``block`` alive with no slot owner — the engine pins the
        COW source across one prefill dispatch so a concurrent extend
        cannot pop and overwrite it before the in-jit copy reads it."""
        b = int(block)
        if b == TRASH_BLOCK or not 0 < b < self.num_blocks:
            raise ValueError(f"cannot pin physical block {b}")
        self._acquire_ref(b)
        self._pinned[b] += 1

    def unpin(self, block: int) -> None:
        b = int(block)
        if self._pinned[b] < 1:
            raise RuntimeError(f"block {b} is not pinned")
        self._pinned[b] -= 1
        if self._pinned[b] == 0:
            del self._pinned[b]
        self._release_ref(b)

    def extend(self, slot: int, n_blocks: int = 1) -> list[int]:
        """Grow ``slot`` by ``n_blocks`` FRESH blocks (admission tail /
        decode crossing a boundary). Each pop fires ``reuse_hook`` so
        the prefix index forgets the recycled bytes before the slot
        overwrites them."""
        owned = self._owned.get(slot)
        if owned is None:
            raise RuntimeError(f"slot {slot} owns nothing; alloc first")
        if len(owned) + n_blocks > self.blocks_per_slot:
            raise ValueError(
                f"slot {slot} would exceed blocks_per_slot "
                f"({len(owned)} + {n_blocks} > {self.blocks_per_slot})"
            )
        if len(self._free) < n_blocks:
            raise NoFreeBlocks(
                f"need {n_blocks} blocks, {len(self._free)} free"
            )
        self._integrate(slot)
        got = []
        for _ in range(n_blocks):
            b = self._free.pop()
            self._refcnt[b] = 1
            if self.reuse_hook is not None:
                self.reuse_hook(b)
            self._table[slot, len(owned)] = b
            owned.append(b)
            got.append(b)
        if got:
            self._dev_table = None
        return got

    def shrink(self, slot: int, keep_blocks: int) -> list[int]:
        """Relinquish ``slot``'s blocks BEYOND the first ``keep_blocks``
        (speculative rollback: a rejected draft suffix hands its
        over-allocated tail back; the kept prefix — committed tokens
        plus the next write — is untouched). Relinquished table entries
        reset to trash; each block returns to the free list only when
        its LAST holder lets go. Returns the relinquished ids (possibly
        empty)."""
        owned = self._owned.get(slot)
        if owned is None:
            raise RuntimeError(f"slot {slot} owns nothing; alloc first")
        if keep_blocks < 1:
            raise ValueError(
                f"keep_blocks must be >= 1, got {keep_blocks} (release() "
                "frees a slot outright)"
            )
        self._integrate(slot)
        adopted = self._adopted.get(slot)
        dropped = []
        while len(owned) > keep_blocks:
            b = owned.pop()
            if b == TRASH_BLOCK:
                raise RuntimeError(f"corrupt free list: block {b}")
            if adopted:
                adopted.discard(b)
            self._release_ref(b)
            self._table[slot, len(owned)] = TRASH_BLOCK
            dropped.append(b)
        if dropped:
            self._dev_table = None
        return dropped

    def release(self, slot: int) -> list[int]:
        """Drop all of ``slot``'s references and reset its table row to
        the trash block. Returns the relinquished ids; blocks shared
        with other holders stay allocated to them."""
        owned = self._owned.pop(slot, None)
        if owned is None:
            raise RuntimeError(f"slot {slot} owns nothing (double-free)")
        self._adopted.pop(slot, None)
        self._bs_acc.pop(slot, None)
        self._bs_sh_acc.pop(slot, None)
        self._bs_t.pop(slot, None)
        for b in owned:
            if b == TRASH_BLOCK:
                raise RuntimeError(f"corrupt free list: block {b}")
            self._release_ref(b)
        self._table[slot, :] = TRASH_BLOCK
        self._dev_table = None
        return owned

    # -- views --------------------------------------------------------------

    def block_row(self, slot: int, width: int) -> np.ndarray:
        """``slot``'s physical ids padded with trash to ``width`` entries
        (the prefill scatter's fixed-shape index vector: pad blocks
        beyond the owned prefix land in trash)."""
        owned = self._owned.get(slot, ())
        row = np.full((width,), TRASH_BLOCK, np.int32)
        n = min(len(owned), width)
        row[:n] = owned[:n]
        return row

    def device_table(self, extra_cols: int = 0):
        """The block table as a device array (cached; host→device copy
        only after a mutation, never inside the decode step).

        ``extra_cols > 0`` appends that many TRASH columns — the
        speculative verify window's overflow guard: a stream within
        ``k`` tokens of ``max_len`` computes window positions past its
        real row, and ``table[s, pos // bs]`` must resolve those to the
        trash block rather than index-clamp into the slot's LAST owned
        block (which holds live tokens). Cached per width."""
        if self._dev_table is None:  # invalidated by a mutation
            self._dev_table = {}
        if extra_cols not in self._dev_table:
            import jax.numpy as jnp

            table = self._table
            if extra_cols:
                pad = np.full(
                    (self.num_slots, extra_cols), TRASH_BLOCK, np.int32
                )
                table = np.concatenate([table, pad], axis=1)
            self._dev_table[extra_cols] = jnp.asarray(table)
        return self._dev_table[extra_cols]

    def check(self) -> None:
        """Invariant sweep (tests + debug): free ∪ Σ-owned ∪ pins
        partitions the non-trash physical blocks COUNTING MULTIPLICITY —
        every block's refcount equals the number of holders naming it,
        free blocks have refcount 0 and no holder, and every non-trash
        block is accounted for exactly (free XOR held)."""
        holders: Counter = Counter(self._pinned)
        for slot, blocks in self._owned.items():
            if len(set(blocks)) != len(blocks):
                raise AssertionError(
                    f"slot {slot} holds a block twice: {blocks}"
                )
            holders.update(blocks)
            row = self._table[slot, : len(blocks)]
            if list(row) != blocks:
                raise AssertionError(
                    f"slot {slot} table row {list(row)} != owned {blocks}"
                )
            if not self._adopted.get(slot, set()) <= set(blocks):
                raise AssertionError(
                    f"slot {slot} adopted set escapes its owned list"
                )
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise AssertionError("duplicate entry on the free list")
        if not all(0 < b < self.num_blocks for b in free_set):
            raise AssertionError("free list entry out of range")
        for b in free_set:
            if holders[b]:
                raise AssertionError(f"block {b} is both free and held")
        for b in range(1, self.num_blocks):
            if int(self._refcnt[b]) != holders[b]:
                raise AssertionError(
                    f"block {b} refcount {int(self._refcnt[b])} != "
                    f"{holders[b]} holders"
                )
            if holders[b] == 0 and b not in free_set:
                raise AssertionError(
                    f"block leak: block {b} has no holder and is not free"
                )
        if TRASH_BLOCK in free_set or holders[TRASH_BLOCK]:
            raise AssertionError("trash block was allocated")
        if int(self._refcnt[TRASH_BLOCK]) != 0:
            raise AssertionError("trash block was allocated")
