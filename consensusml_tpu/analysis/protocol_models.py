"""Abstract models of the serving control-plane protocols (pass 8).

Three hand-written models, each a faithful abstraction of one host-side
protocol, checked exhaustively by :mod:`.model` over every interleaving
of 2–4 abstract actors up to a depth bound:

- :class:`PoolModel` — BlockPool refcounts + PrefixIndex
  (``serve/pool/blocks.py`` / ``prefix.py``): begin/alloc/extend/adopt/
  pin/unpin/shrink/release with the reuse/cached hooks and COW.
  Invariants: free ∪ Σ owned-with-multiplicity ∪ pins partitions the
  physical blocks, no double-free, no leak, and an indexed refcount-0
  block is revived (adopt) or invalidated (fresh pop) but never both.
- :class:`RequestModel` — the request lifecycle
  (``serve/engine.py``): submit→defer→admit→prefill→decode→{complete,
  preempt-readmit, cancel} interleaved with hot-swap generation flips.
  Invariants: generations are monotone, resident slots never decode at
  a stale generation, no lost stream (a completed request emitted
  exactly its target; continuations never rewind emitted tokens), and
  a preempted stream re-admits exactly once per preemption.
- :class:`MembershipModel` — epoch pin/advance
  (``swarm/membership.py``): in-flight rounds complete against their
  pinned epoch (pinned views survive advance), and the metrics gauge
  never lands at an older epoch (the PR 13 ``_fed_epoch`` claim).

Every model doubles as the conformance oracle: recorded traces from the
real classes (``analysis/conformance.py``) replay through the same
``apply``/``invariant`` code with ``strict=False`` relaxations where
the recording is sequential but the modelled action is atomic
(hot-swap tag updates arrive one resident slot at a time).

Seeded-bug variants (:func:`fixture_specs`) re-introduce real bug
classes — a pre-refcount double-free release, a swap flip that leaves
resident slots at a stale generation tag, an unclaimed membership
gauge feed — and the pass *requires* each to yield a counterexample:
a fixture the checker cannot refute means the detector is broken
(the PR 15 negative-fixture pattern).
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from pathlib import Path
from typing import Iterable, Optional, Sequence

from .findings import Finding
from .model import CheckResult, IllegalAction, check_model

__all__ = [
    "PoolModel",
    "DoubleFreePoolModel",
    "NoInvalidatePoolModel",
    "RequestModel",
    "StaleSwapRequestModel",
    "MembershipModel",
    "UnclaimedGaugeMembershipModel",
    "ModelSpec",
    "builtin_specs",
    "fixture_specs",
    "run_builtin",
]


def _need(cond: bool, why: str) -> None:
    if not cond:
        raise IllegalAction(why)


# ---------------------------------------------------------------------------
# (a) BlockPool refcounts + PrefixIndex
# ---------------------------------------------------------------------------


class PoolModel:
    """Abstract BlockPool + PrefixIndex.

    State (all hashable)::

        free     LIFO stack of free physical ids (last = next pop)
        owned    per slot: None (closed) | tuple of held ids, in row order
        adopted  per slot: frozenset of ids acquired via adopt
        pinned   per block: pin count (COW sources)
        indexed  frozenset of ids the prefix index names
        stale    ghost: indexed ids whose bytes were overwritten without
                 invalidation (only buggy variants ever populate it)

    Refcounts are *derived* (Σ owned multiplicity + pins) so the
    invariant checks the partition itself, not a shadow counter.
    Labels carry concrete block ids when replayed from a recording, so
    replay also proves the model's LIFO pop order (including the
    ``cached_hook`` bottom-park) matches the real pool's.
    """

    name = "pool-refcount"
    subject = "consensusml_tpu/serve/pool/blocks.py"

    def __init__(
        self,
        num_slots: int = 2,
        usable_blocks: int = 3,
        blocks_per_slot: int = 2,
    ):
        self.num_slots = num_slots
        self.usable_blocks = usable_blocks
        self.blocks_per_slot = blocks_per_slot

    def initial(self):
        free = tuple(range(self.usable_blocks, 0, -1))  # pops 1, 2, 3, ...
        owned = (None,) * self.num_slots
        adopted = (frozenset(),) * self.num_slots
        pinned = (0,) * (self.usable_blocks + 1)
        return (free, owned, adopted, pinned, frozenset(), frozenset())

    # -- transition helpers -------------------------------------------------

    def _holders(self, owned, pinned) -> Counter:
        holders: Counter = Counter()
        for blocks in owned:
            if blocks:
                holders.update(blocks)
        for b, n in enumerate(pinned):
            if n:
                holders[b] += n
        return holders

    def _free_block(self, free: list, b: int, indexed: frozenset) -> None:
        """Return ``b`` to the free stack: bottom when the prefix index
        still names its bytes (``cached_hook``), top otherwise."""
        if b in indexed:
            free.insert(0, b)
        else:
            free.append(b)

    def _pop_fresh(self, free: list, indexed: set, stale: set) -> int:
        _need(bool(free), "no free blocks")
        b = free.pop()
        # reuse_hook: the index forgets the recycled bytes eagerly
        indexed.discard(b)
        stale.discard(b)
        return b

    def labels(self, state):
        free, owned, adopted, pinned, indexed, stale = state
        for s in range(self.num_slots):
            if owned[s] is None:
                yield ("begin", s)
                continue
            if len(owned[s]) < self.blocks_per_slot and free:
                yield ("extend", s)
            held = set(owned[s])
            for b in sorted(indexed):
                if b not in held and len(owned[s]) < self.blocks_per_slot:
                    yield ("adopt", s, (b,))
            if owned[s] and owned[s][0] not in indexed:
                yield ("index", s)
            if len(owned[s]) > 1:
                yield ("shrink", s, 1)
            yield ("release", s)
            for b in sorted(adopted[s]):
                if free:
                    yield ("cow", s, b)
        for b in sorted(indexed):
            if pinned[b] == 0:
                yield ("pin", b)
        for b in range(1, self.usable_blocks + 1):
            if pinned[b] > 0:
                yield ("unpin", b)

    def apply(self, state, label):
        free, owned, adopted, pinned, indexed, stale = state
        free = list(free)
        owned = list(owned)
        adopted = list(adopted)
        pinned = list(pinned)
        indexed = set(indexed)
        stale = set(stale)
        op = label[0]

        if op == "begin":
            s = label[1]
            _need(owned[s] is None, f"slot {s} already owns blocks")
            owned[s] = ()
            adopted[s] = frozenset()
        elif op in ("extend", "alloc"):
            s = label[1]
            want = label[2] if len(label) > 2 else None  # recorded ids
            if op == "alloc":
                _need(owned[s] is None, f"slot {s} already owns blocks")
                owned[s] = ()
                adopted[s] = frozenset()
            _need(owned[s] is not None, f"slot {s} owns nothing")
            n = len(want) if want is not None else 1
            _need(
                len(owned[s]) + n <= self.blocks_per_slot,
                f"slot {s} would exceed blocks_per_slot",
            )
            got = []
            for _ in range(n):
                got.append(self._pop_fresh(free, indexed, stale))
            if want is not None and tuple(got) != tuple(want):
                raise IllegalAction(
                    f"free-list order mismatch: model pops {tuple(got)}, "
                    f"recording saw {tuple(want)}"
                )
            owned[s] = owned[s] + tuple(got)
        elif op == "adopt":
            s, blocks = label[1], label[2]
            _need(owned[s] is not None, f"slot {s} owns nothing; begin first")
            _need(
                len(owned[s]) + len(blocks) <= self.blocks_per_slot,
                f"slot {s} would exceed blocks_per_slot",
            )
            holders = self._holders(owned, pinned)
            for b in blocks:
                _need(
                    b not in owned[s], f"slot {s} already holds block {b}"
                )
                # only live blocks or current indexed bytes are adoptable
                _need(
                    holders[b] > 0 or b in indexed,
                    f"block {b} is neither live nor indexed",
                )
                if holders[b] == 0:  # revive off the free list
                    _need(b in free, f"block {b} has no holder and no bytes")
                    free.remove(b)
                owned[s] = owned[s] + (b,)
                adopted[s] = adopted[s] | {b}
                holders[b] += 1
        elif op == "index":
            s = label[1]
            _need(bool(owned[s]), f"slot {s} owns nothing to index")
            indexed.add(owned[s][0])
        elif op == "pin":
            b = label[1]
            holders = self._holders(owned, pinned)
            _need(
                holders[b] > 0 or b in indexed,
                f"block {b} is neither live nor indexed",
            )
            if holders[b] == 0:
                _need(b in free, f"block {b} has no holder and no bytes")
                free.remove(b)
            pinned[b] += 1
        elif op == "unpin":
            b = label[1]
            _need(pinned[b] > 0, f"block {b} is not pinned")
            pinned[b] -= 1
            holders = self._holders(owned, pinned)
            if holders[b] == 0:
                self._free_block(free, b, indexed)
        elif op == "shrink":
            s, keep = label[1], label[2]
            _need(owned[s] is not None, f"slot {s} owns nothing")
            _need(keep >= 1, "keep_blocks must be >= 1")
            row = list(owned[s])
            ad = set(adopted[s])
            while len(row) > keep:
                b = row.pop()
                ad.discard(b)
                holders = self._holders([tuple(row)] + [
                    o for i, o in enumerate(owned) if i != s
                ], pinned)
                if holders[b] == 0:
                    self._free_block(free, b, indexed)
            owned[s] = tuple(row)
            adopted[s] = frozenset(ad)
        elif op == "release":
            s = label[1]
            _need(owned[s] is not None, f"slot {s} owns nothing (double-free)")
            row = list(owned[s])
            owned[s] = None
            adopted[s] = frozenset()
            self._do_release(free, owned, pinned, row, indexed)
        elif op == "cow":
            s, b = label[1], label[2]
            _need(owned[s] is not None, f"slot {s} owns nothing")
            _need(b in adopted[s], f"block {b} is not adopted by slot {s}")
            fresh = self._pop_fresh(free, indexed, stale)
            pos = owned[s].index(b)
            owned[s] = owned[s][:pos] + (fresh,) + owned[s][pos + 1 :]
            adopted[s] = adopted[s] - {b}
            holders = self._holders(owned, pinned)
            if holders[b] == 0:
                self._free_block(free, b, indexed)
        else:
            raise IllegalAction(f"unknown action {op!r}")

        return (
            tuple(free),
            tuple(owned),
            tuple(adopted),
            tuple(pinned),
            frozenset(indexed),
            frozenset(stale),
        )

    def _do_release(self, free, owned, pinned, row, indexed):
        """Release one slot's former holding ``row`` (already detached
        from ``owned``): each block returns to the free list only when
        its LAST holder lets go."""
        for i, b in enumerate(row):
            # remaining references: other slots + pins + the not-yet-
            # released tail of this row
            remaining = self._holders(owned, pinned)
            for later in row[i + 1 :]:
                remaining[later] += 1
            if remaining[b] == 0:
                self._free_block(free, b, indexed)

    def invariant(self, state) -> Optional[str]:
        free, owned, adopted, pinned, indexed, stale = state
        holders = self._holders(owned, pinned)
        for s in range(self.num_slots):
            blocks = owned[s]
            if blocks is None:
                continue
            if len(set(blocks)) != len(blocks):
                return f"aliasing: slot {s} holds a block twice: {blocks}"
            if len(blocks) > self.blocks_per_slot:
                return f"capacity: slot {s} exceeds blocks_per_slot"
            if not adopted[s] <= set(blocks):
                return f"aliasing: slot {s} adopted set escapes its owned list"
        if len(set(free)) != len(free):
            return f"double-free: duplicate entry on the free list: {free}"
        for b in free:
            if not 1 <= b <= self.usable_blocks:
                return f"partition: free list entry {b} out of range"
            if holders[b]:
                return f"double-free: block {b} is both free and held"
        free_set = set(free)
        for b in range(1, self.usable_blocks + 1):
            if holders[b] == 0 and b not in free_set:
                return f"leak: block {b} has no holder and is not free"
        if holders[0] or 0 in free_set:
            return "partition: trash block was allocated"
        both = indexed & stale
        if both:
            return (
                f"revive-invalidate: index entry for block {sorted(both)[0]} "
                "survived a fresh pop (bytes overwritten, entry live)"
            )
        return None


class DoubleFreePoolModel(PoolModel):
    """Seeded bug: release returns every block to the free list
    unconditionally — the pre-refcount behaviour. Two slots sharing a
    prefix block make the first release hand the shared block back
    while the second still decodes against it."""

    name = "pool-double-free"

    def _do_release(self, free, owned, pinned, row, indexed):
        for b in row:
            self._free_block(free, b, indexed)


class NoInvalidatePoolModel(PoolModel):
    """Seeded bug: a fresh pop skips ``reuse_hook`` — the prefix index
    keeps naming bytes that a new stream just overwrote, so a later
    admission adopts garbage (revive AND invalidate)."""

    name = "pool-stale-index"

    def _pop_fresh(self, free, indexed, stale):
        _need(bool(free), "no free blocks")
        b = free.pop()
        if b in indexed:  # entry survives the overwrite: now stale
            stale.add(b)
        return b


# ---------------------------------------------------------------------------
# (b) request lifecycle × hot-swap generation flips
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Req:
    phase: str = "new"  # new|queued|admitted|decoding|done|cancelled
    slot: int = -1
    emitted: int = 0
    emitted_seen: int = 0  # ghost: high-water mark (no lost stream)
    preempts: int = 0
    readmits: int = 0
    deferred: bool = False


class RequestModel:
    """Abstract request state machine composed with hot-swap flips.

    ``strict=True`` is the bounded DFS configuration: per-request token
    targets, bounded preemptions/generations, and the atomic-flip
    invariant that every resident slot's generation tag equals the
    engine generation. ``strict=False`` is the conformance-replay
    configuration: recorded hot-swap events arrive one resident slot at
    a time, targets vary per request, and admissions carry the recorded
    ``continuation`` flag which must agree with the model's own
    preempt/readmit accounting.
    """

    name = "request-lifecycle"
    subject = "consensusml_tpu/serve/engine.py"

    def __init__(
        self,
        n_requests: int = 2,
        n_slots: int = 1,
        target_tokens: int = 2,
        max_generation: int = 2,
        max_preempts: int = 1,
        strict: bool = True,
    ):
        self.n_requests = n_requests
        self.n_slots = n_slots
        self.target_tokens = target_tokens
        self.max_generation = max_generation
        self.max_preempts = max_preempts
        self.strict = strict

    def initial(self):
        reqs = tuple(_Req() for _ in range(self.n_requests))
        tags = (0,) * self.n_slots
        # (reqs, engine_gen, staged, slot_tags, gen_seen)
        return (reqs, 0, -1, tags, 0)

    def _occupant(self, reqs, s: int) -> int:
        for i, r in enumerate(reqs):
            if r.slot == s and r.phase in ("admitted", "decoding"):
                return i
        return -1

    def labels(self, state):
        reqs, gen, staged, tags, _seen = state
        for i, r in enumerate(reqs):
            if r.phase == "new":
                yield ("submit", i)
            elif r.phase == "queued":
                if not r.deferred:
                    yield ("defer", i)
                for s in range(self.n_slots):
                    if self._occupant(reqs, s) < 0:
                        yield ("admit", i, s)
                yield ("cancel", i)
            elif r.phase == "admitted":
                yield ("prefill", i)
            elif r.phase == "decoding":
                if r.emitted < self.target_tokens:
                    yield ("tick", i)
                else:
                    yield ("complete", i)
                if r.preempts < self.max_preempts:
                    yield ("preempt", i)
        if staged < 0 and gen < self.max_generation:
            yield ("stage",)
        if staged >= 0:
            yield ("flip",)

    def apply(self, state, label):
        reqs, gen, staged, tags, seen = state
        reqs = list(reqs)
        tags = list(tags)
        op = label[0]

        def req(i) -> _Req:
            return reqs[i]

        if op == "submit":
            i = label[1]
            _need(req(i).phase == "new", "already submitted")
            reqs[i] = dataclasses.replace(req(i), phase="queued")
        elif op == "defer":
            i = label[1]
            _need(req(i).phase == "queued", "defer of a non-queued request")
            if self.strict:
                _need(not req(i).deferred, "defer bound reached")
            reqs[i] = dataclasses.replace(req(i), deferred=True)
        elif op == "admit":
            i, s = label[1], label[2]
            r = req(i)
            _need(r.phase == "queued", f"request {i} is not queued")
            _need(0 <= s < self.n_slots, f"slot {s} out of range")
            _need(self._occupant(reqs, s) < 0, f"slot {s} is occupied")
            continuation = r.preempts > r.readmits
            if len(label) > 3:  # recorded continuation flag: must agree
                _need(
                    bool(label[3]) == continuation,
                    f"request {i}: recorded continuation={label[3]} but "
                    f"model has preempts={r.preempts} readmits={r.readmits}",
                )
            reqs[i] = dataclasses.replace(
                r,
                phase="admitted",
                slot=s,
                readmits=r.readmits + (1 if continuation else 0),
            )
            tags[s] = gen
        elif op == "prefill":
            i = label[1]
            r = req(i)
            _need(r.phase == "admitted", f"request {i} was not admitted")
            emitted = r.emitted if r.emitted > 0 else 1
            reqs[i] = dataclasses.replace(r, phase="decoding", emitted=emitted)
        elif op == "tick":
            i = label[1]
            r = req(i)
            _need(r.phase == "decoding", f"request {i} is not decoding")
            if self.strict:
                _need(r.emitted < self.target_tokens, "target reached")
                reqs[i] = dataclasses.replace(r, emitted=r.emitted + 1)
            # replay: one recorded decode event stands for all ticks
        elif op == "complete":
            i = label[1]
            r = req(i)
            if self.strict:
                _need(r.phase == "decoding", f"request {i} is not decoding")
                _need(r.emitted >= self.target_tokens, "stream not finished")
            else:
                _need(
                    r.phase in ("admitted", "decoding"),
                    f"request {i} is not resident",
                )
            reqs[i] = dataclasses.replace(r, phase="done", slot=-1)
        elif op == "preempt":
            i = label[1]
            r = req(i)
            _need(r.phase == "decoding", f"request {i} is not decoding")
            if self.strict:
                _need(r.preempts < self.max_preempts, "preempt bound reached")
            reqs[i] = dataclasses.replace(
                r, phase="queued", slot=-1, preempts=r.preempts + 1
            )
        elif op == "cancel":
            i = label[1]
            r = req(i)
            _need(r.phase == "queued", f"request {i} is not queued")
            reqs[i] = dataclasses.replace(r, phase="cancelled", slot=-1)
        elif op == "stage":
            _need(staged < 0, "a generation is already staged")
            _need(gen < self.max_generation, "generation bound reached")
            staged = gen + 1
        elif op == "flip":
            _need(staged >= 0, "nothing staged")
            gen = staged
            staged = -1
            tags = self._flip_tags(reqs, tags, gen)
        elif op == "observe_swap":
            # replay form: hotswap events land one resident slot at a time
            i, g = label[1], label[2]
            r = req(i)
            _need(
                r.phase in ("admitted", "decoding"),
                f"request {i} observed a swap while not resident",
            )
            _need(
                g >= tags[r.slot],
                f"slot {r.slot} generation moved backwards: "
                f"{tags[r.slot]} -> {g}",
            )
            tags[r.slot] = g
            gen = max(gen, g)
        else:
            raise IllegalAction(f"unknown action {op!r}")

        reqs = tuple(
            dataclasses.replace(
                r, emitted_seen=max(r.emitted_seen, r.emitted)
            )
            for r in reqs
        )
        seen = max(seen, gen)
        return (reqs, gen, staged, tuple(tags), seen)

    def _flip_tags(self, reqs, tags, gen):
        """Atomic flip: every RESIDENT slot's tag follows the engine
        generation in the same step (``Engine._maybe_swap`` updates all
        live slots before the next decode dispatch)."""
        tags = list(tags)
        for s in range(self.n_slots):
            if self._occupant(reqs, s) >= 0:
                tags[s] = gen
        return tags

    def invariant(self, state) -> Optional[str]:
        reqs, gen, staged, tags, seen = state
        if gen < seen:
            return (
                f"generation-monotone: engine generation moved backwards "
                f"({seen} -> {gen})"
            )
        if staged >= 0 and staged != gen + 1 and self.strict:
            return f"stale-stage: staged generation {staged} vs engine {gen}"
        occupants: dict = {}
        for i, r in enumerate(reqs):
            if r.phase in ("admitted", "decoding"):
                if r.slot in occupants:
                    return (
                        f"slot-aliasing: requests {occupants[r.slot]} and "
                        f"{i} both resident in slot {r.slot}"
                    )
                occupants[r.slot] = i
                tag = tags[r.slot]
                if self.strict and tag != gen:
                    return (
                        f"stale-generation: slot {r.slot} decodes at "
                        f"generation {tag} after flip to {gen}"
                    )
                if tag > gen:
                    return (
                        f"stale-generation: slot {r.slot} tagged {tag} "
                        f"ahead of engine generation {gen}"
                    )
            if r.emitted < r.emitted_seen:
                return (
                    f"lost-stream: request {i} rewound emitted tokens "
                    f"({r.emitted_seen} -> {r.emitted})"
                )
            if self.strict and r.emitted > self.target_tokens:
                return (
                    f"lost-stream: request {i} emitted past its target "
                    f"({r.emitted} > {self.target_tokens})"
                )
            if self.strict and r.phase == "done":
                if r.emitted != self.target_tokens:
                    return (
                        f"lost-stream: request {i} completed with "
                        f"{r.emitted}/{self.target_tokens} tokens"
                    )
            if r.readmits > r.preempts:
                return (
                    f"readmit-accounting: request {i} re-admitted "
                    f"{r.readmits}x for {r.preempts} preemptions"
                )
            if r.phase == "done" and r.readmits != r.preempts:
                return (
                    f"readmit-accounting: request {i} completed with a "
                    f"preemption never re-admitted"
                )
        return None


class StaleSwapRequestModel(RequestModel):
    """Seeded bug: the flip updates the engine generation but leaves
    resident slots' generation tags untouched — a mid-stream request
    keeps decoding against the pre-swap parameters."""

    name = "request-stale-swap"

    def _flip_tags(self, reqs, tags, gen):
        return list(tags)


# ---------------------------------------------------------------------------
# (c) membership epoch pin/advance
# ---------------------------------------------------------------------------


class MembershipModel:
    """Abstract MembershipController: round actors pin the current
    epoch and complete against it; advancer actors advance the epoch
    and then feed the membership gauge from their (possibly stale)
    view — the feed claim (``_fed_epoch``) makes the gauge monotone
    no matter how feeds interleave with further advances."""

    name = "membership-epoch"
    subject = "consensusml_tpu/swarm/membership.py"

    def __init__(
        self,
        n_rounds: int = 2,
        n_advancers: int = 2,
        max_epoch: int = 3,
        claimed: bool = True,
    ):
        self.n_rounds = n_rounds
        self.n_advancers = n_advancers
        self.max_epoch = max_epoch
        self.claimed = claimed

    def initial(self):
        # (epoch, retained, pins, round_pin, pending_feed, fed, fed_seen)
        return (
            0,
            frozenset({0}),
            (),
            (-1,) * self.n_rounds,
            (-1,) * self.n_advancers,
            0,
            0,
        )

    def _pin_count(self, pins, e: int) -> int:
        return dict(pins).get(e, 0)

    def _with_pin(self, pins, e: int, delta: int):
        d = dict(pins)
        d[e] = d.get(e, 0) + delta
        if d[e] == 0:
            del d[e]
        return tuple(sorted(d.items()))

    def labels(self, state):
        epoch, retained, pins, round_pin, pending, fed, _seen = state
        for a in range(self.n_rounds):
            if round_pin[a] < 0:
                yield ("pin", a)
            else:
                yield ("complete", a)
        for v in range(self.n_advancers):
            if pending[v] < 0 and epoch < self.max_epoch:
                yield ("advance", v)
            if pending[v] >= 0:
                yield ("feed", v)

    def apply(self, state, label):
        epoch, retained, pins, round_pin, pending, fed, seen = state
        round_pin = list(round_pin)
        pending = list(pending)
        op = label[0]

        if op == "pin":
            a = label[1]
            _need(round_pin[a] < 0, f"round {a} already holds a pin")
            round_pin[a] = epoch
            pins = self._with_pin(pins, epoch, +1)
        elif op == "complete":
            a = label[1]
            e = round_pin[a]
            _need(e >= 0, f"round {a} holds no pin")
            round_pin[a] = -1
            pins = self._with_pin(pins, e, -1)
            if e != epoch and self._pin_count(pins, e) == 0:
                retained = retained - {e}
        elif op == "advance":
            v = label[1]
            _need(pending[v] < 0, f"advancer {v} has an unfed epoch")
            _need(epoch < self.max_epoch, "epoch bound reached")
            new = epoch + 1
            # retired views survive only while pinned
            retained = frozenset(
                {new} | {e for e in retained if self._pin_count(pins, e) > 0}
            )
            epoch = new
            pending[v] = new
        elif op == "feed":
            v = label[1]
            e = pending[v]
            _need(e >= 0, f"advancer {v} has nothing to feed")
            pending[v] = -1
            fed = self._feed(fed, e)
        else:
            raise IllegalAction(f"unknown action {op!r}")

        seen = max(seen, fed)
        return (
            epoch, retained, pins, tuple(round_pin), tuple(pending), fed, seen
        )

    def _feed(self, fed: int, e: int) -> int:
        # the _fed_epoch claim: only a >= epoch may land on the gauge
        return max(fed, e) if self.claimed else e

    def invariant(self, state) -> Optional[str]:
        epoch, retained, pins, round_pin, pending, fed, seen = state
        if fed < seen:
            return (
                f"gauge-regression: membership gauge fed at epoch {fed} "
                f"after already reporting {seen}"
            )
        if fed > epoch:
            return f"gauge-ahead: gauge epoch {fed} > current epoch {epoch}"
        if epoch not in retained:
            return f"retention: current epoch {epoch} not retained"
        for a, e in enumerate(round_pin):
            if e >= 0 and e not in retained:
                return (
                    f"pinned-view-pruned: round {a}'s pinned epoch {e} is "
                    "no longer retrievable"
                )
        return None


class UnclaimedGaugeMembershipModel(MembershipModel):
    """Seeded bug: the gauge feed skips the ``_fed_epoch`` claim, so
    two racing advances can land the OLDER epoch on the gauge last —
    the exact race PR 13 fixed in ``MembershipController._feed_metrics``."""

    name = "membership-stale-gauge"

    def __init__(self, **kw):
        kw.setdefault("claimed", False)
        super().__init__(**kw)


# ---------------------------------------------------------------------------
# the cml-check pass
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """One model in the pass: what to check and how deep.

    ``max_depth=None`` is full reachability: the shipped correct models
    all have FINITE state spaces at their shipped parameters, so the
    pass proves their invariants over the entire reachable space, not
    just a prefix of it (fixtures keep a finite depth to exercise the
    bounded semantics too).
    """

    model: object
    max_depth: Optional[int]
    max_states: int = 300_000
    # seeded-bug fixtures MUST fail; a fixture the checker cannot
    # refute means the detector is broken (PR 15 pattern)
    expect_violation: bool = False


def builtin_specs() -> list:
    """The shipped correct models, exhaustively explored."""
    return [
        ModelSpec(PoolModel(), max_depth=None),
        ModelSpec(RequestModel(), max_depth=None),
        ModelSpec(MembershipModel(), max_depth=None),
    ]


def fixture_specs() -> list:
    """Seeded-bug variants: each must yield a counterexample."""
    return [
        ModelSpec(DoubleFreePoolModel(), max_depth=8, expect_violation=True),
        ModelSpec(StaleSwapRequestModel(), max_depth=8, expect_violation=True),
        ModelSpec(
            UnclaimedGaugeMembershipModel(), max_depth=8,
            expect_violation=True,
        ),
    ]


def _subject_selected(subject: str, roots, repo_root) -> bool:
    if not roots:
        return True
    target = (Path(repo_root) / subject).resolve()
    for r in roots:
        rp = Path(r).resolve()
        if target == rp or rp in target.parents:
            return True
    return False


def run_builtin(
    roots: Optional[Sequence] = None,
    repo_root: Optional[Path] = None,
) -> list:
    """Run pass 8: check every shipped model, then prove the detector
    still detects by requiring a counterexample from every seeded-bug
    fixture. ``roots`` restricts to models whose SUBJECT file lies
    under one of the given paths (the ``--paths`` contract); a
    fixture runs iff its subject is selected."""
    repo_root = repo_root or Path(__file__).resolve().parents[2]
    findings: list = []
    for spec in builtin_specs() + fixture_specs():
        m = spec.model
        if not _subject_selected(m.subject, roots, repo_root):
            continue
        try:
            res = check_model(
                m, max_depth=spec.max_depth, max_states=spec.max_states
            )
        except RuntimeError as e:
            findings.append(
                Finding(
                    pass_name="model",
                    rule="state-space-overflow",
                    path=m.subject,
                    symbol=m.name,
                    detail="overflow",
                    message=f"{m.name}: {e}",
                )
            )
            continue
        if spec.expect_violation:
            if res.ok or not res.trace:
                findings.append(
                    Finding(
                        pass_name="model",
                        rule="detector-broken",
                        path=m.subject,
                        symbol=m.name,
                        detail="no-counterexample",
                        message=(
                            f"{m.name}: seeded-bug model produced no "
                            f"counterexample within depth {spec.max_depth} "
                            "— the model checker is not detecting "
                            "violations"
                        ),
                    )
                )
        elif not res.ok:
            slug = (res.violation or "violation").split(":", 1)[0].strip()
            findings.append(
                Finding(
                    pass_name="model",
                    rule="invariant-violated",
                    path=m.subject,
                    symbol=m.name,
                    detail=slug,
                    message=(
                        f"{m.name}: {res.violation} "
                        f"[trace: {res.format_trace()}]"
                    ),
                    counterexample=tuple(
                        _fmt(l) for l in res.trace
                    ),
                )
            )
    return findings


def _fmt(label) -> str:
    head = str(label[0])
    if len(label) == 1:
        return head
    return head + "(" + ", ".join(repr(a) for a in label[1:]) + ")"
