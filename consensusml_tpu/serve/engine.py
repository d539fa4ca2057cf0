"""The serving engine: one decode loop thread over a slot table.

``Engine`` owns the compiled program families (paged stages from
:mod:`consensusml_tpu.serve.pool.stages` by default, the PR 5 per-slot
path from :mod:`consensusml_tpu.serve.decode` as ``kv_impl="slot"``),
the KV memory (block pool or slot caches), and a single scheduler
thread that interleaves prefill admissions with in-flight decode
(continuous batching, :mod:`consensusml_tpu.serve.batcher`). Clients —
the in-process API, the socket front-end, loadgen — only touch the
bounded submit queue and per-request handles; all device work stays on
the one engine thread, so the jit caches, the cache pytree, and the
slot table need no locking.

Paged mode adds three behaviors on top of the PR 5 loop
(:mod:`consensusml_tpu.serve.pool`):

- slot occupancy is bounded by total live tokens (the block pool), so
  more lanes than ``HBM / max_len`` can be in flight under a heavy-tail
  length mix; on block exhaustion the youngest stream is preempted by
  RECOMPUTE (its blocks free, its prompt+generated-so-far re-enqueues at
  the head of the line — tokens already streamed stand, nothing drops);
- prefill admission is budgeted per tick (:class:`.pool.stages.
  AdmissionScheduler`): the decode step runs every tick, so a burst of
  long prompts spreads over ticks instead of stalling every stream;
- :meth:`watch` arms the drain-free hot swap: a new artifact generation
  flips params (and every resident slot's generation tag) between two
  decode steps with zero dropped streams and zero recompiles.

The admission / preempt-readmit / hot-swap protocol this loop
implements is model-checked over every interleaving by the
``request-lifecycle`` abstraction (cml-check pass 8,
:mod:`consensusml_tpu.analysis.protocol_models`): slots never aliased,
per-stream generations monotone, no stream lost across a flip, a
preempted stream re-admitted exactly once as a continuation. The
engine's own wide-event request traces double as the conformance
recording — a real preempt + hot-swap run must replay as a valid model
path (``tests/test_model_check.py``).

Sampling is in-jit and per-request (:mod:`consensusml_tpu.serve.
sampling`): ``submit(temperature=, top_p=, seed=, eos_id=)`` threads the
triple through the compiled steps as data — greedy is the
``temperature = 0`` case of the same executables, and a stream replays
deterministically from its echoed seed. ``Engine(...,
spec_decode=SpecConfig(model=draft, params=..., k=...))`` switches the
per-token decode step for the speculative round
(:mod:`consensusml_tpu.serve.pool.spec`): the draft proposes ``k``
tokens per lane, ONE fused target forward verifies every lane's window,
and rejection-sampling acceptance keeps the output distribution exactly
target-only sampling (1 to ``k + 1`` tokens per lane per round).

SLO instrumentation (docs/serving.md, docs/observability.md): every
request path stage lands on the ``consensusml_serve_*`` /
``consensusml_pool_*`` metric families (TTFT, inter-token latency, queue
depth, batch occupancy, block occupancy, evictions, swaps, tokens/s) and
``serve.prefill`` / ``serve.decode_step`` spans.

The steady-state contract: after :meth:`warmup` (one decode compile +
one prefill compile per prompt bucket), serving ANY admission order of
ANY mix of prompt lengths performs ZERO further compiles —
:meth:`compile_counts` exposes the jit cache sizes so tests assert it
(``tests/test_serve.py::test_engine_serves_8_concurrent_streams_zero_recompiles``),
and cml-check's jaxpr contracts pin the step-over-step program hash per
stage.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Any, Sequence

import numpy as np

__all__ = ["ServeConfig", "Engine", "load_engine"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine geometry + admission policy (all fixed at construction —
    shapes are compile-time)."""

    num_slots: int = 8  # decode batch lanes
    max_len: int = 0  # cache length; 0 = the model's max_len
    queue_depth: int = 64  # bounded admission queue
    max_new_tokens: int = 16  # default per-request generation cap
    eos_id: int | None = None  # default stop token; submit() can override
    idle_wait_s: float = 0.02  # scheduler block when nothing is in flight
    # -- default sampling (submit() overrides per request) ---------------
    temperature: float = 0.0  # 0 = greedy argmax (the original path)
    top_p: float = 1.0  # nucleus mass; 1.0 = full distribution
    # -- paged KV pool (serve/pool/; "slot" = the PR 5 per-slot rows) ----
    kv_impl: str = "paged"  # "paged" | "slot"
    block_size: int = 8  # tokens per physical KV block (must divide max_len)
    num_blocks: int = 0  # pool size; 0 = num_slots * max_len/block_size + 1
    prefill_budget: int = 0  # prefill tokens per tick; 0 = one max_len bucket
    # refcounted prefix-block sharing (serve/pool/prefix.py): matched
    # block-aligned prompt prefixes map into the slot's table and only
    # the unshared suffix prefills. Bit-exact vs the unshared path, so
    # the switch is a perf/memory knob, never a quality one. Opt-in:
    # warmup compiles one extra prefill executable per suffix bucket
    # (plus draft twins under spec decode), so engines that never see
    # repeated prompts shouldn't pay that compile time.
    prefix_cache: bool = False
    # paged-attention tier (models/paged_attention.py): "gather" = the
    # two-step reference, and the default: the compiled kernel does not
    # lower on the chip yet (Mosaic refuses its rank-4 einsum — see the
    # module docstring). "auto" resolves via resolve_attention_impl —
    # compiled pallas on TPU (today: the compiler's error at warmup),
    # the interpreter elsewhere, NEVER silently the reference. All impls
    # are bit-exact, so switching tiers never changes a stream.
    attn_impl: str = "gather"  # "gather" | "jnp" | "interpret" | "pallas" | "auto"


class Engine:
    """In-process serving engine over an exported consensus artifact.

    ``Engine(model, params)`` then :meth:`submit` from any thread;
    :meth:`score` is the prefill-only batch scoring path (golden parity
    with the evaluator's consensus-mean model). Use as a context manager
    or call :meth:`shutdown` — it drains in-flight work by default.
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        config: ServeConfig | None = None,
        *,
        spec_decode: Any = None,
    ):
        import jax

        from consensusml_tpu.obs import get_registry, get_tracer
        from consensusml_tpu.serve import decode as D
        from consensusml_tpu.serve.batcher import Request, RequestHandle, SlotTable

        self.config = cfg = config or ServeConfig()
        self._dm = dm = D.DecodeModel.wrap(model)
        self.max_len = cfg.max_len or dm.max_len
        if not 0 < self.max_len <= dm.max_len:
            raise ValueError(
                f"max_len {self.max_len} outside (0, {dm.max_len}] "
                "(the model's position table bounds the cache)"
            )
        if cfg.num_slots < 1:
            raise ValueError(f"num_slots must be positive, got {cfg.num_slots}")
        if cfg.kv_impl not in ("paged", "slot"):
            raise ValueError(
                f"kv_impl must be 'paged' or 'slot', got {cfg.kv_impl!r}"
            )
        self.paged = cfg.kv_impl == "paged"
        from consensusml_tpu.models.paged_attention import (
            resolve_attention_impl,
        )

        # resolve ONCE at construction — "auto" means the kernel path
        # (pallas on TPU, interpret elsewhere), and the resolved value
        # is what stats()/the serve CLI report, so the executed tier is
        # always the reported tier
        self.attn_impl = resolve_attention_impl(cfg.attn_impl)
        if self.attn_impl != "gather" and not self.paged:
            raise ValueError(
                f"attn_impl={self.attn_impl!r} requires kv_impl='paged' "
                "(the fused kernels read the block pool; the slot path "
                "keeps its own parity baseline)"
            )
        self._params = jax.device_put(params)
        if self.paged:
            from consensusml_tpu.serve import pool as P

            # paged buckets start at the block size so every bucket is
            # block-aligned (prefill scatters whole blocks)
            self.buckets = D.prefill_buckets(
                self.max_len, smallest=max(8, cfg.block_size)
            )
            misaligned = [b for b in self.buckets if b % cfg.block_size]
            if misaligned:
                raise ValueError(
                    f"block_size {cfg.block_size} does not divide prefill "
                    f"bucket(s) {misaligned} (buckets "
                    f"{list(self.buckets)} for max_len {self.max_len}); "
                    "the prefill scatter chunks whole blocks — use a "
                    "power-of-two block_size, or one >= 8 that divides "
                    "max_len"
                )
            self._pool = P.BlockPool(
                cfg.num_slots, self.max_len, cfg.block_size, cfg.num_blocks
            )
            self._pages = P.init_pages(
                dm, self._pool.num_blocks, cfg.block_size
            )
            self._prefill_fn = P.make_paged_prefill_fn(dm)
            self._decode_fn = P.make_paged_decode_fn(
                dm, attn_impl=self.attn_impl
            )
            self._sched = P.AdmissionScheduler(
                cfg.prefill_budget or self.max_len
            )
            # content-addressed prefix sharing (serve/pool/prefix.py):
            # the index invalidates eagerly on block reuse (reuse_hook)
            # and freed-but-indexed blocks park at the bottom of the
            # free stack (cached_hook) so cached prefixes die last
            self._prefix = None
            if cfg.prefix_cache:
                self._prefix = P.PrefixIndex(cfg.block_size)
                self._pool.reuse_hook = self._prefix.invalidate_block
                self._pool.cached_hook = self._prefix.cached
                self._prefix_prefill_fn = P.make_prefix_prefill_fn(
                    dm, attn_impl=self.attn_impl
                )
        else:
            self.buckets = D.prefill_buckets(self.max_len)
            self._pool = None
            self._cache = D.init_cache(dm, cfg.num_slots, self.max_len)
            self._prefill_fn = D.make_prefill_fn(dm)
            self._decode_fn = D.make_decode_fn(dm)
            self._sched = None
            self._prefix = None
        self._score_fn = D.make_score_fn(dm)
        # -- speculative decode (serve/pool/spec.py): a draft model over
        # its own smaller pages, one fused k-verify on the target -------
        self.spec = spec_decode
        if self.spec is not None:
            from consensusml_tpu.serve import pool as P

            if not self.paged:
                raise ValueError(
                    "spec_decode requires kv_impl='paged' (the k-verify "
                    "is a widening of the paged decode stage)"
                )
            sd = D.DecodeModel.wrap(self.spec.model)
            if sd.vocab_size != dm.vocab_size:
                raise ValueError(
                    f"draft vocab {sd.vocab_size} != target vocab "
                    f"{dm.vocab_size}; speculative acceptance compares "
                    "distributions over one shared vocabulary"
                )
            if sd.max_len < self.max_len:
                raise ValueError(
                    f"draft max_len {sd.max_len} < engine max_len "
                    f"{self.max_len}; the draft must reach every "
                    "position the target serves"
                )
            self._draft_dm = sd
            self._draft_params = jax.device_put(self.spec.params)
            # the draft's pages share the pool's BLOCK TABLE (identical
            # logical geometry: same blocks, same offsets) but are their
            # own — smaller — arrays, sized by the draft architecture
            self._draft_pages = P.init_pages(
                sd, self._pool.num_blocks, cfg.block_size
            )
            self._draft_prefill_fn = P.make_paged_prefill_fn(sd)
            self._propose_fn = P.make_draft_propose_fn(
                sd, self.spec.k, attn_impl=self.attn_impl
            )
            self._verify_fn = P.make_verify_fn(
                dm, self.spec.k, attn_impl=self.attn_impl
            )
            self._spec_extra_cols = (
                P.spec_table_cols(
                    self._pool.blocks_per_slot, cfg.block_size, self.spec.k
                )
                - self._pool.blocks_per_slot
            )
            if self._prefix is not None:
                # draft pages share the pool's block table, so a prefix
                # hit skips the DRAFT prefill too — same program family
                # over the draft's own pages
                self._draft_prefix_prefill_fn = P.make_prefix_prefill_fn(
                    sd, attn_impl=self.attn_impl
                )
        self._Request, self._RequestHandle = Request, RequestHandle

        self._queue: "queue.Queue" = queue.Queue(cfg.queue_depth)
        # evicted continuations re-enter here, ahead of fresh arrivals
        # (engine thread appends/pops; submit's lost-race sweep may drain)
        self._requeue: "collections.deque" = collections.deque()
        self._table = SlotTable(cfg.num_slots)
        self._generation = 0  # artifact generation (load_engine sets it)
        self._watcher = None
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._drained = threading.Event()
        # readiness for fleet placement: set when a warmup() completes,
        # so a router's /healthz poll never routes streams onto a
        # replica still paying multi-second compiles (docs/fleet.md)
        self._warmed = threading.Event()

        from consensusml_tpu.obs import get_request_registry

        self._tracer = get_tracer()
        # request-scoped traces: every request's submit → admission →
        # prefill → decode → completion story (obs/requests.py; the
        # flight recorder dumps this registry on a serving crash)
        self._rt = get_request_registry()
        from consensusml_tpu.obs.events import get_wide_event_log

        # wide-event accounting (obs/events.py): ONE structured record
        # per terminal request, joining the trace with token counts,
        # block-seconds, and ledger-derived cost — the engine is a
        # producer, so it ARMS the global log (dump paths only peek)
        self._events = get_wide_event_log()
        self._cost_ledger = None  # set by register_costs()
        reg = get_registry()
        self._registry = reg
        # per-tenant labeled children (consensusml_tenant_*), created
        # lazily on a tenant's first terminal event and cached — the
        # registry dedupes by key, the cache just skips its lock
        self._tenant_children: dict[str, dict[str, Any]] = {}
        self._m_requests = reg.counter(
            "consensusml_serve_requests_total", "requests accepted by submit()"
        )
        self._m_rejected = reg.counter(
            "consensusml_serve_rejected_total",
            "requests rejected (bounded queue full or engine draining)",
        )
        self._m_completed = reg.counter(
            "consensusml_serve_completed_total", "requests served to completion"
        )
        self._m_tokens = reg.counter(
            "consensusml_serve_tokens_total", "tokens generated (prefill + decode)"
        )
        from consensusml_tpu.obs.metrics import DEFAULT_SLO_BUCKETS

        self._m_ttft = reg.histogram(
            "consensusml_serve_ttft_seconds",
            "time to first token: arrival -> first generated token",
            buckets=DEFAULT_SLO_BUCKETS,
        )
        self._m_intertoken = reg.histogram(
            "consensusml_serve_intertoken_seconds",
            "per-decode-step latency (== inter-token gap for resident slots)",
            buckets=DEFAULT_SLO_BUCKETS,
        )
        self._m_prefill = reg.histogram(
            "consensusml_serve_prefill_seconds", "prefill forward wall time",
            buckets=DEFAULT_SLO_BUCKETS,
        )
        self._m_queue = reg.gauge(
            "consensusml_serve_queue_depth", "requests waiting for a slot"
        )
        self._m_occupancy = reg.gauge(
            "consensusml_serve_batch_occupancy",
            "active decode slots / num_slots (sampled per step)",
        )
        self._m_tps = reg.gauge(
            "consensusml_serve_tokens_per_sec",
            "decode throughput: active slots / step wall time (sampled)",
        )
        self._m_generation = reg.gauge(
            "consensusml_serve_generation",
            "artifact generation currently serving",
        )
        self._m_swaps = reg.counter(
            "consensusml_serve_swaps_total",
            "drain-free hot swaps applied (params flipped between steps)",
        )
        self._m_evictions = reg.counter(
            "consensusml_pool_evictions_total",
            "streams preempted by recompute on block-pool exhaustion",
        )
        # loop liveness: set every engine-thread iteration — the
        # staleness signal the default alert ruleset's serve-loop-stale
        # rule (and a fleet router's /healthz poll) watches; a wedged
        # decode step or a dead engine thread stops it moving
        self._m_loop_heartbeat = reg.gauge(
            "consensusml_serve_loop_heartbeat_seconds",
            "unix time of the engine loop's latest iteration (liveness; "
            "staleness means the serving thread is wedged or dead)",
        )
        self._m_loop_heartbeat.set(time.time())
        if self.spec is not None:
            self._m_spec_rounds = reg.counter(
                "consensusml_spec_rounds_total",
                "speculative rounds (one draft scan + one fused verify)",
            )
            self._m_spec_proposed = reg.counter(
                "consensusml_spec_proposed_total",
                "draft tokens proposed across all live lanes",
            )
            self._m_spec_accepted = reg.counter(
                "consensusml_spec_accepted_total",
                "draft tokens accepted by the target's rejection sampler",
            )
            self._m_spec_rate = reg.gauge(
                "consensusml_spec_acceptance_rate",
                "accepted / proposed over the engine lifetime (sampled "
                "per verify round) — the k-tuning signal",
            )
        # live HBM tagging (obs/memviz.py): the engine's big resident
        # consumers as first-class gauges, so per-engine KV headroom is
        # a signal a fleet router can place traffic on (ROADMAP item 2)
        # and the three-way reconciliation can attribute serving bytes
        self._params_nbytes = sum(
            int(x.nbytes) for x in jax.tree.leaves(self._params)
        )
        self._m_params_bytes = reg.gauge(
            "consensusml_serve_params_bytes",
            "device bytes of the serving params tree (current generation)",
        )
        self._m_params_bytes.set(self._params_nbytes)
        if self.paged:
            self._m_blocks_free = reg.gauge(
                "consensusml_pool_blocks_free",
                "free physical KV blocks (trash block excluded)",
            )
            self._m_block_occ = reg.gauge(
                "consensusml_pool_block_occupancy",
                "allocated blocks / usable blocks (sampled per step)",
            )
            self._m_blocks_free.set(self._pool.free_blocks)
            self._m_block_occ.set(0.0)
            pool_bytes = sum(
                int(x.nbytes) for x in jax.tree.leaves(self._pages)
            )
            self._block_nbytes = pool_bytes // max(self._pool.num_blocks, 1)
            if self.spec is not None:
                self._draft_block_nbytes = sum(
                    int(x.nbytes) for x in jax.tree.leaves(self._draft_pages)
                ) // max(self._pool.num_blocks, 1)
            self._m_pool_hbm = reg.gauge(
                "consensusml_pool_hbm_bytes",
                "device bytes held by the paged KV block pool (all layers)",
            )
            self._m_pool_hbm.set(pool_bytes)
            self._m_pool_hbm_free = reg.gauge(
                "consensusml_pool_hbm_free_bytes",
                "KV bytes still allocatable (free blocks x per-block "
                "bytes) — the per-engine serving HBM headroom signal",
            )
            self._m_pool_hbm_free.set(
                self._pool.free_blocks * self._block_nbytes
            )
        if self._prefix is not None:
            self._m_prefix_hits = reg.counter(
                "consensusml_prefix_hits_total",
                "admissions that adopted at least one indexed prefix block",
            )
            self._m_prefix_misses = reg.counter(
                "consensusml_prefix_misses_total",
                "admissions that prefilled from scratch (no indexed prefix)",
            )
            self._m_prefix_hit_blocks = reg.counter(
                "consensusml_prefix_hit_blocks_total",
                "KV blocks mapped in from the prefix index instead of "
                "prefilled",
            )
            self._m_prefix_cow_copies = reg.counter(
                "consensusml_prefix_cow_copies_total",
                "copy-on-write block copies (full-match divergence: the "
                "last shared block copied to a fresh page in-jit)",
            )
            self._m_prefix_bytes_saved = reg.counter(
                "consensusml_prefix_bytes_saved_total",
                "KV bytes NOT materialized thanks to prefix sharing "
                "(adopted blocks x per-block bytes, draft pages included)",
            )
            self._m_prefix_entries = reg.gauge(
                "consensusml_prefix_entries",
                "live prefix-index entries (current generation)",
            )
            self._m_prefix_shared_blocks = reg.gauge(
                "consensusml_prefix_shared_blocks",
                "physical blocks currently held by more than one stream",
            )

        # host-side SLO accumulators for loadgen percentiles —
        # BOUNDED rings (a serving process lives for weeks; the Prometheus
        # histograms carry the full-lifetime distributions, these lists
        # only feed stats() percentiles over the recent window)
        self._ttfts: "collections.deque[float]" = collections.deque(maxlen=4096)
        self._step_times: "collections.deque[float]" = collections.deque(
            maxlen=4096
        )
        self._occupancy_sum = 0.0
        self._block_occupancy_sum = 0.0
        self._decode_steps = 0
        self._tokens_out = 0
        self._tokens_in = 0  # prompt tokens of first-time admissions
        self._decode_time_s = 0.0
        self._evictions = 0
        self._swaps = 0
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_tokens = 0  # emitted by verify rounds (prefill excluded)
        # prefix-cache host accumulators (mirror the counters for
        # stats() reads without registry scrapes); the tokens-
        # computed counter runs on EVERY paged engine so a prefix-off
        # baseline reports the same field
        self._prefill_tokens_computed = 0
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_hit_blocks = 0
        self._prefix_cow_copies = 0
        self._prefix_bytes_saved = 0
        self._error: BaseException | None = None

        self._thread = threading.Thread(
            target=self._loop, name="serve-engine", daemon=True
        )
        self._thread.start()

    # -- client API ---------------------------------------------------------

    def submit(
        self,
        ids: Sequence[int],
        max_new_tokens: int | None = None,
        *,
        block: bool = True,
        timeout: float | None = None,
        trace: Any = None,
        temperature: float | None = None,
        top_p: float | None = None,
        seed: int | None = None,
        eos_id: int | None = None,
        tenant: str | None = None,
    ):
        """Enqueue one request; returns a ``RequestHandle``.

        ``trace`` is an optional :class:`~consensusml_tpu.obs.
        TraceContext` the client minted (loadgen / the line-JSON
        protocol); without one the engine mints its own, so EVERY
        accepted request has a recorded trace (docs/observability.md
        "Request tracing").

        ``temperature``/``top_p``/``seed`` sample THIS request
        (defaults: the ``ServeConfig`` values / a freshly minted seed);
        the resolved triple is echoed on the ``GenResult`` so the stream
        replays deterministically — same seed, same tokens, whatever
        else shares the batch. ``eos_id`` overrides the engine-wide stop
        token per request (the two causal-LM families use different eos
        ids; ``None`` keeps the config default).

        ``tenant`` labels THIS request for per-workload attribution
        (docs/observability.md "Wide events & tenant accounting"):
        it rides the trace, the terminal wide event, the
        ``consensusml_tenant_*`` labeled families, and the echoed
        ``GenResult``. ``None``/empty means ``"default"``; the label is
        sanitized at this boundary (obs/events.py).

        Raises ``queue.Full`` when the bounded queue is full (with
        ``block=False`` or after ``timeout``) and ``RuntimeError`` once
        the engine is draining — both count on
        ``consensusml_serve_rejected_total``.
        """
        max_new = (
            self.config.max_new_tokens if max_new_tokens is None else max_new_tokens
        )
        temp = self.config.temperature if temperature is None else float(temperature)
        tp = self.config.top_p if top_p is None else float(top_p)
        if temp < 0:
            raise ValueError(f"temperature must be >= 0, got {temp}")
        if not 0 < tp <= 1:
            raise ValueError(f"top_p must be in (0, 1], got {tp}")
        if seed is None:
            import os as _os

            # greedy lanes never consume the seed; sampled lanes get a
            # fresh one so independent requests draw independent streams
            seed = (
                0 if temp == 0
                else int.from_bytes(_os.urandom(4), "little")
            )
        seed = int(seed) & 0xFFFFFFFF
        eos = self.config.eos_id if eos_id is None else int(eos_id)
        if self._draining.is_set() or self._stop.is_set():
            self._m_rejected.inc()
            if self._error is not None:
                raise RuntimeError(
                    f"engine died on {type(self._error).__name__}: "
                    f"{self._error}"
                ) from self._error
            raise RuntimeError("engine is draining/closed; not accepting requests")
        if len(ids) < 1:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be positive, got {max_new}")
        if len(ids) + max_new > self.max_len:
            raise ValueError(
                f"prompt ({len(ids)}) + max_new_tokens ({max_new}) exceeds "
                f"the cache length {self.max_len}; shorten one or build the "
                "engine with a larger ServeConfig.max_len"
            )
        from consensusml_tpu.obs import TraceContext
        from consensusml_tpu.obs.events import sanitize_tenant

        tenant_s = sanitize_tenant(tenant)
        ctx = trace if trace is not None else TraceContext.mint("srv")
        handle = self._RequestHandle(len(ids))
        req = self._Request(
            list(map(int, ids)), max_new, handle, ctx=ctx,
            temperature=temp, top_p=tp, seed=seed, eos_id=eos,
            tenant=tenant_s,
        )
        self._rt.start(
            ctx, len(ids), max_new_tokens=max_new,
            generation=self._generation, tenant=tenant_s,
        )
        try:
            self._queue.put(req, block=block, timeout=timeout)
        except queue.Full:
            self._m_rejected.inc()
            self._rt.finish(ctx.request_id, "rejected", detail="queue_full")
            raise
        if self._drained.is_set():
            # lost the race against loop exit: the put landed after the
            # loop's final cancellation sweep and nothing will ever
            # service it — sweep again ourselves and refuse
            self._cancel_queued()
            self._m_rejected.inc()
            raise RuntimeError(
                "engine is draining/closed; not accepting requests"
            )
        self._m_requests.inc()
        self._m_queue.set(self._queue.qsize())
        return handle

    def score(self, ids) -> Any:
        """Prefill-only batch scoring: f32 logits ``(B, S, V)`` for a full
        token batch — the forward is traced identically to the held-out
        evaluator's, so an exported artifact scores BIT-EXACTLY what
        ``evaluate()``'s mean model scores (the golden parity test)."""
        import jax.numpy as jnp

        return self._score_fn(self._params, jnp.asarray(ids, jnp.int32))

    def warmup(self, buckets: Sequence[int] | None = None) -> dict[str, int]:
        """Compile the steady-state program set: the decode step plus one
        prefill per prompt bucket. Returns :meth:`compile_counts`.

        Runs on the caller's thread against a THROWAWAY cache of the same
        shapes (jit caches key on shape, so the executables are shared
        with the live path) — the engine thread may already be serving,
        and warmup must not mutate (or donate away) the cache it is
        using. In paged mode the throwaway pool's all-zero block table
        routes every warmup write into the trash block.

        The program FAMILIES compile on parallel chains (XLA releases
        the GIL): the full-prefill chain, the prefix-suffix chain, and
        their draft twins each thread a private throwaway cache through
        their bucket ladder, so arming the prefix cache (or a draft)
        widens warmup instead of lengthening it — wall time stays ~the
        longest single chain. Transient cost: one extra cache per
        ACTIVE chain (a prefix-off, non-speculative engine allocates
        exactly one, as before); memory-tight deployments can stage via
        repeated ``warmup(buckets=[b])`` calls.
        """
        import jax.numpy as jnp

        from consensusml_tpu.serve import decode as D

        s = self.config.num_slots
        toks = jnp.zeros((s,), jnp.int32)
        samp = (  # per-slot sampling arrays (all-greedy warms the same
            jnp.zeros((s,), jnp.float32),  # executable sampled lanes use)
            jnp.ones((s,), jnp.float32),
            jnp.zeros((s,), jnp.uint32),
        )
        samp1 = (jnp.float32(0.0), jnp.float32(1.0), jnp.uint32(0))
        if self.paged:
            from consensusml_tpu.serve import pool as P

            bs = self.config.block_size
            bks = list(buckets if buckets is not None else self.buckets)
            trash = jnp.int32(P.TRASH_BLOCK)

            def chain_target():
                pages = P.init_pages(self._dm, self._pool.num_blocks, bs)
                for b in bks:
                    ids = jnp.zeros((1, b), jnp.int32)
                    row = jnp.zeros((b // bs,), jnp.int32)
                    _tok, _logits, pages = self._prefill_fn(
                        self._params, pages, ids, jnp.int32(1), row, *samp1
                    )
                if self.spec is None:
                    # a speculative engine never runs the one-token
                    # decode step (_spec_step replaces it) — don't burn
                    # a compile on an executable that will not execute
                    table = jnp.zeros(
                        (s, self._pool.blocks_per_slot), jnp.int32
                    )
                    self._decode_fn(
                        self._params, pages, table, toks,
                        jnp.zeros_like(toks), *samp,
                    )
                else:
                    stable = jnp.zeros(
                        (s, self._pool.blocks_per_slot + self._spec_extra_cols),
                        jnp.int32,
                    )
                    dpg = P.init_pages(
                        self._draft_dm, self._pool.num_blocks, bs
                    )
                    props, q_sel, q_probs, _dpg = self._propose_fn(
                        self._draft_params, dpg, stable, toks,
                        jnp.zeros_like(toks), *samp,
                    )
                    self._verify_fn(
                        self._params, pages, stable, toks, props, q_sel,
                        q_probs, jnp.zeros_like(toks), *samp,
                    )

            def chain_prefix(dm, params, fn):
                # the prefix path's suffix buckets walk the SAME ladder
                # — compile each so a hit never compiles on the serving
                # thread (all-trash row + trash COW pair = no-op writes)
                pages = P.init_pages(dm, self._pool.num_blocks, bs)
                for b in bks:
                    ids = jnp.zeros((1, b), jnp.int32)
                    prow = jnp.zeros(
                        (self._pool.blocks_per_slot + b // bs,), jnp.int32
                    )
                    _t, _l, pages = fn(
                        params, pages, ids, jnp.int32(1), jnp.int32(0),
                        prow, trash, trash, *samp1,
                    )

            def chain_draft():
                dpages = P.init_pages(
                    self._draft_dm, self._pool.num_blocks, bs
                )
                for b in bks:
                    ids = jnp.zeros((1, b), jnp.int32)
                    row = jnp.zeros((b // bs,), jnp.int32)
                    _t, _l, dpages = self._draft_prefill_fn(
                        self._draft_params, dpages, ids, jnp.int32(1),
                        row, *samp1,
                    )

            chains = [chain_target]
            if self._prefix is not None:
                chains.append(
                    lambda: chain_prefix(
                        self._dm, self._params, self._prefix_prefill_fn
                    )
                )
            if self.spec is not None:
                chains.append(chain_draft)
                if self._prefix is not None:
                    chains.append(
                        lambda: chain_prefix(
                            self._draft_dm, self._draft_params,
                            self._draft_prefix_prefill_fn,
                        )
                    )
            if len(chains) == 1:
                chains[0]()
            else:
                from concurrent.futures import ThreadPoolExecutor

                with ThreadPoolExecutor(len(chains)) as ex:
                    futs = [ex.submit(c) for c in chains]
                    for f in futs:
                        f.result()  # re-raise any chain's failure here
            self._warmed.set()
            return self.compile_counts()
        cache = D.init_cache(self._dm, self.config.num_slots, self.max_len)
        for b in buckets if buckets is not None else self.buckets:
            ids = jnp.zeros((1, b), jnp.int32)
            _tok, _logits, cache = self._prefill_fn(
                self._params, cache, ids, jnp.int32(1), jnp.int32(0),
                *samp1,
            )
        self._decode_fn(
            self._params, cache, toks, jnp.zeros_like(toks), *samp
        )
        self._warmed.set()
        return self.compile_counts()

    def watch(self, path: str, poll_s: float = 0.25):
        """Arm the drain-free hot swap: poll ``path`` for a new artifact
        generation, stage it off-thread, flip between decode steps
        (:mod:`consensusml_tpu.serve.pool.hotswap`). On a speculative
        engine the watcher also stages the DRAFT artifact riding in the
        ``draft/`` subdirectory (``export_draft``) under the same
        generation counter, so target and draft flip together. Returns
        the watcher."""
        from consensusml_tpu.serve.pool import GenerationWatcher

        if self._watcher is not None:
            raise RuntimeError("engine is already watching an artifact dir")
        self._watcher = GenerationWatcher(
            path, current_generation=self._generation, poll_s=poll_s,
            stage_draft=self.spec is not None,
        )
        return self._watcher

    @property
    def generation(self) -> int:
        """Artifact generation currently serving (0 = direct params)."""
        return self._generation

    def _maybe_swap(self) -> None:
        """Engine-thread flip of a staged generation (between steps).

        The staged tree must match the live tree leaf-for-leaf — same
        structure, shapes, dtypes — or the compiled programs would
        recompile (or worse, serve garbage); a mismatch is rejected and
        counted, and the engine keeps serving the current generation.
        """
        if self._watcher is None:
            return
        sw = self._watcher.take()
        if sw is None:
            return
        import jax

        def _tree_matches(live, staged):
            if jax.tree.structure(live) != jax.tree.structure(staged):
                return False
            return all(
                a.shape == b.shape and a.dtype == b.dtype
                for a, b in zip(jax.tree.leaves(live), jax.tree.leaves(staged))
            )

        ok = _tree_matches(self._params, sw.params)
        if ok and self.spec is not None and sw.draft_params is not None:
            # the draft flips with the target or not at all — a target
            # from generation g+1 verifying a draft from g would still
            # be distribution-correct, but the staged PAIR is what the
            # export protocol promised, so a torn pair is rejected whole
            ok = _tree_matches(self._draft_params, sw.draft_params)
        if not ok:
            self._watcher.reject(sw)  # roll back: a fixed same-gen
            return  # re-export must be stageable
        self._params = sw.params
        if self.spec is not None and sw.draft_params is not None:
            self._draft_params = sw.draft_params
        self._generation = sw.generation
        self._params_nbytes = sum(
            int(x.nbytes) for x in jax.tree.leaves(sw.params)
        )
        self._m_params_bytes.set(self._params_nbytes)
        for _i, slot in self._table.active:
            slot.generation = sw.generation
            # a mid-stream generation flip is part of the request's
            # story: prefix decoded under g, suffix under g+1
            self._rt.event(
                self._rid(slot.request), "hotswap", generation=sw.generation
            )
        self._swaps += 1
        self._m_swaps.inc()
        self._m_generation.set(sw.generation)
        if self._prefix is not None:
            # stale-generation entries are already unreachable (lookups
            # key on the current generation); this reclaims them and
            # lets the pool stop favoring their blocks as cached
            self._prefix.drop_stale(sw.generation)
            self._m_prefix_entries.set(len(self._prefix))

    def compile_counts(self) -> dict[str, int]:
        """Jit-cache entry counts per program family — the
        zero-recompile-after-warmup assertion reads this."""
        out = {}
        fams = [
            ("prefill", self._prefill_fn),
            ("decode", self._decode_fn),
            ("score", self._score_fn),
        ]
        if self._prefix is not None:
            fams.append(("prefix_prefill", self._prefix_prefill_fn))
        if self.spec is not None:
            fams += [
                ("draft_prefill", self._draft_prefill_fn),
                ("propose", self._propose_fn),
                ("verify", self._verify_fn),
            ]
            if self._prefix is not None:
                fams.append(
                    ("draft_prefix_prefill", self._draft_prefix_prefill_fn)
                )
        for name, fn in fams:
            size = getattr(fn, "_cache_size", None)
            out[name] = int(size()) if size is not None else -1
        return out

    def register_costs(self, ledger: Any = None) -> dict[str, Any]:
        """Register every serving executable in the cost ledger
        (:mod:`consensusml_tpu.obs.costs`): one prefill row per prompt
        bucket, the one decode row, and the hot-swap staging transfer.

        AOT-lowers with shape structs mirroring the live call shapes —
        nothing executes, no cache is mutated, and the zero-recompile
        contract's :meth:`compile_counts` is byte-identical before and
        after (pinned by ``pytest -m profiling``). The price is one
        duplicate compile per executable on the caller's thread, so run
        it alongside :meth:`warmup`, not per request. Returns
        ``{name: ExecutableCost}``.
        """
        import jax
        import jax.numpy as jnp

        if ledger is None:
            from consensusml_tpu.obs import get_cost_ledger

            ledger = get_cost_ledger()
        st = lambda t: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t
        )
        params = st(self._params)
        rows: dict[str, Any] = {}
        base_meta = {
            "kv_impl": self.config.kv_impl,
            "num_slots": self.config.num_slots,
            "max_len": self.max_len,
        }
        if self.paged:
            from consensusml_tpu.models.paged_attention import (
                resolve_attention_impl,
            )
            from consensusml_tpu.serve.pool.stages import (
                decode_cost_args,
                make_paged_decode_fn,
                prefill_cost_args,
            )

            pages = st(self._pages)
            bs = self.config.block_size
            base_meta["attn_impl"] = self.attn_impl
            # the KERNEL-tier impl for the side-by-side ".fused" rows:
            # the engine's own tier when it already runs fused, else
            # the auto resolution (pallas on TPU, interpret elsewhere —
            # never the gather reference)
            fused_impl = (
                self.attn_impl
                if self.attn_impl in ("interpret", "pallas")
                else resolve_attention_impl("auto")
            )
            for b in self.buckets:
                name = f"serve.prefill.b{b}"
                rows[name] = ledger.register(
                    name, self._prefill_fn, params, pages,
                    *prefill_cost_args(b, bs),
                    meta={**base_meta, "bucket": b, "block_size": bs},
                )
            if self._prefix is not None:
                from consensusml_tpu.serve.pool.stages import (
                    prefix_prefill_cost_args,
                )

                # one row per SUFFIX bucket: _request_cost joins each
                # prefix-hit admission against the bucket that actually
                # ran, so a 32-prompt admitted on an 8-token suffix is
                # charged the b8 executable, not the b32 one
                for b in self.buckets:
                    name = f"serve.prefix_prefill.b{b}"
                    rows[name] = ledger.register(
                        name, self._prefix_prefill_fn, params, pages,
                        *prefix_prefill_cost_args(
                            b, bs, self._pool.blocks_per_slot
                        ),
                        meta={**base_meta, "bucket": b, "block_size": bs},
                    )
            rows["serve.decode"] = ledger.register(
                "serve.decode", self._decode_fn, params, pages,
                *decode_cost_args(
                    self.config.num_slots, self._pool.blocks_per_slot
                ),
                meta={
                    **base_meta,
                    "num_blocks": self._pool.num_blocks,
                    "block_size": bs,
                },
            )
            # the fused decode step as its OWN row, so the attribution
            # table shows fused vs gather side by side (same shapes,
            # same load; AOT-only — no jit dispatch cache is touched)
            fused_decode_fn = (
                self._decode_fn
                if self.attn_impl == fused_impl
                else make_paged_decode_fn(self._dm, attn_impl=fused_impl)
            )
            rows["serve.decode.fused"] = ledger.register(
                "serve.decode.fused", fused_decode_fn, params, pages,
                *decode_cost_args(
                    self.config.num_slots, self._pool.blocks_per_slot
                ),
                meta={
                    **base_meta,
                    "attn_impl": fused_impl,
                    "num_blocks": self._pool.num_blocks,
                    "block_size": bs,
                },
            )
            if self.spec is not None:
                from consensusml_tpu.serve.pool.spec import (
                    propose_cost_args,
                    spec_table_cols,
                    verify_cost_args,
                )

                k = self.spec.k
                cols = spec_table_cols(self._pool.blocks_per_slot, bs, k)
                dparams = st(self._draft_params)
                dpages = st(self._draft_pages)
                spec_meta = {**base_meta, "k": k}
                for b in self.buckets:
                    name = f"serve.draft_prefill.b{b}"
                    rows[name] = ledger.register(
                        name, self._draft_prefill_fn, dparams, dpages,
                        *prefill_cost_args(b, bs),
                        meta={**spec_meta, "bucket": b, "block_size": bs},
                    )
                if self._prefix is not None:
                    from consensusml_tpu.serve.pool.stages import (
                        prefix_prefill_cost_args,
                    )

                    for b in self.buckets:
                        name = f"serve.draft_prefix_prefill.b{b}"
                        rows[name] = ledger.register(
                            name, self._draft_prefix_prefill_fn, dparams,
                            dpages,
                            *prefix_prefill_cost_args(
                                b, bs, self._pool.blocks_per_slot
                            ),
                            meta={
                                **spec_meta, "bucket": b, "block_size": bs,
                            },
                        )
                rows["serve.spec.propose"] = ledger.register(
                    "serve.spec.propose", self._propose_fn, dparams,
                    dpages,
                    *propose_cost_args(self.config.num_slots, cols),
                    meta=spec_meta,
                )
                rows["serve.spec.verify"] = ledger.register(
                    "serve.spec.verify", self._verify_fn, params, pages,
                    *verify_cost_args(
                        self.config.num_slots, cols, k,
                        self._dm.vocab_size,
                    ),
                    meta=spec_meta,
                )
                from consensusml_tpu.serve.pool.spec import make_verify_fn

                fused_verify_fn = (
                    self._verify_fn
                    if self.attn_impl == fused_impl
                    else make_verify_fn(self._dm, k, attn_impl=fused_impl)
                )
                rows["serve.spec.verify.fused"] = ledger.register(
                    "serve.spec.verify.fused", fused_verify_fn, params,
                    pages,
                    *verify_cost_args(
                        self.config.num_slots, cols, k,
                        self._dm.vocab_size,
                    ),
                    meta={**spec_meta, "attn_impl": fused_impl},
                )
        else:
            cache = st(self._cache)
            samp1 = (
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.uint32),
            )
            for b in self.buckets:
                name = f"serve.prefill.b{b}"
                rows[name] = ledger.register(
                    name, self._prefill_fn, params, cache,
                    jax.ShapeDtypeStruct((1, b), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    *samp1,
                    meta={**base_meta, "bucket": b},
                )
            toks = jax.ShapeDtypeStruct((self.config.num_slots,), jnp.int32)
            f32s = jax.ShapeDtypeStruct((self.config.num_slots,), jnp.float32)
            u32s = jax.ShapeDtypeStruct((self.config.num_slots,), jnp.uint32)
            rows["serve.decode"] = ledger.register(
                "serve.decode", self._decode_fn, params, cache, toks, toks,
                f32s, f32s, u32s,
                meta=base_meta,
            )
        # the hot-swap stage is a transfer, not a program: restore +
        # device_put of one params tree on the watcher thread
        rows["serve.hotswap.stage"] = ledger.register_transfer(
            "serve.hotswap.stage", self._params,
            meta={**base_meta, "generation": self._generation},
        )
        self._cost_ledger = ledger
        return rows

    @property
    def warmed(self) -> bool:
        """True once a :meth:`warmup` has completed — the readiness bit
        ``/healthz`` (and a fleet router's placement) gates on."""
        return self._warmed.is_set()

    def drain(self, timeout: float | None = None) -> bool:
        """Stop admitting; serve everything queued + in flight to
        completion. Returns True when fully drained (the SIGTERM path —
        see :class:`consensusml_tpu.serve.server.ServeServer`)."""
        self._draining.set()
        return self._drained.wait(timeout)

    def shutdown(self, drain: bool = True, timeout: float | None = None) -> None:
        if drain:
            self.drain(timeout)
        self._stop.set()
        self._thread.join(timeout=5.0)
        if self._watcher is not None:
            self._watcher.stop()

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))

    def stats(self) -> dict[str, Any]:
        """Host-side SLO summary (``tools/loadgen.py`` reads this;
        Prometheus scrapes the registry for the live families).
        Percentiles cover the last 4096 samples; totals are lifetime."""
        pct = lambda xs, q: (
            float(np.percentile(list(xs), q)) if xs else float("nan")
        )
        decode_time = self._decode_time_s
        out = {
            "kv_impl": self.config.kv_impl,
            "attn_impl": self.attn_impl,
            "tokens_in": self._tokens_in,
            "tokens_out": self._tokens_out,
            "decode_steps": self._decode_steps,
            "ttft_p50_ms": 1e3 * pct(self._ttfts, 50),
            "ttft_p99_ms": 1e3 * pct(self._ttfts, 99),
            "intertoken_p50_ms": 1e3 * pct(self._step_times, 50),
            "intertoken_p99_ms": 1e3 * pct(self._step_times, 99),
            "mean_batch_occupancy": (
                self._occupancy_sum / self._decode_steps
                if self._decode_steps
                else 0.0
            ),
            "decode_tokens_per_sec": (
                self._tokens_out / decode_time if decode_time > 0 else 0.0
            ),
            "generation": self._generation,
            "warmed": self.warmed,
            "swaps": self._swaps,
            "evictions": self._evictions,
            "compile_counts": self.compile_counts(),
        }
        if self.paged:
            out["pool"] = {
                "num_blocks": self._pool.num_blocks,
                "block_size": self._pool.block_size,
                "usable_blocks": self._pool.usable_blocks,
                "free_blocks": self._pool.free_blocks,
                "mean_block_occupancy": (
                    self._block_occupancy_sum / self._decode_steps
                    if self._decode_steps
                    else 0.0
                ),
            }
            # on every paged engine (prefix-off baselines report the
            # same field): padded tokens the prefill executables
            # actually computed — the number prefix sharing shrinks
            out["prefill_tokens_computed"] = self._prefill_tokens_computed
            if self._prefix is not None:
                lookups = self._prefix_hits + self._prefix_misses
                out["prefix_cache"] = {
                    "hits": self._prefix_hits,
                    "misses": self._prefix_misses,
                    "hit_rate": (
                        self._prefix_hits / lookups if lookups else 0.0
                    ),
                    "hit_blocks": self._prefix_hit_blocks,
                    "cow_copies": self._prefix_cow_copies,
                    "bytes_saved": self._prefix_bytes_saved,
                    "entries": len(self._prefix),
                    "indexed_blocks": self._prefix.indexed_blocks,
                    "shared_blocks": self._pool.shared_blocks,
                    "invalidations": self._prefix.invalidations,
                }
        if self.spec is not None:
            out["spec"] = {
                "k": self.spec.k,
                "rounds": self._spec_rounds,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "acceptance_rate": (
                    self._spec_accepted / self._spec_proposed
                    if self._spec_proposed
                    else 0.0
                ),
                # round-emitted tokens only (prefill firsts excluded),
                # so intertoken_seconds / tokens_per_round is the honest
                # per-token gap (docs/observability.md)
                "tokens_per_round": (
                    self._spec_tokens / self._spec_rounds
                    if self._spec_rounds
                    else 0.0
                ),
            }
        return out

    # -- engine thread ------------------------------------------------------

    def _loop(self) -> None:
        q = self._queue
        try:
            while not self._stop.is_set():
                self._m_loop_heartbeat.set(time.time())
                self._maybe_swap()  # flip a staged generation between steps
                if self._sched is not None:
                    self._sched.start_tick()
                self._admit_waiting()
                if self._table.num_active:
                    if self.spec is not None:
                        self._spec_step()
                    else:
                        self._decode_step()
                    continue
                if self._draining.is_set() and q.empty() and not self._requeue:
                    break
                if self._requeue:  # deferred by budget; retry next tick
                    continue
                try:
                    req = q.get(timeout=self.config.idle_wait_s)
                except queue.Empty:
                    continue
                self._m_queue.set(q.qsize())
                # route through _admit_waiting's capacity/budget gate
                # next iteration — a direct _admit here would bypass the
                # pool's can_admit check and lean on a hidden
                # pool-empty-when-idle invariant
                self._requeue.append(req)
        except BaseException as e:
            # a device error mid-serving (OOM compiling a bucket, bad
            # params) must not leave clients parked on silent handles:
            # mark the engine dead (submit refuses from here on), fail
            # everything in flight, and re-raise so the thread's death is
            # loud in logs rather than a mystery hang
            self._error = e
            raise
        finally:
            self._stop.set()
            self._draining.set()
            # cancel loudly: in-flight slots and queued requests get a
            # terminal "cancelled" result instead of a hung handle
            for i, slot in self._table.active:
                self._table.release(i)
                if self.paged:
                    # settle block-seconds for the wide event; the pool
                    # itself is NOT released here (unchanged: the
                    # process is exiting, nothing re-admits)
                    self._settle_block_seconds(slot.request, i)
                self._finish_handle(
                    slot.request, slot.request.handle._all, "cancelled"
                )
            self._cancel_queued()
            self._drained.set()

    def _cancel_queued(self) -> None:
        """Drain-and-cancel everything in the submit queue. Called by the
        loop at exit AND by submit() when it loses the race against loop
        exit (its put landed after the loop's final sweep) — once
        ``_drained`` is set nothing services the queue, so cancelling is
        always correct, and the thread-safe ``get_nowait`` hands each
        request to exactly one canceller."""
        while self._requeue:
            try:
                req = self._requeue.popleft()
            except IndexError:
                break
            self._finish_handle(req, req.handle._all, "cancelled")
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            self._finish_handle(req, [], "cancelled")

    def _pop_waiting(self):
        """Next admission candidate: evicted continuations first (their
        tokens are already streaming to a client), then fresh arrivals."""
        if self._requeue:
            return self._requeue.popleft()
        req = self._queue.get_nowait()
        self._m_queue.set(self._queue.qsize())
        return req

    def _admit_waiting(self) -> None:
        while self._table.free_slot() is not None:
            try:
                req = self._pop_waiting()
            except queue.Empty:
                return
            plan = None
            if self.paged:
                from consensusml_tpu.serve.pool import blocks_for_tokens

                # the prefix plan is re-derived on EVERY attempt — a
                # deferred tick may see matched blocks recycled (or new
                # ones indexed) in the meantime, and the capacity/budget
                # charge below must match the plan that will actually run
                plan = self._prefix_plan(req)
                if plan is None:
                    bucket = self._bucket(len(req.ids))
                    need = blocks_for_tokens(
                        len(req.ids) + 1, self.config.block_size
                    )
                else:
                    # charge only what the prefix path consumes: fresh
                    # pops + free-list revivals of cached matched
                    # blocks, and the SUFFIX bucket against the budget
                    bucket = plan["bucket"]
                    need = plan["free_needed"]
                # defer (don't drop) when this tick's prefill budget is
                # spent or the pool can't hold the prompt yet; the
                # request keeps its place at the head of the line —
                # every deferred tick lands on the request's trace, so
                # a long admission wait is attributable, not invisible
                if not self._pool.can_admit(need):
                    self._rt.event(
                        self._rid(req), "admission.defer", reason="blocks"
                    )
                    self._requeue.appendleft(req)
                    return
                if not self._sched.try_admit(bucket):
                    self._rt.event(
                        self._rid(req), "admission.defer", reason="budget"
                    )
                    self._requeue.appendleft(req)
                    return
            self._admit(req, plan)

    @staticmethod
    def _rid(req) -> str | None:
        """The request's trace id, when it carries one (requests built
        outside submit() — direct Request() in tests — may not)."""
        return getattr(req.ctx, "request_id", None)

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"prompt length {n} exceeds max bucket {self.buckets[-1]}")

    def _prefix_plan(self, req) -> dict | None:
        """Resolve ``req``'s admission against the prefix index: None =
        run the full-causal prefill (index off, or nothing matched).
        Otherwise a plan naming the blocks to adopt, the copy-on-write
        source (a FULL match diverges inside its last block: the slot
        re-points at a fresh page, the jit copies the shared rows over,
        and only the final token recomputes), the suffix start/length,
        and the free-block cost (fresh pops + revivals of matched blocks
        currently parked on the free list)."""
        if self._prefix is None:
            return None
        from consensusml_tpu.serve.pool import blocks_for_tokens

        n = len(req.ids)
        match = self._prefix.lookup(req.tenant, self._generation, req.ids)
        if not match:
            return None
        bs = self.config.block_size
        full = len(match) * bs == n
        if full:
            # every prompt block is indexed; the admission still needs
            # last-token logits to sample from, and that recompute's
            # K/V write lands INSIDE the final matched block, which
            # other holders share — so the final block becomes the COW
            # pair and only the prefix before it is adopted outright.
            # (The first decode write at position n opens a fresh block
            # — n % bs == 0 on a full match — so it never collides.)
            adopted = match[:-1]
            cow_src = match[-1]
            start = n - 1
        else:
            adopted = match
            cow_src = None
            start = len(match) * bs
        suffix_len = n - start
        total = blocks_for_tokens(n + 1, bs)
        fresh = total - len(adopted)
        revive = sum(
            1 for b in adopted if self._pool.refcount(b) == 0
        )
        if cow_src is not None and self._pool.refcount(cow_src) == 0:
            revive += 1
        return {
            "match": match,
            "adopted": adopted,
            "cow_src": cow_src,
            "start": start,
            "suffix_len": suffix_len,
            "bucket": self._bucket(suffix_len),
            "fresh": fresh,
            "free_needed": fresh + revive,
        }

    def _admit(self, req, plan=None) -> None:
        """Prefill ``req`` into a free slot (admission = one bucketed
        forward that seeds the slot cache and the first token). A raise
        mid-admission cancels THIS request's handle before propagating —
        at that point it is out of the queue but not yet in the slot
        table, so neither of the loop's exit sweeps would reach it."""
        try:
            self._admit_inner(req, plan)
        except BaseException:
            self._finish_handle(req, req.handle._all, "cancelled")
            raise

    def _admit_inner(self, req, plan=None) -> None:
        import jax.numpy as jnp

        from consensusml_tpu.serve.batcher import Slot

        idx = self._table.free_slot()
        assert idx is not None, "admission with no free slot"
        n = len(req.ids)
        kind = "prefix" if plan is not None else "full"
        bucket = plan["bucket"] if plan is not None else self._bucket(n)
        # an evicted continuation re-prefills prompt + generated-so-far;
        # its TTFT already happened and its token count keeps running
        already = len(req.handle._all)
        # every admission's bucket feeds the wide event's cost join —
        # a continuation re-prefills (a real forward) into a possibly
        # larger bucket, and each one is paid for. The kind picks which
        # ledger row the bucket joins (full vs prefix executable).
        req.prefill_buckets.append(bucket)
        req.prefill_kinds.append(kind)
        ids = np.zeros((1, bucket), np.int32)
        if plan is not None:
            ids[0, : plan["suffix_len"]] = req.ids[plan["start"] :]
        else:
            ids[0, :n] = req.ids
        self._rt.event(
            self._rid(req), "admission", slot=idx, bucket=bucket,
            continuation=bool(already), prefix_blocks=(
                len(plan["match"]) if plan is not None else 0
            ),
        )
        t0 = time.perf_counter()
        samp = (
            jnp.float32(req.temperature),
            jnp.float32(req.top_p),
            jnp.uint32(req.seed),
        )
        with self._tracer.span("serve.prefill", bucket=bucket, slot=idx):
            if self.paged and plan is not None:
                tok_dev = self._prefix_admit(idx, req, plan, bucket, ids, samp)
            elif self.paged:
                from consensusml_tpu.serve.pool import blocks_for_tokens

                if self._prefix is not None:
                    self._prefix_misses += 1
                    self._m_prefix_misses.inc()
                bs = self.config.block_size
                # cover the prompt AND the first decode write (position n)
                self._pool.alloc(idx, blocks_for_tokens(n + 1, bs))
                try:
                    row = jnp.asarray(self._pool.block_row(idx, bucket // bs))
                    tok_dev, _logits, self._pages = self._prefill_fn(
                        self._params,
                        self._pages,
                        jnp.asarray(ids),
                        jnp.int32(n),
                        row,
                        *samp,
                    )
                    if self.spec is not None:
                        # the draft's pages need the prompt too: same
                        # block row, the draft's own page arrays (its
                        # sampled token is discarded — the target's is
                        # the stream's first token)
                        _dt, _dl, self._draft_pages = self._draft_prefill_fn(
                            self._draft_params,
                            self._draft_pages,
                            jnp.asarray(ids),
                            jnp.int32(n),
                            row,
                            *samp,
                        )
                except BaseException:
                    self._pool.release(idx)  # no leaked blocks on a raise
                    raise
            else:
                tok_dev, _logits, self._cache = self._prefill_fn(
                    self._params,
                    self._cache,
                    jnp.asarray(ids),
                    jnp.int32(n),
                    jnp.int32(idx),
                    *samp,
                )
            tok = int(tok_dev)  # device fence: the first token is real now
        if self.paged:
            # target-model tokens the prefill executable computed (the
            # padded bucket — what the device actually ran); prefix hits
            # shrink this to the suffix bucket
            self._prefill_tokens_computed += bucket
            if self._prefix is not None:
                # index this admission's full PROMPT chunks only —
                # positions a PREFILL trace wrote. A continuation's
                # decode-generated tokens stay unindexed: decode-written
                # K/V is only bit-identical to itself, and the index
                # must never serve bytes a fresh full prefill would not
                # reproduce exactly. First writer wins, so a hit
                # admission re-asserts its adopted entries at zero cost.
                self._prefix.insert(
                    req.tenant, self._generation,
                    req.ids[: req.handle.prompt_len],
                    self._pool.owned(idx),
                )
                self._m_prefix_entries.set(len(self._prefix))
                self._m_prefix_shared_blocks.set(self._pool.shared_blocks)
        now = time.perf_counter()
        rid = self._rid(req)
        self._m_prefill.observe(now - t0, exemplar=rid)
        self._rt.event(
            rid, "prefill", bucket=bucket, seconds=round(now - t0, 6)
        )
        ttft = now - req.arrival_t
        if already == 0:
            self._m_ttft.observe(ttft, exemplar=rid)
            self._ttfts.append(ttft)
            req.handle._ttft_s = ttft
            self._tokens_in += n
            self._tenant_metrics(req.tenant)["ttft"].observe(
                ttft, exemplar=rid
            )
        else:  # continuation: the stream's real TTFT already happened
            ttft = getattr(req.handle, "_ttft_s", 0.0)
        req.handle._emit(tok)
        self._m_tokens.inc()
        self._tokens_out += 1
        if already + 1 >= req.max_new_tokens or tok == req.eos_id:
            reason = "eos" if tok == req.eos_id else "max_tokens"
            if self.paged:
                self._settle_block_seconds(req, idx)
                self._pool.release(idx)
            self._finish_handle(req, req.handle._all, reason, ttft=ttft)
            return
        self._table.occupy(
            idx,
            Slot(
                request=req, next_pos=n, pending=tok, generated=already + 1,
                ttft_s=ttft, last_token_t=now, generation=self._generation,
            ),
        )

    def _settle_block_seconds(self, req, idx) -> None:
        """Fold slot ``idx``'s hold-time integral onto ``req`` before
        its references go back: unshared hold is CHARGED to the request
        (the wide event's block_seconds), prefix-shared hold is
        attributed separately (shared_block_seconds) — a request never
        pays for blocks the cache kept alive anyway."""
        unshared, shared = self._pool.block_seconds_split(idx)
        req.block_seconds += unshared
        req.shared_block_seconds += shared

    def _prefix_admit(self, idx, req, plan, bucket, ids, samp):
        """Run one prefix-hit admission's device work: adopt the
        matched blocks, pop fresh ones for the suffix, and dispatch the
        suffix-window prefill (plus the draft's, on a spec engine —
        draft pages share the block geometry, so the hit skips the
        draft prefill too). Returns the sampled first-token device
        value; on a raise the slot's references are fully unwound."""
        import jax.numpy as jnp

        from consensusml_tpu.serve.pool import TRASH_BLOCK

        bs = self.config.block_size
        pool = self._pool
        pool.begin(idx)  # outside the unwind: a double-alloc raise here
        pinned = None  # must not release the EXISTING owner's blocks
        try:
            pool.adopt(idx, plan["adopted"])
            if plan["cow_src"] is not None:
                # hold the source across the dispatch: the extend below
                # must not pop it off the free list (a cached-free
                # match) and hand it out as this slot's "fresh" page
                pool.pin(plan["cow_src"])
                pinned = plan["cow_src"]
            fresh = pool.extend(idx, plan["fresh"])
            if plan["cow_src"] is not None:
                cow_src, cow_dst = plan["cow_src"], fresh[0]
            else:
                cow_src = cow_dst = TRASH_BLOCK
            row = jnp.asarray(
                pool.block_row(idx, pool.blocks_per_slot + bucket // bs)
            )
            tok_dev, _logits, self._pages = self._prefix_prefill_fn(
                self._params,
                self._pages,
                jnp.asarray(ids),
                jnp.int32(plan["suffix_len"]),
                jnp.int32(plan["start"]),
                row,
                jnp.int32(cow_src),
                jnp.int32(cow_dst),
                *samp,
            )
            if self.spec is not None:
                _dt, _dl, self._draft_pages = self._draft_prefix_prefill_fn(
                    self._draft_params,
                    self._draft_pages,
                    jnp.asarray(ids),
                    jnp.int32(plan["suffix_len"]),
                    jnp.int32(plan["start"]),
                    row,
                    jnp.int32(cow_src),
                    jnp.int32(cow_dst),
                    *samp,
                )
        except BaseException:
            if pinned is not None:
                pool.unpin(pinned)
            pool.release(idx)  # no leaked references on a raise
            raise
        if pinned is not None:
            # the dispatch is in the device stream; any later write to
            # the source block is ordered after this read completes
            pool.unpin(pinned)
        hit_blocks = len(plan["match"])
        req.prefix_hit_blocks += hit_blocks
        self._prefix_hits += 1
        self._m_prefix_hits.inc()
        self._prefix_hit_blocks += hit_blocks
        self._m_prefix_hit_blocks.inc(hit_blocks)
        if plan["cow_src"] is not None:
            self._prefix_cow_copies += 1
            self._m_prefix_cow_copies.inc()
        saved = hit_blocks * self._block_nbytes
        if self.spec is not None:
            saved += hit_blocks * self._draft_block_nbytes
        self._prefix_bytes_saved += saved
        self._m_prefix_bytes_saved.inc(saved)
        return tok_dev

    def _youngest_active(self) -> int:
        """Eviction victim: the most recently arrived stream (it has the
        least sunk work to recompute and the fewest tokens streamed)."""
        return max(
            self._table.active,
            key=lambda t: (t[1].request.arrival_t, t[0]),
        )[0]

    def _evict(self, idx: int) -> None:
        """Recompute-preemption: free ``idx``'s blocks and re-enqueue its
        stream as prompt + everything generated so far. The re-prefill
        seeds the continuation's cache and next token, so the client's
        stream continues — tokens already emitted stand, none drop."""
        slot = self._table.release(idx)
        req = slot.request
        # settle the hold-time integral before the blocks go back; the
        # re-admission restarts the clock on a fresh allocation
        self._settle_block_seconds(req, idx)
        self._pool.release(idx)
        # req.ids may itself be a continuation; the first prompt_len ids
        # are always the original prompt
        req.ids = list(req.ids[: req.handle.prompt_len]) + list(
            req.handle._all
        )
        self._rt.event(
            self._rid(req), "preempt", reason="blocks_exhausted",
            generated=len(req.handle._all),
        )
        # head of the line, AHEAD of any budget-deferred fresh arrival
        # (its tokens are already streaming to a client; a fresh request
        # admitted first could consume the very blocks it needs)
        self._requeue.appendleft(req)
        self._evictions += 1
        self._m_evictions.inc()

    def _grow_blocks(self, extra_tokens: int = 0) -> None:
        """Before a paged step: give every lane the blocks its writes
        need — the next position, plus ``extra_tokens`` more for a
        speculative verify window — evicting youngest-first when the
        pool is exhausted (the lane needing the block may itself be the
        youngest — then it preempts itself and re-enters via requeue).
        Window positions past ``blocks_per_slot`` are NOT allocated:
        they overflow into the trash-padded table columns by design."""
        bs = self.config.block_size
        bps = self._pool.blocks_per_slot
        for i, _slot in self._table.active:
            while True:
                slot = self._table.slots[i]
                if slot is None:
                    break  # evicted while resolving an earlier lane
                target = min(
                    bps, (slot.next_pos + extra_tokens) // bs + 1
                )
                if len(self._pool.owned(i)) >= target:
                    break  # this step's write blocks are already owned
                from consensusml_tpu.serve.pool import NoFreeBlocks

                try:
                    self._pool.extend(i, 1)
                except NoFreeBlocks:
                    victim = self._youngest_active()
                    self._evict(victim)
                    if victim == i:
                        break

    def _decode_step(self) -> None:
        import jax.numpy as jnp

        if self.paged:
            self._grow_blocks()
            if not self._table.num_active:  # everything preempted
                return
        active = self._table.active
        s = self.config.num_slots
        tokens, positions, temps, tops, seeds = self._slot_arrays(active)
        t0 = time.perf_counter()
        with self._tracer.span("serve.decode_step", active=len(active)):
            if self.paged:
                next_dev, self._pages = self._decode_fn(
                    self._params,
                    self._pages,
                    self._pool.device_table(),
                    jnp.asarray(tokens),
                    jnp.asarray(positions),
                    jnp.asarray(temps),
                    jnp.asarray(tops),
                    jnp.asarray(seeds),
                )
            else:
                next_dev, self._cache = self._decode_fn(
                    self._params, self._cache, jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(temps),
                    jnp.asarray(tops), jnp.asarray(seeds),
                )
            next_toks = np.asarray(next_dev)  # device fence per step
        dt = time.perf_counter() - t0
        now = time.perf_counter()
        # exemplar: the oldest resident stream — the one that has been
        # paying this step time the longest — stands in for the batch
        self._m_intertoken.observe(
            dt,
            exemplar=self._rid(
                min(active, key=lambda t: t[1].request.arrival_t)[1].request
            ),
        )
        self._step_times.append(dt)
        self._decode_time_s += dt
        self._decode_steps += 1
        self._occupancy_sum += len(active) / s
        self._m_occupancy.set(len(active) / s)
        if dt > 0:
            self._m_tps.set(len(active) / dt)
        if self.paged:
            occ = self._pool.used_blocks / self._pool.usable_blocks
            self._block_occupancy_sum += occ
            self._m_block_occ.set(occ)
            self._m_blocks_free.set(self._pool.free_blocks)
            self._m_pool_hbm_free.set(
                self._pool.free_blocks * self._block_nbytes
            )
        # one lock round-trip covers every resident slot's tick
        self._rt.decode_ticks(
            [self._rid(slot.request) for _i, slot in active]
        )
        for _i, slot in active:
            slot.request.decode_ticks += 1  # wide-event cost join
        for i, slot in active:
            self._emit_and_advance(i, slot, [int(next_toks[i])], now)

    def _slot_arrays(self, active):
        """Fixed-shape per-slot host arrays for one device step: pending
        tokens, write positions, and each lane's sampling triple (free
        lanes stay zero — greedy over garbage into the trash block)."""
        s = self.config.num_slots
        tokens = np.zeros((s,), np.int32)
        positions = np.zeros((s,), np.int32)
        temps = np.zeros((s,), np.float32)
        tops = np.ones((s,), np.float32)
        seeds = np.zeros((s,), np.uint32)
        for i, slot in active:
            tokens[i] = slot.pending
            positions[i] = slot.next_pos
            temps[i] = slot.request.temperature
            tops[i] = slot.request.top_p
            seeds[i] = slot.request.seed
        return tokens, positions, temps, tops, seeds

    def _emit_and_advance(self, i, slot, toks, now) -> int:
        """Emit ``toks`` (one decode token, or a verify round's accepted
        prefix + final) on lane ``i``, advancing position/generation
        bookkeeping one token at a time so eos / token-cap / length
        stops land at the exact right token — tokens past the stop are
        dropped, not emitted. Returns the number actually emitted."""
        req = slot.request
        for emitted, tok in enumerate(toks, start=1):
            req.handle._emit(tok)
            self._m_tokens.inc()
            self._tokens_out += 1
            slot.generated += 1
            slot.next_pos += 1
            slot.pending = tok
            slot.last_token_t = now
            reason = None
            if tok == req.eos_id:
                reason = "eos"
            elif slot.generated >= req.max_new_tokens:
                reason = "max_tokens"
            elif slot.next_pos >= self.max_len:
                reason = "length"  # safety net; submit() validation bounds it
            if reason is not None:
                self._table.release(i)
                if self.paged:
                    self._settle_block_seconds(req, i)
                    self._pool.release(i)
                self._finish_handle(
                    req, req.handle._all, reason,
                    ttft=slot.ttft_s, generation=slot.generation,
                )
                return emitted
        return len(toks)

    def _spec_step(self) -> None:
        """One speculative round: draft proposes ``k`` tokens per lane
        (one scan executable), the target verifies ALL lanes' windows in
        ONE fused forward, and each lane commits its accepted prefix +
        the replacement/bonus token — 1 to ``k + 1`` tokens per lane per
        round, two device dispatches, one host fence."""
        k = self.spec.k
        self._grow_blocks(extra_tokens=k)
        if not self._table.num_active:  # everything preempted
            return
        import jax.numpy as jnp

        active = self._table.active
        tokens, positions, temps, tops, seeds = self._slot_arrays(active)
        table = self._pool.device_table(self._spec_extra_cols)
        t0 = time.perf_counter()
        with self._tracer.span("serve.spec_step", active=len(active), k=k):
            props_dev, q_sel, q_probs, self._draft_pages = self._propose_fn(
                self._draft_params,
                self._draft_pages,
                table,
                jnp.asarray(tokens),
                jnp.asarray(positions),
                jnp.asarray(temps),
                jnp.asarray(tops),
                jnp.asarray(seeds),
            )
            n_acc_dev, final_dev, self._pages = self._verify_fn(
                self._params,
                self._pages,
                table,
                jnp.asarray(tokens),
                props_dev,
                q_sel,
                q_probs,
                jnp.asarray(positions),
                jnp.asarray(temps),
                jnp.asarray(tops),
                jnp.asarray(seeds),
            )
            props = np.asarray(props_dev)  # device fence per round
            n_acc = np.asarray(n_acc_dev)
            finals = np.asarray(final_dev)
        dt = time.perf_counter() - t0
        now = time.perf_counter()
        self._m_intertoken.observe(
            dt,
            exemplar=self._rid(
                min(active, key=lambda t: t[1].request.arrival_t)[1].request
            ),
        )
        self._step_times.append(dt)
        self._decode_time_s += dt
        self._decode_steps += 1
        s = self.config.num_slots
        self._occupancy_sum += len(active) / s
        self._m_occupancy.set(len(active) / s)
        self._rt.decode_ticks(
            [self._rid(slot.request) for _i, slot in active]
        )
        round_emitted = 0
        round_accepted = 0
        # per-stream accounting lands BEFORE emission: a request this
        # round finishes must carry its final round on its trace too
        spec_rows = []
        for i, slot in active:
            n = int(n_acc[i])
            req = slot.request
            req.spec_proposed += k
            req.spec_accepted += n
            req.decode_ticks += 1  # one spec round = one resident tick
            round_accepted += n
            spec_rows.append((self._rid(req), k, n))
        self._rt.spec_ticks(spec_rows)
        for i, slot in active:
            n = int(n_acc[i])
            toks = [int(props[i, j]) for j in range(n)] + [int(finals[i])]
            round_emitted += self._emit_and_advance(i, slot, toks, now)
        if self._pool.free_blocks == 0:
            # rejected-suffix rollback, lazily: positions rolled back
            # above (next_pos only advanced past the accepted prefix);
            # the over-allocated window-tail blocks are handed back only
            # under pool pressure — otherwise the very next round would
            # re-extend the same lanes and churn the device block table
            # every round for nothing
            bs = self.config.block_size
            for i, slot in self._table.active:
                self._pool.shrink(i, slot.next_pos // bs + 1)
        self._spec_rounds += 1
        self._spec_proposed += k * len(active)
        self._spec_accepted += round_accepted
        self._spec_tokens += round_emitted
        self._m_spec_rounds.inc()
        self._m_spec_proposed.inc(k * len(active))
        self._m_spec_accepted.inc(round_accepted)
        self._m_spec_rate.set(
            self._spec_accepted / self._spec_proposed
            if self._spec_proposed
            else 0.0
        )
        if dt > 0:
            self._m_tps.set(round_emitted / dt)
        occ = self._pool.used_blocks / self._pool.usable_blocks
        self._block_occupancy_sum += occ
        self._m_block_occ.set(occ)
        self._m_blocks_free.set(self._pool.free_blocks)
        self._m_pool_hbm_free.set(
            self._pool.free_blocks * self._block_nbytes
        )

    def _tenant_metrics(self, tenant: str) -> dict[str, Any]:
        """The ``consensusml_tenant_*`` labeled children for one tenant,
        created lazily on first touch and cached. Labeled by tenant so
        PR 14's labeled-children alert matching gives every tenant its
        OWN burn-rate SLO over ``consensusml_tenant_ttft_seconds`` with
        zero alert-engine changes (docs/observability.md)."""
        m = self._tenant_children.get(tenant)
        if m is not None:
            return m
        from consensusml_tpu.obs.metrics import DEFAULT_SLO_BUCKETS

        reg, labels = self._registry, {"tenant": tenant}
        m = self._tenant_children[tenant] = {
            "requests": reg.counter(
                "consensusml_tenant_requests_total",
                "terminal requests per tenant (any finish reason)",
                labels=labels,
            ),
            "tokens": reg.counter(
                "consensusml_tenant_tokens_total",
                "generated tokens per tenant",
                labels=labels,
            ),
            "tflops": reg.counter(
                "consensusml_tenant_tflops_total",
                "ledger-joined TFLOPs consumed per tenant",
                labels=labels,
            ),
            "block_seconds": reg.counter(
                "consensusml_tenant_block_seconds_total",
                "KV block-seconds held per tenant (pool hold-time integral)",
                labels=labels,
            ),
            "ttft": reg.histogram(
                "consensusml_tenant_ttft_seconds",
                "time to first token per tenant (the per-tenant SLO series)",
                buckets=DEFAULT_SLO_BUCKETS,
                labels=labels,
            ),
        }
        return m

    def _request_cost(self, req) -> dict[str, Any]:
        """Ledger-join one request's device cost: each admission's
        prefill-bucket row (+ the draft's on a speculative engine), plus
        ``decode_ticks`` × the per-step row — ``serve.decode`` on the
        plain path, ``serve.spec.propose + serve.spec.verify`` per
        round on the speculative path. The base (unsuffixed) rows are
        registered from the engine's OWN executables, so they price the
        executed attention tier whatever ``attn_impl`` resolved to.
        Costs are XLA's cost analysis, never guessed; with no ledger
        registered the event still emits, zeros + ``cost_joined:
        False``."""
        ledger = self._cost_ledger
        flops = bytes_ = 0.0
        joined = ledger is not None
        if ledger is not None:
            rows = []
            # kinds parallel the buckets: a prefix-hit admission joins
            # the SUFFIX bucket's prefix-prefill row — its actual
            # executable — not the full prefill's (requests minted
            # outside submit() may predate the kinds list; default full)
            kinds = req.prefill_kinds or ["full"] * len(req.prefill_buckets)
            for b, kind in zip(req.prefill_buckets, kinds):
                stem = (
                    "serve.prefix_prefill" if kind == "prefix"
                    else "serve.prefill"
                )
                rows.append(ledger.row(f"{stem}.b{b}"))
                if self.spec is not None:
                    dstem = (
                        "serve.draft_prefix_prefill" if kind == "prefix"
                        else "serve.draft_prefill"
                    )
                    rows.append(ledger.row(f"{dstem}.b{b}"))
            if self.spec is not None:
                step_rows = [
                    ledger.row("serve.spec.propose"),
                    ledger.row("serve.spec.verify"),
                ]
            else:
                step_rows = [ledger.row("serve.decode")]
            for row in rows:
                if row is None:
                    joined = False
                    continue
                flops += row.flops
                bytes_ += row.bytes_accessed
            for row in step_rows:
                if row is None:
                    joined = False
                    continue
                flops += req.decode_ticks * row.flops
                bytes_ += req.decode_ticks * row.bytes_accessed
        return {
            "flops": flops,
            "hbm_bytes": bytes_,
            "tflops": flops / 1e12,
            "cost_joined": joined,
        }

    _STAGES = ("submit", "admission", "prefill", "decode", "complete")

    def _emit_wide_event(
        self, req, tokens, reason, ttft, latency, generation
    ) -> None:
        """The terminal wide event: ONE record joining the request's
        trace timings, token counts, pool block-seconds, resolved
        attention tier, and ledger cost (obs/events.py). Called from
        :meth:`_finish_handle` so every terminal state — complete,
        truncated, error-drain — emits exactly once; rejected requests
        never reach here and emit nothing."""
        rid = self._rid(req)
        tr = self._rt.get(rid) if rid else None
        stages_us: dict[str, float] = {}
        defer_ticks = preemptions = 0
        if tr is not None:
            for ev in tr.events:
                name = ev.get("name")
                if name in self._STAGES and name not in stages_us:
                    stages_us[name] = round(
                        ev["ts_us"] - tr.t_start_us, 3
                    )
            defer_ticks = tr.defer_ticks
            preemptions = tr.preemptions
        ev = {
            "trace_id": getattr(req.ctx, "trace_id", ""),
            "request_id": rid or "",
            "tenant": req.tenant,
            "finish_reason": reason,
            "prompt_len": req.handle.prompt_len,
            "tokens_out": len(tokens),
            "ttft_s": round(ttft, 6) if tokens else None,
            "latency_s": round(latency, 6),
            "decode_ticks": req.decode_ticks,
            "defer_ticks": defer_ticks,
            "preemptions": preemptions,
            "generation": generation,
            "spec_proposed": req.spec_proposed,
            "spec_accepted": req.spec_accepted,
            # block_seconds charges only EXCLUSIVE holds; prefix-shared
            # hold time is attributed separately so N streams over one
            # system prompt don't each pay for the same blocks
            "block_seconds": round(req.block_seconds, 6),
            "shared_block_seconds": round(req.shared_block_seconds, 6),
            "prefix_hit_blocks": req.prefix_hit_blocks,
            "attn_impl": self.attn_impl,
            "kv_impl": self.config.kv_impl,
            "prefill_buckets": list(req.prefill_buckets),
            # stage offsets from submit, µs — the joined trace timeline
            "stages_us": stages_us,
        }
        ev.update(self._request_cost(req))
        self._events.emit(ev)
        tm = self._tenant_metrics(req.tenant)
        tm["requests"].inc()
        if tokens:
            tm["tokens"].inc(len(tokens))
        # consumption is real whatever the finish reason — a cancelled
        # stream still burned its flops and held its blocks
        if ev["tflops"] > 0:
            tm["tflops"].inc(ev["tflops"])
        if req.block_seconds > 0:
            tm["block_seconds"].inc(req.block_seconds)

    def _finish_handle(
        self, req, tokens, reason: str, ttft: float = 0.0,
        generation: int | None = None,
    ) -> None:
        from consensusml_tpu.serve.batcher import GenResult

        now = time.perf_counter()
        latency = now - req.arrival_t
        ctx = req.ctx
        gen = self._generation if generation is None else generation
        req.handle._finish(
            GenResult(
                tokens=list(tokens),
                finish_reason=reason,
                ttft_s=ttft,
                latency_s=latency,
                prompt_len=req.handle.prompt_len,
                generation=gen,
                trace_id=getattr(ctx, "trace_id", ""),
                request_id=getattr(ctx, "request_id", ""),
                temperature=req.temperature,
                top_p=req.top_p,
                seed=req.seed,
                spec_proposed=req.spec_proposed,
                spec_accepted=req.spec_accepted,
                tenant=req.tenant,
                block_seconds=req.block_seconds,
                shared_block_seconds=req.shared_block_seconds,
                prefix_hit_blocks=req.prefix_hit_blocks,
            )
        )
        self._rt.finish(
            self._rid(req), reason,
            tokens=len(tokens), ttft_s=round(ttft, 6),
            latency_s=round(latency, 6),
        )
        # the wide event reads the COMPLETED trace (the registry resolves
        # finished ids while the done-ring holds them), so emit after
        self._emit_wide_event(req, tokens, reason, ttft, latency, gen)
        if reason != "cancelled":
            self._m_completed.inc()


def load_engine(
    path: str, config: ServeConfig | None = None, *, spec_k: int = 0
) -> Engine:
    """Build an :class:`Engine` from a serving artifact directory: the
    meta names the config, :func:`configs.build` rebuilds the
    architecture, and the consensus-mean params load in. Raises on
    non-LM artifacts (only causal LMs have a decode path).

    ``spec_k > 0`` additionally loads the DRAFT artifact from the
    ``draft/`` subdirectory (:func:`consensusml_tpu.serve.export.
    export_draft`) and serves speculatively with that proposal depth;
    raises when no draft artifact rides the directory."""
    import os

    from consensusml_tpu import configs
    from consensusml_tpu.serve.export import DRAFT_SUBDIR, load_serving

    meta, params, _model_state = load_serving(path)
    bundle = configs.build(meta["config_name"], meta.get("scale", "smoke"))
    spec = None
    if spec_k:
        from consensusml_tpu.serve.pool import SpecConfig

        draft_dir = os.path.join(path, DRAFT_SUBDIR)
        dmeta, dparams, _dms = load_serving(draft_dir)  # raises w/ context
        dbundle = configs.build(
            dmeta["config_name"], dmeta.get("scale", "smoke")
        )
        spec = SpecConfig(model=dbundle.model, params=dparams, k=spec_k)
    engine = Engine(bundle.model, params, config, spec_decode=spec)
    # seed the hot-swap ordering key from the artifact: watch() must
    # reject re-reads of THIS generation, not just generation 0
    engine._generation = int(meta.get("generation", 0))
    engine._m_generation.set(engine._generation)
    return engine
