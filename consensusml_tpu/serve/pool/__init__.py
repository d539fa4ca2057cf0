"""Serving memory + scheduling subsystem: paged KV blocks, refcounted
prefix sharing, disaggregated prefill/decode stages, drain-free hot
checkpoint swap.

Four pieces, one contract (fixed shapes, zero recompiles after warmup,
no host sync in the decode hot loop):

- :mod:`.blocks` — the paged block pool: slot occupancy bounded by total
  live tokens instead of ``num_slots * max_len``; blocks are refcounted
  so several streams (and the prefix index) can hold one physical block;
- :mod:`.prefix` — the content-addressed prefix index: shared
  block-aligned prompt prefixes prefill once, later requests adopt the
  matched blocks and prefill only the unshared suffix (tenant-keyed,
  generation-invalidated, copy-on-write on divergence);
- :mod:`.stages` — separately-jitted prefill/decode programs plus the
  per-tick admission budget that keeps decode from waiting on long
  prefills (TTFT p99 is the target metric);
- :mod:`.hotswap` — generation-tagged artifact reload: the engine flips
  to a newly exported consensus mean between decode steps with no drain
  and no dropped streams.

The engine (:class:`consensusml_tpu.serve.Engine`) runs this path by
default (``ServeConfig.kv_impl="paged"``); the PR 5 per-slot path stays
as ``kv_impl="slot"`` — the parity baseline the tests compare against
bit for bit.
"""

from consensusml_tpu.serve.pool.blocks import (  # noqa: F401
    BlockPool,
    NoFreeBlocks,
    TRASH_BLOCK,
    blocks_for_tokens,
    init_pages,
)
from consensusml_tpu.serve.pool.prefix import (  # noqa: F401
    PrefixIndex,
)
from consensusml_tpu.serve.pool.stages import (  # noqa: F401
    AdmissionScheduler,
    make_paged_decode_fn,
    make_paged_prefill_fn,
    make_prefix_prefill_fn,
    prefix_prefill_cost_args,
)
from consensusml_tpu.serve.pool.hotswap import (  # noqa: F401
    GenerationWatcher,
    StagedSwap,
)
from consensusml_tpu.serve.pool.spec import (  # noqa: F401
    SpecConfig,
    make_draft_propose_fn,
    make_verify_fn,
    spec_table_cols,
)

__all__ = [
    "BlockPool",
    "NoFreeBlocks",
    "TRASH_BLOCK",
    "blocks_for_tokens",
    "init_pages",
    "PrefixIndex",
    "AdmissionScheduler",
    "make_paged_decode_fn",
    "make_paged_prefill_fn",
    "make_prefix_prefill_fn",
    "prefix_prefill_cost_args",
    "GenerationWatcher",
    "StagedSwap",
    "SpecConfig",
    "make_draft_propose_fn",
    "make_verify_fn",
    "spec_table_cols",
]
