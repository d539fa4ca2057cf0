"""Paged KV pool, disaggregated stages, drain-free hot swap (ISSUE 8).

Three pinned properties:

- **Paged-attention parity** — decode through the block pool is
  bit-exact against the PR 5 per-slot cache path stage by stage, and the
  greedy streams it serves match the full causal forward token for
  token, for both causal-LM families.
- **Free-list invariants** — no double-alloc, no double-free, no leak:
  free ∪ owned partitions the physical blocks across admit/extend/
  release cycles, randomized churn, and real engine admit/evict/swap
  traffic (block exhaustion preempts by recompute and the stream still
  completes, tokens intact).
- **Drain-free hot swap** — the e2e acceptance: train 2 rounds →
  export → serve concurrent streams → export a NEW generation
  mid-traffic → the engine flips params between decode steps with zero
  dropped streams and zero recompiles after warmup.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from consensusml_tpu import configs
from consensusml_tpu.serve import Engine, ServeConfig, load_engine
from consensusml_tpu.serve import decode as D
from consensusml_tpu.serve import pool as P
from consensusml_tpu.serve.export import (
    bump_generation,
    export_serving,
    serving_meta,
)
from consensusml_tpu.serve.pool.hotswap import GenerationWatcher

pytestmark = pytest.mark.serving


def _tiny_gpt2():
    from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM

    return GPT2LM(
        config=GPT2Config(
            vocab_size=64, hidden=32, layers=2, heads=2, max_len=32, dropout=0.0
        )
    )


def _tiny_llama():
    from consensusml_tpu.models.llama import llama_tiny

    return llama_tiny()


def _init(model, seq=8, seed=0):
    return model.init(jax.random.key(seed), jnp.zeros((1, seq), jnp.int32))["params"]


# ---------------------------------------------------------------------------
# Block pool accounting
# ---------------------------------------------------------------------------


def test_block_pool_alloc_extend_release_invariants():
    pool = P.BlockPool(num_slots=4, max_len=32, block_size=8)  # 16 + trash
    assert pool.usable_blocks == 16
    assert pool.free_blocks == 16
    got = pool.alloc(0, 2)
    assert len(got) == 2 and P.TRASH_BLOCK not in got
    assert pool.owned(0) == got
    assert pool.free_blocks == 14
    more = pool.extend(0, 1)
    assert pool.owned(0) == got + more
    pool.check()
    freed = pool.release(0)
    assert sorted(freed) == sorted(got + more)
    assert pool.free_blocks == 16
    # the released slot's table row points at trash again
    assert np.all(np.asarray(pool.device_table())[0] == P.TRASH_BLOCK)
    pool.check()


def test_block_pool_rejects_misuse():
    pool = P.BlockPool(num_slots=2, max_len=32, block_size=8)
    with pytest.raises(ValueError, match="divide"):
        P.BlockPool(num_slots=2, max_len=30, block_size=8)
    with pytest.raises(ValueError, match="cannot hold"):
        P.BlockPool(num_slots=2, max_len=32, block_size=8, num_blocks=3)
    pool.alloc(0, 2)
    with pytest.raises(RuntimeError, match="double-alloc"):
        pool.alloc(0, 1)
    with pytest.raises(ValueError, match="blocks_per_slot"):
        pool.extend(0, 4)
    with pytest.raises(RuntimeError, match="owns nothing"):
        pool.extend(1, 1)
    pool.release(0)
    with pytest.raises(RuntimeError, match="double-free"):
        pool.release(0)
    # exhaustion raises NoFreeBlocks, never hands out the trash block
    pool.alloc(0, 4)
    pool.alloc(1, 4)
    with pytest.raises(ValueError, match="blocks_per_slot"):
        pool.extend(1, 1)
    pool2 = P.BlockPool(num_slots=2, max_len=32, block_size=8, num_blocks=5)
    pool2.alloc(0, 4)
    with pytest.raises(P.NoFreeBlocks):
        pool2.alloc(1, 1)
    pool2.check()


def test_block_pool_randomized_churn_never_leaks():
    rng = np.random.default_rng(0)
    pool = P.BlockPool(num_slots=8, max_len=64, block_size=8, num_blocks=25)
    live: set[int] = set()
    for _ in range(500):
        if live and rng.random() < 0.4:
            s = int(rng.choice(sorted(live)))
            live.remove(s)
            pool.release(s)
        else:
            free_slots = [s for s in range(8) if s not in live]
            if not free_slots:
                continue
            s = int(rng.choice(free_slots))
            want = int(rng.integers(1, 5))
            try:
                pool.alloc(s, want)
                live.add(s)
            except P.NoFreeBlocks:
                pass
        if live and rng.random() < 0.3:
            s = int(rng.choice(sorted(live)))
            if len(pool.owned(s)) < pool.blocks_per_slot:
                try:
                    pool.extend(s, 1)
                except P.NoFreeBlocks:
                    pass
        pool.check()  # free ∪ owned partitions the blocks, every step
    assert pool.used_blocks == sum(len(pool.owned(s)) for s in live)


def test_blocks_for_tokens():
    assert P.blocks_for_tokens(1, 8) == 1
    assert P.blocks_for_tokens(8, 8) == 1
    assert P.blocks_for_tokens(9, 8) == 2
    assert P.blocks_for_tokens(64, 8) == 8


# ---------------------------------------------------------------------------
# Paged-attention parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_paged_stages_bitexact_vs_slot_path(family):
    """Stage-level parity: paged prefill + paged decode produce the SAME
    tokens and the same gathered KV view as the per-slot path, bit for
    bit. gather_paged_kv reassembles (S, max_len, H, D) in the exact
    per-slot layout, so the attention reduction order is identical."""
    model = _tiny_gpt2() if family == "gpt2" else _tiny_llama()
    vocab = model.config.vocab_size
    params = _init(model)
    dm = D.DecodeModel.wrap(model)
    slots, max_len, bs = 2, 32, 8
    prompt = jax.random.randint(jax.random.key(3), (1, 6), 0, vocab)
    bucket = 8  # block-aligned prompt bucket
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :6] = np.asarray(prompt)

    greedy1 = (jnp.float32(0.0), jnp.float32(1.0), jnp.uint32(0))
    greedy = (
        jnp.zeros((slots,), jnp.float32),
        jnp.ones((slots,), jnp.float32),
        jnp.zeros((slots,), jnp.uint32),
    )
    # slot path (PR 5)
    cache = D.init_cache(dm, slots, max_len)
    slot_prefill = D.make_prefill_fn(dm)
    slot_decode = D.make_decode_fn(dm)
    tok_s, logits_s, cache = slot_prefill(
        params, cache, jnp.asarray(ids), jnp.int32(6), jnp.int32(0),
        *greedy1,
    )

    # paged path (pool)
    pool = P.BlockPool(slots, max_len, bs)
    pages = P.init_pages(dm, pool.num_blocks, bs)
    pool.alloc(0, P.blocks_for_tokens(6 + 1, bs))
    paged_prefill = P.make_paged_prefill_fn(dm)
    paged_decode = P.make_paged_decode_fn(dm)
    tok_p, logits_p, pages = paged_prefill(
        params, pages, jnp.asarray(ids), jnp.int32(6),
        jnp.asarray(pool.block_row(0, bucket // bs)),
        *greedy1,
    )
    assert int(tok_s) == int(tok_p)
    np.testing.assert_array_equal(np.asarray(logits_s), np.asarray(logits_p))

    # decode steps cross a block boundary (pos 6..11 crosses at 8)
    toks_s = toks_p = None
    tok_sc, tok_pc = tok_s, tok_p
    pos = 6
    for step in range(6):
        if (pos // bs) >= len(pool.owned(0)):
            pool.extend(0, 1)
        tokens_s = jnp.zeros((slots,), jnp.int32).at[0].set(tok_sc)
        positions = jnp.zeros((slots,), jnp.int32).at[0].set(pos)
        out_s, cache = slot_decode(
            params, cache, tokens_s, positions, *greedy
        )
        tokens_p = jnp.zeros((slots,), jnp.int32).at[0].set(tok_pc)
        out_p, pages = paged_decode(
            params, pages, pool.device_table(), tokens_p, positions,
            *greedy,
        )
        toks_s, toks_p = int(out_s[0]), int(out_p[0])
        assert toks_s == toks_p, f"divergence at decode step {step}"
        tok_sc, tok_pc = toks_s, toks_p
        pos += 1

    # gathered paged view == slot cache rows over the live prefix
    from consensusml_tpu.models.attention import gather_paged_kv

    for layer in range(dm.layers):
        kg, vg = gather_paged_kv(
            pages[layer]["k"], pages[layer]["v"], pool.device_table()
        )
        np.testing.assert_array_equal(
            np.asarray(kg[0, :pos]), np.asarray(cache[layer]["k"][0, :pos])
        )
        np.testing.assert_array_equal(
            np.asarray(vg[0, :pos]), np.asarray(cache[layer]["v"][0, :pos])
        )


@pytest.mark.parametrize(
    # ~60s/family on this box; gpt2 keeps the paged-vs-slot-vs-full parity
    # axis in the fast tier, llama rides the slow tier (its paged path is
    # still exercised fast by the fused-vs-gather stream parity test).
    "family",
    ["gpt2", pytest.param("llama", marks=pytest.mark.slow)],
)
def test_paged_engine_matches_slot_engine_and_full_forward(family):
    """Engine-level parity: the SAME prompts greedily decoded through the
    paged engine, the per-slot engine, and a full-causal-forward loop
    produce identical token streams."""
    model = _tiny_gpt2() if family == "gpt2" else _tiny_llama()
    vocab = model.config.vocab_size
    params = _init(model)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, vocab - 1, size=n).tolist() for n in (2, 5, 9, 13)]
    max_new = 6

    def serve(cfg):
        with Engine(model, params, cfg) as eng:
            eng.warmup()
            handles = [eng.submit(p, max_new) for p in prompts]
            return [h.result(timeout=120).tokens for h in handles]

    paged = serve(ServeConfig(num_slots=4, max_len=32, kv_impl="paged"))
    slot = serve(ServeConfig(num_slots=4, max_len=32, kv_impl="slot"))
    assert paged == slot

    # full causal forward, greedy: the reference with no cache at all.
    # The cached path's reduction order differs from the full forward's
    # (PR 5 pinned their logits at atol=1e-4, not bitwise), so a served
    # token must be the full forward's argmax up to that float noise —
    # near-ties may break either way, a wrong token never passes
    # ONE compiled program for every length: right-pad to max_len and
    # read the last real position (causal: padding cannot reach it) —
    # op-by-op dispatch at 24 distinct lengths cost this test a minute
    full_forward = jax.jit(
        lambda ids: model.apply({"params": params}, ids, deterministic=True)
    )
    for p, toks in zip(prompts, paged):
        ids = list(p)
        for t in range(max_new):
            padded = jnp.asarray([ids + [0] * (32 - len(ids))], jnp.int32)
            logits = np.asarray(full_forward(padded)[0, len(ids) - 1])
            assert logits[toks[t]] >= logits.max() - 1e-4, (
                f"prompt len {len(p)}, step {t}: served token "
                f"{toks[t]} is not the full forward's argmax"
            )
            ids.append(toks[t])  # follow the served stream


# ---------------------------------------------------------------------------
# Engine admit/evict/swap traffic over the pool
# ---------------------------------------------------------------------------


def test_engine_eviction_recompute_completes_all_streams():
    """A pool too small for the offered concurrency preempts streams by
    recompute (blocks free, the stream re-enqueues) — every stream still
    completes with its full token count, token-identical to an engine
    that never evicts, and the free list balances afterwards."""
    model = _tiny_gpt2()
    params = _init(model)
    prompts = [
        np.random.default_rng(i).integers(0, 63, size=4 + 3 * i).tolist()
        for i in range(4)
    ]
    # 16 generated tokens per stream: peak demand is 14 blocks (3+3+4+4)
    # against the tight pool's 9 usable, so eviction pressure is
    # STRUCTURAL — it cannot be raced away by one stream finishing
    # before another is admitted on a slow, loaded box
    max_new = 16

    def serve(num_blocks):
        cfg = ServeConfig(
            num_slots=4, max_len=32, kv_impl="paged", block_size=8,
            num_blocks=num_blocks,
        )
        with Engine(model, params, cfg) as eng:
            eng.warmup()
            handles = [eng.submit(p, max_new) for p in prompts]
            results = [h.result(timeout=120) for h in handles]
            stats = eng.stats()
            eng._pool.check()  # invariants hold after live traffic
            assert stats["pool"]["free_blocks"] == stats["pool"]["usable_blocks"]
        return results, stats

    # 9 usable blocks cannot hold 4 streams growing toward ~26 tokens
    tight, tight_stats = serve(num_blocks=10)
    roomy, roomy_stats = serve(num_blocks=0)  # auto: never evicts
    assert roomy_stats["evictions"] == 0
    assert tight_stats["evictions"] > 0
    assert [r.tokens for r in tight] == [r.tokens for r in roomy]
    assert all(len(r.tokens) == max_new for r in tight)
    assert all(r.finish_reason == "max_tokens" for r in tight)


def test_admission_scheduler_budget():
    s = P.AdmissionScheduler(prefill_budget=32)
    s.start_tick()
    assert s.try_admit(64)  # first admission of a tick always fits
    assert not s.try_admit(8)  # budget already spent
    s.start_tick()
    assert s.try_admit(16)
    assert s.try_admit(16)
    assert not s.try_admit(8)
    s.start_tick()
    assert s.try_admit(8)
    with pytest.raises(ValueError):
        P.AdmissionScheduler(prefill_budget=0)


# ---------------------------------------------------------------------------
# Generations: export counter + watcher protocol
# ---------------------------------------------------------------------------


def _export_tiny_artifact(tmp_path, seed=0, **kw):
    from consensusml_tpu.train import init_stacked_state

    bundle = configs.build("gpt2_topk", "smoke")
    state = init_stacked_state(
        bundle.cfg, bundle.init_params, jax.random.key(seed), bundle.world_size
    )
    return export_serving(
        str(tmp_path / "art"), state, config_name="gpt2_topk", round=0, **kw
    )


def test_export_generation_monotonic(tmp_path):
    art = _export_tiny_artifact(tmp_path)
    assert serving_meta(art)["generation"] == 1
    _export_tiny_artifact(tmp_path, seed=1)  # same dir: re-export bumps
    assert serving_meta(art)["generation"] == 2
    assert bump_generation(art) == 3
    assert serving_meta(art)["generation"] == 3
    with pytest.raises(ValueError, match="generation"):
        _export_tiny_artifact(tmp_path, generation=0)


def test_watcher_stages_new_generations_and_rejects_backwards(tmp_path):
    """Protocol unit test with an injected loader (no orbax restore):
    stage iff the generation strictly advances; reading a REGRESSED meta
    counts a rejection and never stages."""
    art = _export_tiny_artifact(tmp_path)
    loads = []

    def loader(path):
        loads.append(path)
        return serving_meta(path), {"w": jnp.zeros((2,))}, {}

    w = GenerationWatcher.__new__(GenerationWatcher)  # no thread: poll by hand
    import threading

    from consensusml_tpu.obs import get_registry

    w._lock = threading.Lock()  # first: the generation property locks
    w.path, w.poll_s, w.generation = art, 999.0, 1
    w.stage_draft = False
    w._loader, w._staged = loader, None
    w._rejected_gen, w._flip_rejected = None, None
    reg = get_registry()
    w._m_staged = reg.counter("test_pool_w_staged", "t")
    w._m_rejected = reg.counter("test_pool_w_rejected", "t")
    w._m_load = reg.histogram("test_pool_w_load", "t")

    assert not w.poll_once()  # generation 1 == current: nothing to do
    assert loads == [] and w.take() is None
    bump_generation(art)
    assert w.poll_once()  # 2 > 1: loads + stages
    assert loads == [art]
    sw = w.take()
    assert sw.generation == 2 and w.take() is None
    # a stale artifact (generation moved BACKWARDS) is rejected unloaded
    meta = serving_meta(art)
    meta["generation"] = 1
    from consensusml_tpu.serve.export import _write_meta

    before = w._m_rejected.value
    _write_meta(art, meta)
    assert not w.poll_once()
    assert loads == [art]  # no second load
    assert w._m_rejected.value == before + 1
    # the SAME stale meta polled again does not ramp the counter — one
    # regression event counts once, not once per poll
    assert not w.poll_once()
    assert w._m_rejected.value == before + 1

    # engine-side flip rejection rolls the accepted mark back: the same
    # bad artifact is not restaged, but a REWRITE at the same generation
    # (a corrected re-export) is
    meta["generation"] = 3
    _write_meta(art, meta)
    assert w.poll_once()
    sw = w.take()
    assert sw.generation == 3
    w.reject(sw)
    assert w.generation == 2
    assert not w.poll_once()  # same (gen, mtime): skipped, no reload
    assert loads == [art, art]
    _write_meta(art, meta)  # corrected artifact, same generation
    os.utime(
        os.path.join(art, "serve_meta.json"), (time.time(), time.time() + 1)
    )
    assert w.poll_once()  # new mtime: staged again
    assert w.take().generation == 3 and w.generation == 3


# ---------------------------------------------------------------------------
# E2E acceptance: drain-free hot swap mid-traffic
# ---------------------------------------------------------------------------


def test_e2e_hot_swap_mid_traffic(tmp_path):
    """Train 2 rounds → export → serve concurrent streams → export a NEW
    generation mid-traffic → the engine flips between decode steps:
    zero dropped streams, zero recompiles after warmup."""
    import train as train_cli

    from consensusml_tpu.train import init_stacked_state

    art = str(tmp_path / "serving")
    rc = train_cli.main(
        [
            "--config", "gpt2_topk", "--device", "cpu", "--backend", "simulated",
            "--workers", "2", "--rounds", "2", "--log-every", "1",
            "--export-serving", art,
        ]
    )
    assert rc == 0
    assert serving_meta(art)["generation"] == 1

    bundle = configs.build("gpt2_topk", "smoke")
    engine = load_engine(
        art, ServeConfig(num_slots=4, max_len=32, max_new_tokens=24)
    )
    assert engine.generation == 1
    try:
        warm = engine.warmup()
        engine.watch(art, poll_s=0.02)
        rng = np.random.default_rng(5)
        results = []
        swapped_mid_wave = False
        for wave in range(6):
            gen_at_submit = engine.generation
            handles = [
                engine.submit(rng.integers(0, 63, size=n).tolist(), 24)
                for n in (3, 5, 7, 8)
            ]
            if wave == 0:
                # a REAL new artifact (fresh weights, same tree) lands
                # under the live engine — generation auto-bumps to 2.
                # Wave 0 was submitted BEFORE this export, so any wave-0
                # result finishing under generation 2 straddled the flip.
                assert gen_at_submit == 1
                state = init_stacked_state(
                    bundle.cfg, bundle.init_params, jax.random.key(99),
                    bundle.world_size,
                )
                export_serving(art, state, config_name="gpt2_topk", round=0)
                assert serving_meta(art)["generation"] == 2
            wave_results = [h.result(timeout=120) for h in handles]
            results.extend(wave_results)
            gens = {r.generation for r in wave_results}
            if any(r.generation > gen_at_submit for r in wave_results) or (
                engine.generation == 2 and 1 in gens
            ):
                # streams submitted under generation 1 finished under 2
                # (flip landed while they were resident), or finished
                # under 1 with the engine already on 2: the swap was LIVE
                swapped_mid_wave = True
            if engine.generation == 2 and wave >= 1:
                break
        # zero dropped streams: every stream ran to its token cap
        assert all(len(r.tokens) == 24 for r in results)
        assert all(r.finish_reason == "max_tokens" for r in results)
        assert engine.generation == 2, "the staged generation never flipped"
        stats = engine.stats()
        assert stats["swaps"] == 1
        assert swapped_mid_wave, "no stream was in flight across the flip"
        # zero recompiles across the swap: the new tree is byte-shape
        # identical, so the staged params hit the SAME executables
        after = engine.compile_counts()
        assert (after["prefill"], after["decode"]) == (
            warm["prefill"], warm["decode"],
        ), "hot swap recompiled a serving stage"
    finally:
        engine.shutdown()


def test_swap_rejects_mismatched_tree(tmp_path):
    """A staged tree whose leaves do not match the live tree (different
    arch exported over the artifact dir) is rejected at flip time — the
    engine keeps serving the old generation instead of recompiling."""
    model = _tiny_gpt2()
    params = _init(model)
    with Engine(model, params, ServeConfig(num_slots=2, max_len=32)) as eng:
        eng.warmup()
        from consensusml_tpu.serve.pool.hotswap import StagedSwap

        class FakeWatcher:
            def __init__(self):
                self.rejections = 0

            def take(self):
                return StagedSwap(5, {"totally": jnp.zeros((3,))}, {})

            def reject(self, staged=None):
                self.rejections += 1

            def stop(self):
                pass

        eng._watcher = FakeWatcher()
        h = eng.submit([1, 2, 3], 4)
        assert len(h.result(timeout=60).tokens) == 4
        assert eng._watcher.rejections >= 1
        assert eng.generation == 0  # never flipped
