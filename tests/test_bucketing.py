"""Bucketed gossip wire (consensus/bucketing.py + GossipConfig.bucket_bytes).

Covers: plan pack/unpack exactness (odd sizes, mixed dtypes, cap edge
cases), bucketed-vs-per-leaf round equivalence for dense/masked/CHOCO on
both backends, wire accounting (never larger than per-leaf), the lifted
overlap+compression restriction, and the dispatch-count reduction the
bucketing exists for (jaxpr op counts on the GPT-2-medium tree — CI has
no TPU, so op counts stand in for launch latency).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from consensusml_tpu.comm import WorkerMesh, simulated
from consensusml_tpu.compress import (
    ChunkedTopKCompressor,
    IdentityCompressor,
    TopKCompressor,
    topk_int8_compressor,
)
from consensusml_tpu.consensus import (
    ConsensusEngine,
    FaultConfig,
    GossipConfig,
    OverlapState,
    build_plan,
)
from consensusml_tpu.topology import DenseTopology, RingTopology


_shard_map = jax.shard_map

WORLD = 8
TOPO = RingTopology(WORLD)

# chunk-decomposable codec => bucketed by default; impl="jnp" so the CPU
# mesh runs the exact math the kernels implement
CHUNKED = ChunkedTopKCompressor(chunk=128, k_per_chunk=8, impl="jnp")


def _tree(seed=0, world=WORLD):
    """Odd-sized leaves, one below the codec chunk — the shapes where
    per-leaf/bucketed divergence would show."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(world, 40, 13)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(world, 7)), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(world, 300)), jnp.float32),
    }


def _pair(**kw):
    """(bucketed engine, per-leaf engine) for the same gossip config."""
    bucketed = ConsensusEngine(GossipConfig(topology=TOPO, **kw))
    per_leaf = ConsensusEngine(
        GossipConfig(topology=TOPO, bucket_bytes=None, **kw)
    )
    assert bucketed.bucketed and not per_leaf.bucketed
    return bucketed, per_leaf


def _run_sim(engine, tree, rounds, alive=None):
    w = simulated.mixing_matrix(engine.topology)
    state = engine.init_state(tree, world_size=WORLD)
    for _ in range(rounds):
        tree, state = engine.round_simulated(tree, state, w, alive=alive)
    return tree


def _run_col(engine, stacked, rounds):
    wmesh = WorkerMesh.create(engine.topology, platform="cpu")
    axes = engine.topology.axis_names

    @jax.jit
    @functools.partial(
        _shard_map, mesh=wmesh.mesh, in_specs=P(*axes), out_specs=P(*axes)
    )
    def run(tree):
        state = engine.init_state(tree)
        for _ in range(rounds):
            tree, state = engine.round_collective(tree, state)
        return tree

    return run(stacked)


# ---------------------------------------------------------------------------
# plan mechanics
# ---------------------------------------------------------------------------


def test_plan_roundtrip_odd_sizes_mixed_dtypes():
    """(c) pack(unpack) is exact for odd-sized, mixed-dtype trees, and
    buckets stay dtype-homogeneous."""
    rng = np.random.default_rng(3)
    leaves = [
        jnp.asarray(rng.normal(size=(17, 3)), jnp.float32),
        jnp.asarray(rng.normal(size=(5,)), jnp.bfloat16),
        jnp.asarray(rng.integers(0, 100, size=(9, 2)), jnp.int32),
        jnp.asarray(rng.normal(size=(1,)), jnp.float32),
        jnp.asarray(rng.normal(size=(250,)), jnp.bfloat16),
    ]
    plan = build_plan(
        [(x.shape, x.dtype) for x in leaves], bucket_bytes=1 << 20, align=128
    )
    for b in plan.buckets:
        for bl in b.leaves:
            assert leaves[bl.index].dtype == b.dtype
            assert bl.padded % 128 == 0
    bufs = plan.pack(leaves)
    back = plan.unpack(bufs)
    for orig, got in zip(leaves, back):
        assert orig.dtype == got.dtype and orig.shape == got.shape
        np.testing.assert_array_equal(np.asarray(orig), np.asarray(got))
    # stacked form round-trips too
    stacked = [jnp.stack([x, x]) for x in leaves]
    back = plan.unpack(plan.pack(stacked, stacked=True), stacked=True)
    for orig, got in zip(stacked, back):
        np.testing.assert_array_equal(np.asarray(orig), np.asarray(got))


def test_plan_cap_edge_cases():
    """(d) one giant bucket vs one leaf per bucket; an over-cap leaf gets
    its own bucket (leaves never split)."""
    shapes = [((64,), jnp.float32), ((64,), jnp.float32), ((4096,), jnp.float32)]
    giant = build_plan(shapes, bucket_bytes=1 << 30)
    assert giant.num_buckets == 1
    tiny = build_plan(shapes, bucket_bytes=1)  # every leaf overflows the cap
    assert tiny.num_buckets == len(shapes)
    # the 16 KiB leaf exceeds a 1 KiB cap but still lands (alone)
    mixed = build_plan(shapes, bucket_bytes=1024)
    assert mixed.num_buckets == 2
    assert {tuple(bl.index for bl in b.leaves) for b in mixed.buckets} == {
        (0, 1), (2,),
    }


def test_engine_path_selection():
    """Bucketing engages for exact mixing and chunk-decomposable codecs;
    global top-k, push-sum and bucket_bytes=None fall back."""
    mk = lambda **kw: ConsensusEngine(GossipConfig(topology=TOPO, **kw))
    assert mk().bucketed
    assert mk(compressor=CHUNKED, gamma=0.5).bucketed
    assert not mk(compressor=TopKCompressor(ratio=0.25), gamma=0.5).bucketed
    assert not mk(bucket_bytes=None).bucketed
    assert not mk(push_sum=True).bucketed
    with pytest.raises(ValueError, match="bucket_bytes"):
        GossipConfig(topology=TOPO, bucket_bytes=0)


# ---------------------------------------------------------------------------
# (a) bucketed round == per-leaf round, all variants, both backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kw",
    [
        {},  # dense
        dict(compressor=CHUNKED, gamma=0.5),  # CHOCO, chunk-decomposable
        dict(compressor=IdentityCompressor(), gamma=1.0),
    ],
    ids=["dense", "choco", "identity"],
)
def test_bucketed_matches_per_leaf_simulated(kw):
    eb, ep = _pair(**kw)
    got = _run_sim(eb, _tree(), rounds=4)
    want = _run_sim(ep, _tree(), rounds=4)
    # f32 eps is 1.2e-7 and four rounds of mixing sums in a different
    # association order: a few ulps, not one (jax 0.9.0's XLA:CPU lands
    # 2 of 56 elements at 3.8e-6 relative)
    for k in got:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=1e-5, atol=1e-6
        )


def test_bucketed_masked_matches_per_leaf_simulated():
    """Masked (fault-model) exact mixing: same alive draw, same result."""
    eb, ep = _pair(faults=FaultConfig(drop_prob=0.5))
    alive = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32)
    got = _run_sim(eb, _tree(1), rounds=3, alive=alive)
    want = _run_sim(ep, _tree(1), rounds=3, alive=alive)
    for k in got:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=1e-6, atol=1e-7
        )


@pytest.mark.parametrize(
    "kw",
    [{}, dict(compressor=CHUNKED, gamma=0.5)],
    ids=["dense", "choco"],
)
def test_bucketed_matches_per_leaf_collective(kw):
    eb, ep = _pair(**kw)
    got = _run_col(eb, _tree(2), rounds=3)
    want = _run_col(ep, _tree(2), rounds=3)
    for k in got:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=1e-6, atol=1e-7
        )


def test_bucketed_collective_matches_simulated():
    """Cross-backend parity stays intact on the bucketed wire (the two
    backends must build the identical plan from per-worker shapes)."""
    for kw in ({}, dict(compressor=CHUNKED, gamma=0.5)):
        eng = ConsensusEngine(GossipConfig(topology=TOPO, **kw))
        assert eng.bucketed
        got_c = _run_col(eng, _tree(4), rounds=3)
        got_s = _run_sim(eng, _tree(4), rounds=3)
        for k in got_c:
            np.testing.assert_allclose(
                np.asarray(got_c[k]), np.asarray(got_s[k]),
                rtol=1e-5, atol=1e-6,
            )


def test_bucketed_composed_codec_close_to_per_leaf():
    """The config-5 composed codec (chunked top-k + int8-quantized
    values): bucketing coalesces the VALUE vectors before the outer int8
    pass, so outputs agree to quantization noise, not bit-exactly — and
    both stay contractive."""
    comp = topk_int8_compressor(chunk=128, k=32, impl="jnp")
    eb, ep = _pair(compressor=comp, gamma=0.4)
    got = _run_sim(eb, _tree(5), rounds=6)
    want = _run_sim(ep, _tree(5), rounds=6)
    err = lambda t: float(
        ConsensusEngine(GossipConfig(topology=TOPO)).consensus_error_simulated(t)
    )
    e0 = err(_tree(5))
    assert err(got) < 0.7 * e0 and err(want) < 0.7 * e0
    for k in got:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=0.02, atol=0.02
        )


def test_bucketed_dense_topology_psum_path():
    """uses_psum topologies mix per bucket through pmean — exact consensus
    in one round, bit-matching the per-leaf result."""
    topo = DenseTopology(4)
    eng_b = ConsensusEngine(GossipConfig(topology=topo))
    eng_p = ConsensusEngine(GossipConfig(topology=topo, bucket_bytes=None))
    tree = _tree(6, world=4)
    w = simulated.mixing_matrix(topo)
    got, _ = eng_b.round_simulated(dict(tree), None, w)
    want, _ = eng_p.round_simulated(dict(tree), None, w)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=1e-6, atol=1e-7
        )


# ---------------------------------------------------------------------------
# (b) wire accounting
# ---------------------------------------------------------------------------


def test_wire_bytes_unchanged_or_smaller():
    tree = {
        "w": jnp.zeros((40, 13), jnp.float32),
        "b": jnp.zeros((7,), jnp.float32),
        "v": jnp.zeros((300,), jnp.float32),
    }
    # dense: bucketing is pure coalescing — identical byte count
    eb, ep = _pair()
    assert eb.wire_bytes_per_round(tree) == ep.wire_bytes_per_round(tree)
    # chunked top-k: leaf-aligned packing mirrors the codec's own per-leaf
    # padding — identical
    eb, ep = _pair(compressor=CHUNKED, gamma=0.5)
    assert eb.wire_bytes_per_round(tree) == ep.wire_bytes_per_round(tree)
    # composed codec at the config-5 shape (k=8 winners per chunk): the
    # coalesced value vector amortizes the outer int8 codec's per-leaf
    # scale/index overhead — not larger (the accounting is exact either
    # way: wire_bytes_per_round reports the padded bucket payload)
    comp = topk_int8_compressor(chunk=128, k=8, impl="jnp")
    eb, ep = _pair(compressor=comp, gamma=0.5)
    assert eb.wire_bytes_per_round(tree) <= ep.wire_bytes_per_round(tree)


# ---------------------------------------------------------------------------
# overlap + compression (lifted on the bucketed path only)
# ---------------------------------------------------------------------------


def test_overlap_compression_gate():
    """Per-leaf/fused/non-decomposable stay rejected; the bucketed path
    with a chunk-decomposable deterministic codec is allowed."""
    ok = GossipConfig(
        topology=TOPO, overlap=True, compressor=CHUNKED, gamma=0.4
    )
    assert ConsensusEngine(ok).bucketed
    with pytest.raises(NotImplementedError, match="compression"):
        GossipConfig(
            topology=TOPO, overlap=True,
            compressor=TopKCompressor(ratio=0.1),  # not chunk-decomposable
        )
    with pytest.raises(NotImplementedError, match="compression"):
        GossipConfig(
            topology=TOPO, overlap=True, compressor=CHUNKED,
            bucket_bytes=None,
        )
    with pytest.raises(NotImplementedError, match="warmup|refresh|compose"):
        GossipConfig(
            topology=TOPO, overlap=True, compressor=CHUNKED,
            codec_warmup_rounds=2,
        )
    from consensusml_tpu.compress import QSGDCompressor

    with pytest.raises(NotImplementedError, match="STOCHASTIC"):
        GossipConfig(
            topology=TOPO, overlap=True, compressor=QSGDCompressor(chunk=128)
        )


def test_overlap_identity_codec_equals_exact_overlap():
    """Q=identity, gamma=1: the delayed CHOCO correction IS the delayed
    (W - I) z — anchors the compressed-overlap algebra to the tested
    exact mode."""
    e_id = ConsensusEngine(
        GossipConfig(
            topology=TOPO, overlap=True,
            compressor=IdentityCompressor(), gamma=1.0,
        )
    )
    e_ex = ConsensusEngine(GossipConfig(topology=TOPO, overlap=True))
    w = simulated.mixing_matrix(TOPO)
    zi, ze = _tree(7), _tree(7)
    si = e_id.init_state(zi, world_size=WORLD)
    se = e_ex.init_state(ze, world_size=WORLD)
    for _ in range(5):
        zi = e_id.apply_correction(zi, si)
        si = e_id.correction_simulated(zi, w, si)
        ze = e_ex.apply_correction(ze, se)
        se = e_ex.correction_simulated(ze, w)
        for k in zi:
            np.testing.assert_allclose(
                np.asarray(zi[k]), np.asarray(ze[k]), rtol=1e-5, atol=1e-6
            )


def test_overlap_choco_contracts_and_preserves_mean():
    # k=16/128: CHOCO's stable gamma shrinks with the compression ratio
    # (docs/convergence.md), and the delayed correction inherits that —
    # the 1/16 codec at gamma 0.4 sits outside the contraction region
    comp = ChunkedTopKCompressor(chunk=128, k_per_chunk=16, impl="jnp")
    eng = ConsensusEngine(
        GossipConfig(topology=TOPO, overlap=True, compressor=comp, gamma=0.4)
    )
    w = simulated.mixing_matrix(TOPO)
    z = _tree(8)
    mean0 = {k: np.asarray(v).mean(0) for k, v in z.items()}
    err0 = float(eng.consensus_error_simulated(z))
    st = eng.init_state(z, world_size=WORLD)
    assert isinstance(st, OverlapState) and st.choco is not None
    for _ in range(60):
        z = eng.apply_correction(z, st)
        st = eng.correction_simulated(z, w, st)
    assert float(eng.consensus_error_simulated(z)) < 0.15 * err0
    for k in z:  # delayed corrections still cancel across workers
        np.testing.assert_allclose(np.asarray(z[k]).mean(0), mean0[k], atol=1e-4)


def test_overlap_compressed_collective_matches_simulated():
    eng = ConsensusEngine(
        GossipConfig(topology=TOPO, overlap=True, compressor=CHUNKED, gamma=0.4)
    )
    wmesh = WorkerMesh.create(TOPO, platform="cpu")

    @jax.jit
    @functools.partial(
        _shard_map,
        mesh=wmesh.mesh,
        in_specs=P(*TOPO.axis_names),
        out_specs=P(*TOPO.axis_names),
    )
    def run(tree):
        st = eng.init_state(tree)
        for _ in range(4):
            tree = eng.apply_correction(tree, st)
            st = eng.correction_collective(tree, st)
        return tree

    got_c = run(_tree(9))
    w = simulated.mixing_matrix(TOPO)
    z = _tree(9)
    st = eng.init_state(z, world_size=WORLD)
    for _ in range(4):
        z = eng.apply_correction(z, st)
        st = eng.correction_simulated(z, w, st)
    for k in z:
        np.testing.assert_allclose(
            np.asarray(got_c[k]), np.asarray(z[k]), rtol=1e-5, atol=1e-6
        )


def test_overlap_compressed_bn_stats_ride_exact_correction():
    """The "auto" compress filter holds in overlap mode too: model_state
    gets the plain (W - I) z correction, params the CHOCO one."""
    eng = ConsensusEngine(
        GossipConfig(topology=TOPO, overlap=True, compressor=CHUNKED, gamma=0.4)
    )
    rng = np.random.default_rng(11)
    tree = {
        "params": {"w": jnp.asarray(rng.normal(size=(WORLD, 40, 13)), jnp.float32)},
        "model_state": {
            "var": jnp.asarray(1.0 + rng.random((WORLD, 33)), jnp.float32)
        },
    }
    w = simulated.mixing_matrix(TOPO)
    st = eng.init_state(tree, world_size=WORLD)
    # CHOCO tracking covers params only
    assert len(jax.tree.leaves(st.choco.xhat)) == 1
    st2 = eng.correction_simulated(tree, w, st)
    want = simulated.mix_stacked(tree["model_state"]["var"], w) - tree[
        "model_state"
    ]["var"]
    np.testing.assert_allclose(
        np.asarray(st2.correction["model_state"]["var"]),
        np.asarray(want), rtol=1e-6, atol=1e-7,
    )


# ---------------------------------------------------------------------------
# dispatch counts (the point of the whole exercise)
# ---------------------------------------------------------------------------


def _count_primitives(jaxpr, counts):
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if hasattr(sub, "eqns"):
                    _count_primitives(sub, counts)
                elif hasattr(sub, "jaxpr"):
                    _count_primitives(sub.jaxpr, counts)
    return counts


@pytest.mark.slow  # the PER-LEAF trace over 292 leaves takes ~25 s
def test_gpt2_medium_dispatch_reduction():
    """On the GPT-2-medium tree (292 leaves), the bucketed round must
    issue <= 1/10th the per-leaf path's ppermute AND compress dispatches.
    Asserted on jaxpr op counts (CI has no TPU to measure launches on);
    shapes only — nothing is materialized."""
    from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM

    model = GPT2LM(config=GPT2Config())  # gpt2-medium dims
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
    )
    assert len(jax.tree.leaves(shapes)) == 292
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((WORLD,) + x.shape, x.dtype), shapes
    )
    wmesh = WorkerMesh.create(TOPO, platform="cpu")
    comp = topk_int8_compressor(chunk=512, k=8, impl="auto")  # config 5

    def counts_for(bucket_bytes):
        eng = ConsensusEngine(
            GossipConfig(
                topology=TOPO, compressor=comp, gamma=0.1,
                bucket_bytes=bucket_bytes,
            )
        )

        def round_fn(tree):
            st = eng.init_state(tree)
            out, _ = eng.round_collective(tree, st)
            return out

        f = functools.partial(
            _shard_map,
            mesh=wmesh.mesh,
            in_specs=P(*TOPO.axis_names),
            out_specs=P(*TOPO.axis_names),
        )(round_fn)
        return _count_primitives(jax.make_jaxpr(f)(stacked).jaxpr, {})

    bucketed = counts_for(4 * 2**20)
    per_leaf = counts_for(None)
    # compress dispatches: one top_k per compress call on this codec
    assert per_leaf["top_k"] == 292
    assert per_leaf["ppermute"] >= 292 * 2  # >= one send per leaf per shift
    assert bucketed["ppermute"] * 10 <= per_leaf["ppermute"]
    assert bucketed["top_k"] * 10 <= per_leaf["top_k"]
