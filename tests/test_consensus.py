"""Consensus engine tests: exact and CHOCO compressed gossip.

Key properties (SURVEY.md §7): identity-compressor CHOCO == plain gossip;
collective (shard_map/ppermute) == simulated (mixing matrix) for the
compressed path; compressed gossip contracts consensus error while
preserving the worker mean; payload on the wire is genuinely small.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from consensusml_tpu.comm import WorkerMesh, simulated
from consensusml_tpu.compress import IdentityCompressor, TopKCompressor, topk_int8_compressor
from consensusml_tpu.consensus import ConsensusEngine, GossipConfig
from consensusml_tpu.topology import DenseTopology, RingTopology, TorusTopology


def _params(topo, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(topo.world_size, 8, 4)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(topo.world_size, 4)), jnp.float32),
    }


def _run_collective(engine, stacked, rounds):
    topo = engine.topology
    wmesh = WorkerMesh.create(topo, platform="cpu")
    blocked = jax.tree.map(
        lambda v: jax.device_put(
            v.reshape(*topo.mesh_shape, *v.shape[1:]), wmesh.worker_sharding()
        ),
        stacked,
    )

    @jax.jit
    @jax.shard_map(
        mesh=wmesh.mesh, in_specs=P(*topo.axis_names), out_specs=P(*topo.axis_names)
    )
    def run(tree):
        state = engine.init_state(tree)
        for _ in range(rounds):
            tree, state = engine.round_collective(tree, state)
        return tree

    out = run(blocked)
    return jax.tree.map(
        lambda v, ref: np.asarray(v).reshape(ref.shape), out, stacked
    )


def _run_simulated(engine, stacked, rounds):
    w = simulated.mixing_matrix(engine.topology)
    # fused CHOCO state is flat per worker: stacked init needs the count
    state = engine.init_state(
        stacked, world_size=engine.topology.world_size
    )
    for _ in range(rounds):
        stacked, state = engine.round_simulated(stacked, state, w)
    return jax.tree.map(np.asarray, stacked)


TOPOS = [RingTopology(8), TorusTopology(2, 4), DenseTopology(4)]


@pytest.mark.parametrize("topo", TOPOS, ids=lambda t: f"{t.name}{t.mesh_shape}")
def test_exact_engine_is_mixing(topo):
    engine = ConsensusEngine(GossipConfig(topology=topo))
    stacked = _params(topo)
    got = _run_collective(engine, stacked, rounds=1)
    w = topo.mixing_matrix()
    for key in stacked:
        flat = np.asarray(stacked[key]).reshape(topo.world_size, -1)
        np.testing.assert_allclose(
            got[key].reshape(topo.world_size, -1), w @ flat, rtol=1e-6, atol=1e-6
        )


def test_identity_choco_equals_plain_gossip():
    """CHOCO with Q=identity, gamma=1 reduces to x <- W x every round."""
    topo = RingTopology(8)
    engine = ConsensusEngine(
        GossipConfig(topology=topo, compressor=IdentityCompressor(), gamma=1.0)
    )
    stacked = _params(topo, seed=4)
    got = _run_simulated(engine, stacked, rounds=3)
    w = np.linalg.matrix_power(topo.mixing_matrix(), 3)
    for key in stacked:
        flat = np.asarray(stacked[key]).reshape(topo.world_size, -1)
        np.testing.assert_allclose(
            got[key].reshape(topo.world_size, -1), w @ flat, rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("topo", TOPOS, ids=lambda t: f"{t.name}{t.mesh_shape}")
def test_choco_collective_matches_simulated(topo):
    comp = TopKCompressor(ratio=0.25)
    engine = ConsensusEngine(GossipConfig(topology=topo, compressor=comp, gamma=0.5))
    stacked = _params(topo, seed=5)
    got_c = _run_collective(engine, stacked, rounds=4)
    got_s = _run_simulated(engine, stacked, rounds=4)
    for key in stacked:
        np.testing.assert_allclose(got_c[key], got_s[key], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "comp,gamma",
    [
        (TopKCompressor(ratio=0.25), 0.4),
        (topk_int8_compressor(ratio=0.25, chunk=32), 0.4),
    ],
    ids=["topk", "topk+int8"],
)
def test_choco_contracts_and_preserves_mean(comp, gamma):
    topo = RingTopology(8)
    engine = ConsensusEngine(GossipConfig(topology=topo, compressor=comp, gamma=gamma))
    stacked = _params(topo, seed=6)
    mean_before = {k: np.asarray(v).mean(0) for k, v in stacked.items()}
    err0 = float(engine.consensus_error_simulated(stacked))

    w = simulated.mixing_matrix(topo)
    state = engine.init_state(stacked)
    x = stacked
    for _ in range(60):
        x, state = engine.round_simulated(x, state, w)
    err = float(engine.consensus_error_simulated(x))
    assert err < 0.15 * err0, f"consensus error {err} vs initial {err0}"
    for k in stacked:
        np.testing.assert_allclose(
            np.asarray(x[k]).mean(0), mean_before[k], atol=1e-4
        )


def test_compressed_wire_is_small():
    """The payload that rides ppermute is ~25x smaller than dense (topk 1%
    of f32 + int8 values + i32 indices)."""
    comp = TopKCompressor(ratio=0.01)
    dense = 1_000_000 * 4
    assert comp.wire_bytes((1000, 1000), jnp.float32) <= dense / 12


def test_wire_bytes_per_round_accounting():
    """Bandwidth accounting: codec payloads vs dense, per-shift sends."""
    import numpy as np

    from consensusml_tpu.compress import topk_int8_compressor
    from consensusml_tpu.topology import (
        DenseTopology,
        OnePeerExponentialTopology,
        RingTopology,
    )

    params = {"w": jnp.zeros((100, 100)), "b": jnp.zeros((100,))}
    dense_bytes = (100 * 100 + 100) * 4

    # exact ring: dense payload x 2 shifts
    eng = ConsensusEngine(GossipConfig(topology=RingTopology(8)))
    assert eng.wire_bytes_per_round(params) == dense_bytes * 2
    # dense topology: one all-reduce pass
    eng = ConsensusEngine(GossipConfig(topology=DenseTopology(4)))
    assert eng.wire_bytes_per_round(params) == dense_bytes
    # compressed: payload well under dense
    comp = topk_int8_compressor(ratio=0.01, chunk=128)
    eng = ConsensusEngine(
        GossipConfig(topology=RingTopology(8), compressor=comp, gamma=0.5)
    )
    compressed = eng.wire_bytes_per_round(params)
    assert compressed < dense_bytes // 5
    assert compressed == 2 * sum(
        comp.wire_bytes(x.shape, jnp.float32) for x in params.values()
    )
    # one-peer time-varying: single send per round on average
    eng = ConsensusEngine(GossipConfig(topology=OnePeerExponentialTopology(8)))
    assert eng.wire_bytes_per_round(params) == dense_bytes
    # push-sum adds the mass scalar
    eng = ConsensusEngine(GossipConfig(topology=RingTopology(8), push_sum=True))
    assert eng.wire_bytes_per_round(params) == dense_bytes * 2 + 8


def test_compress_filter_mixes_model_state_exactly():
    """The "auto" compress filter: params ride CHOCO, the model_state
    subtree (BN running statistics) mixes EXACTLY — sparse delta codecs
    destroy running stats (measured: ResNet-50 study top-1 0.13 vs 0.80).
    """
    topo = RingTopology(8)
    engine = ConsensusEngine(
        GossipConfig(
            topology=topo,
            compressor=topk_int8_compressor(ratio=0.1, chunk=32),
            gamma=0.5,
        )
    )
    rng = np.random.default_rng(12)
    tree = {
        "params": {
            "w": jnp.asarray(rng.normal(size=(8, 16, 8)), jnp.float32)
        },
        "model_state": {
            "batch_stats": {
                "var": jnp.asarray(
                    1.0 + 0.1 * rng.random(size=(8, 32)), jnp.float32
                )
            }
        },
    }
    w = simulated.mixing_matrix(topo)
    state = engine.init_state(tree, world_size=8)
    # CHOCO state exists for params only: one leaf, shaped like w
    assert len(jax.tree.leaves(state.xhat)) == 1
    out, _ = engine.round_simulated(tree, state, w)
    # stats after ONE round equal exact mixing (no compression error)
    want = simulated.mix_stacked(tree["model_state"]["batch_stats"]["var"], w)
    np.testing.assert_allclose(
        np.asarray(out["model_state"]["batch_stats"]["var"]),
        np.asarray(want), rtol=1e-6, atol=1e-6,
    )
    # params went through the codec: NOT equal to exact mixing
    wmix = simulated.mix_stacked(tree["params"]["w"], w)
    assert float(jnp.max(jnp.abs(out["params"]["w"] - wmix))) > 1e-4
    # and variances stayed positive (the failure mode this guards)
    assert float(jnp.min(out["model_state"]["batch_stats"]["var"])) > 0


def test_compress_filter_none_compresses_everything():
    """compress_filter=None restores the old everything-compressed
    behavior, and raw trees without model_state are untouched by auto."""
    topo = RingTopology(4)
    rng = np.random.default_rng(13)
    tree = {
        "params": {"w": jnp.asarray(rng.normal(size=(4, 8, 8)), jnp.float32)},
        "model_state": {
            "m": jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
        },
    }
    w = simulated.mixing_matrix(topo)
    comp = topk_int8_compressor(ratio=0.5, chunk=32)
    eng_none = ConsensusEngine(
        GossipConfig(
            topology=topo, compressor=comp, gamma=0.5, compress_filter=None
        )
    )
    st = eng_none.init_state(tree, world_size=4)
    # state spans BOTH subtrees when the filter is off
    assert len(jax.tree.leaves(st.xhat)) == 2
    out, _ = eng_none.round_simulated(tree, st, w)
    mixed = simulated.mix_stacked(tree["model_state"]["m"], w)
    assert float(jnp.max(jnp.abs(out["model_state"]["m"] - mixed))) > 1e-5


def test_compress_filter_cross_backend_parity():
    """Collective == simulated with the split active (BN-style tree)."""
    topo = RingTopology(8)
    engine = ConsensusEngine(
        GossipConfig(
            topology=topo,
            compressor=TopKCompressor(ratio=0.25),
            gamma=0.5,
        )
    )
    rng = np.random.default_rng(14)
    stacked = {
        "params": {"w": jnp.asarray(rng.normal(size=(8, 8, 4)), jnp.float32)},
        "model_state": {
            "s": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        },
    }
    got_c = _run_collective(engine, stacked, rounds=3)
    got_s = _run_simulated(engine, stacked, rounds=3)
    for leaf_c, leaf_s in zip(jax.tree.leaves(got_c), jax.tree.leaves(got_s)):
        np.testing.assert_allclose(leaf_c, leaf_s, rtol=1e-5, atol=1e-5)


def test_compress_filter_composes_with_path_filter():
    """path_filter (what gossips) and compress_filter (what compresses)
    both act on ORIGINAL paths: a two-stage filter would silently lose
    the model_state exclusion once paths became flat-list indices."""
    topo = RingTopology(4)
    engine = ConsensusEngine(
        GossipConfig(
            topology=topo,
            compressor=topk_int8_compressor(ratio=0.25, chunk=32),
            gamma=0.5,
            # gossip everything except the frozen subtree
            path_filter=lambda p: getattr(p[-1], "key", None) != "frozen",
        )
    )
    rng = np.random.default_rng(15)
    tree = {
        "params": {
            "w": jnp.asarray(rng.normal(size=(4, 8, 8)), jnp.float32),
            "frozen": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
        },
        "model_state": {
            "var": jnp.asarray(1.0 + rng.random(size=(4, 32)), jnp.float32)
        },
    }
    w = simulated.mixing_matrix(topo)
    state = engine.init_state(tree, world_size=4)
    # CHOCO tracks ONLY params/w: not frozen (path_filter), not var (auto)
    assert len(jax.tree.leaves(state.xhat)) == 1
    out, _ = engine.round_simulated(tree, state, w)
    # frozen leaf passed through untouched
    np.testing.assert_array_equal(
        np.asarray(out["params"]["frozen"]), np.asarray(tree["params"]["frozen"])
    )
    # stats mixed EXACTLY despite the path_filter being present
    np.testing.assert_allclose(
        np.asarray(out["model_state"]["var"]),
        np.asarray(simulated.mix_stacked(tree["model_state"]["var"], w)),
        rtol=1e-6, atol=1e-6,
    )


def test_gossip_steps_multiplies_contraction():
    """T consensus iterations per round contract like T single rounds
    (exact mixing: x -> W^T x), cross-backend, and wire accounting
    multiplies by T."""
    import numpy as np

    from consensusml_tpu.comm.simulated import mixing_matrix
    from consensusml_tpu.compress import topk_int8_compressor

    world = 8
    topo = RingTopology(world)
    w = mixing_matrix(topo)
    rng = np.random.default_rng(0)
    x = {"a": jnp.asarray(rng.normal(size=(world, 64)), jnp.float32)}

    e1 = ConsensusEngine(GossipConfig(topology=topo))
    e3 = ConsensusEngine(GossipConfig(topology=topo, gossip_steps=3))
    y1, _ = e1.round_simulated(x, None, w)
    y111, _ = e1.round_simulated(y1, None, w)
    y111, _ = e1.round_simulated(y111, None, w)
    y3, _ = e3.round_simulated(x, None, w)
    np.testing.assert_allclose(
        np.asarray(y3["a"]), np.asarray(y111["a"]), rtol=1e-5, atol=1e-6
    )

    # CHOCO: T iterations contract consensus error strictly more than 1
    comp = topk_int8_compressor(ratio=0.25, chunk=32)
    err = lambda v: float(
        np.sqrt(np.mean(np.sum((v - v.mean(0)) ** 2, axis=-1)))
    )
    for steps, expect_better in [(1, None), (4, True)]:
        eng = ConsensusEngine(
            GossipConfig(topology=topo, compressor=comp, gamma=0.2,
                         gossip_steps=steps)
        )
        st = eng.init_state(x, world_size=world)
        v = dict(x)
        for _ in range(5):
            v, st = eng.round_simulated(v, st, w)
        e = err(np.asarray(v["a"]))
        if steps == 1:
            e_single = e
        else:
            assert e < 0.5 * e_single, (e, e_single)

    # wire accounting multiplies by T
    p = {"a": jnp.zeros((512,), jnp.float32)}
    w1 = ConsensusEngine(
        GossipConfig(topology=topo, compressor=comp, gamma=0.2)
    ).wire_bytes_per_round(p)
    w4 = ConsensusEngine(
        GossipConfig(topology=topo, compressor=comp, gamma=0.2, gossip_steps=4)
    ).wire_bytes_per_round(p)
    assert w4 == 4 * w1


def test_gossip_steps_collective_matches_simulated():
    """gossip_steps > 1 stays cross-validated between backends (CHOCO)."""
    topo = RingTopology(8)
    comp = topk_int8_compressor(ratio=0.25, chunk=32)
    engine = ConsensusEngine(
        GossipConfig(topology=topo, compressor=comp, gamma=0.3, gossip_steps=3)
    )
    stacked = _params(topo)
    got = _run_collective(engine, stacked, rounds=2)
    want = _run_simulated(engine, stacked, rounds=2)
    for key in stacked:
        np.testing.assert_allclose(got[key], want[key], rtol=2e-5, atol=1e-6)


def test_gossip_steps_stochastic_codec_backends_agree():
    """The PER-ITERATION rng fold (gossip_steps > 1 + stochastic codec)
    must draw identical randomness on both backends — the deterministic
    topk test above cannot catch a fold-convention divergence."""
    import functools

    from consensusml_tpu.compress import QSGD4Compressor

    topo = RingTopology(4)
    comp = QSGD4Compressor(chunk=32)
    assert comp.stochastic
    engine = ConsensusEngine(
        GossipConfig(topology=topo, compressor=comp, gamma=0.3, gossip_steps=3)
    )
    rng = np.random.default_rng(5)
    stacked = {
        "a": jnp.asarray(rng.normal(size=(4, 64)), jnp.float32),
    }
    keys = jax.random.split(jax.random.key(7), 4)

    # simulated
    st = engine.init_state(stacked, world_size=4)
    sim, _ = engine.round_simulated(stacked, st, simulated.mixing_matrix(topo), rng=keys)

    # collective
    wmesh = WorkerMesh.create(topo, platform="cpu")
    blocked = jax.tree.map(
        lambda v: jax.device_put(v, wmesh.stacked_sharding()), stacked
    )
    bkeys = jax.device_put(keys, wmesh.stacked_sharding())

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=wmesh.mesh,
        in_specs=(P(*topo.axis_names), P(*topo.axis_names)),
        out_specs=P(*topo.axis_names),
    )
    def run(tree, k):
        sq = lambda t: jax.tree.map(lambda v: v.reshape(v.shape[1:]), t)
        state = engine.init_state(sq(tree))
        out, _ = engine.round_collective(sq(tree), state, rng=sq({"k": k})["k"])
        return jax.tree.map(lambda v: v.reshape((1,) + v.shape), out)

    col = run(blocked, bkeys)
    np.testing.assert_allclose(
        np.asarray(col["a"]), np.asarray(sim["a"]), rtol=2e-5, atol=1e-6
    )


def test_codec_warmup_rounds():
    """Warmup rounds mix exactly (bit-equal to the exact engine) while
    warming xhat/s; post-warmup rounds run pure CHOCO with tracking
    already caught up — and the whole schedule stays cross-backend."""
    topo = RingTopology(8)
    comp = topk_int8_compressor(ratio=0.25, chunk=32)
    warm_engine = ConsensusEngine(
        GossipConfig(topology=topo, compressor=comp, gamma=0.3,
                     codec_warmup_rounds=2)
    )
    exact_engine = ConsensusEngine(GossipConfig(topology=topo))

    # warmup must track the exact engine at the SAME gossip_steps too
    w2 = simulated.mixing_matrix(topo)
    wg = ConsensusEngine(
        GossipConfig(topology=topo, compressor=comp, gamma=0.3,
                     codec_warmup_rounds=1, gossip_steps=2)
    )
    eg = ConsensusEngine(GossipConfig(topology=topo, gossip_steps=2))
    p0 = _params(topo, seed=9)
    stg = wg.init_state(p0, world_size=topo.world_size)
    warm_out, _ = wg.round_simulated(p0, stg, w2, step=jnp.int32(0))
    exact_out, _ = eg.round_simulated(p0, None, w2)
    for key in p0:
        np.testing.assert_allclose(
            np.asarray(warm_out[key]), np.asarray(exact_out[key]), rtol=1e-6
        )
    stacked = _params(topo)
    w = simulated.mixing_matrix(topo)

    # rounds 0-1 (warmup): params move EXACTLY like exact mixing
    st = warm_engine.init_state(stacked, world_size=topo.world_size)
    cur = stacked
    exact = stacked
    for step in range(2):
        cur, st = warm_engine.round_simulated(
            cur, st, w, step=jnp.int32(step)
        )
        exact, _ = exact_engine.round_simulated(exact, None, w)
        for key in stacked:
            np.testing.assert_allclose(
                np.asarray(cur[key]), np.asarray(exact[key]), rtol=1e-6
            )
    # tracking state warmed: xhat moved toward x (not still zero)
    assert float(jnp.abs(st.xhat["w"]).sum()) > 0

    # post-warmup: compressed rounds keep contracting disagreement
    err = lambda t: float(
        np.sqrt(np.mean(np.sum((np.asarray(t["w"]) - np.asarray(t["w"]).mean(0)) ** 2, axis=-1)))
    )
    e_before = err(cur)
    for step in range(2, 6):
        cur, st = warm_engine.round_simulated(cur, st, w, step=jnp.int32(step))
    assert err(cur) < e_before

    # cross-backend: the same schedule through the collective engine
    got = _run_collective_steps(warm_engine, stacked, rounds=4)
    st2 = warm_engine.init_state(stacked, world_size=topo.world_size)
    sim = stacked
    for step in range(4):
        sim, st2 = warm_engine.round_simulated(sim, st2, w, step=jnp.int32(step))
    for key in stacked:
        np.testing.assert_allclose(
            got[key], np.asarray(sim[key]), rtol=2e-5, atol=1e-6
        )


def _run_collective_steps(engine, stacked, rounds):
    """Like _run_collective but passing the round counter (warmup)."""
    import functools

    topo = engine.topology
    wmesh = WorkerMesh.create(topo, platform="cpu")
    blocked = jax.tree.map(
        lambda v: jax.device_put(
            v.reshape(*topo.mesh_shape, *v.shape[1:]), wmesh.worker_sharding()
        ),
        stacked,
    )

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=wmesh.mesh,
        in_specs=P(*topo.axis_names),
        out_specs=P(*topo.axis_names),
    )
    def run(tree):
        state = engine.init_state(tree)
        for step in range(rounds):
            tree, state = engine.round_collective(
                tree, state, step=jnp.int32(step)
            )
        return tree

    out = run(blocked)
    return jax.tree.map(
        lambda v, ref: np.asarray(v).reshape(ref.shape), out, stacked
    )


def test_codec_refresh_every():
    """Every K-th round runs the dense warmup-style round: bit-equal to
    exact mixing on refresh rounds, CHOCO between, cross-backend."""
    topo = RingTopology(8)
    comp = topk_int8_compressor(ratio=0.25, chunk=32)
    eng = ConsensusEngine(
        GossipConfig(topology=topo, compressor=comp, gamma=0.3,
                     codec_refresh_every=3)
    )
    exact = ConsensusEngine(GossipConfig(topology=topo))
    stacked = _params(topo, seed=11)
    w = simulated.mixing_matrix(topo)

    st = eng.init_state(stacked, world_size=topo.world_size)
    cur = stacked
    for step in range(6):
        prev = cur
        cur, st = eng.round_simulated(cur, st, w, step=jnp.int32(step))
        if step % 3 == 0:  # refresh rounds mix exactly
            ref, _ = exact.round_simulated(prev, None, w)
            # the refresh round mixes the same values through the bucketed
            # wire: same math, another summation order — a few f32 ulps
            for key in stacked:
                np.testing.assert_allclose(
                    np.asarray(cur[key]), np.asarray(ref[key]),
                    rtol=1e-5, atol=1e-7,
                )

    # cross-backend over the mixed schedule
    got = _run_collective_steps(eng, stacked, rounds=5)
    st2 = eng.init_state(stacked, world_size=topo.world_size)
    sim = stacked
    for step in range(5):
        sim, st2 = eng.round_simulated(sim, st2, w, step=jnp.int32(step))
    for key in stacked:
        np.testing.assert_allclose(
            got[key], np.asarray(sim[key]), rtol=2e-5, atol=1e-6
        )
