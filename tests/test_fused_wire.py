"""Fused gossip wire (ISSUE 9): one-pass pack+quantize kernels, the
fp8/e4m3 codec, first-class bucket-aligned sub-byte codecs, and
pipelined multi-round overlap gossip.

The fused wire is a TRANSPORT fusion, not a codec change — its whole
contract is "same bytes, same bits, fewer HBM round-trips", so nearly
every test here is a bit-exactness pin: fused payloads vs the two-step
codec's, fused engine rounds vs unfused, kernel (interpret) impl vs jnp,
collective vs simulated. The pipelined-overlap tests pin the ISSUE's
acceptance pair: depth 1 bit-exact with the plain overlap recurrence,
depth > 1 converging to the same consensus mean.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from consensusml_tpu.comm import WorkerMesh, simulated
from consensusml_tpu.compress import (
    Fp8Compressor,
    PallasFp8Compressor,
    PallasInt4Compressor,
    PallasInt8Compressor,
    fused_bucket_codec,
    resolve_codec_impl,
    topk_int8_compressor,
)
from consensusml_tpu.compress.kernels import FusedBucketCodec
from consensusml_tpu.consensus import (
    ConsensusEngine,
    GossipConfig,
    OverlapState,
)
from consensusml_tpu.consensus.bucketing import build_fused_plan
from consensusml_tpu.topology import RingTopology

WORLD = 8
TOPO = RingTopology(WORLD)

# chunk 128 = the kernel lane width: valid for every impl of every codec
CODECS = {
    "int8": PallasInt8Compressor,
    "int4": PallasInt4Compressor,
    "fp8": PallasFp8Compressor,
}


def _tree(seed=0, world=None):
    """Odd leaf sizes (bucket padding) + one sub-chunk leaf."""
    rng = np.random.default_rng(seed)
    lead = () if world is None else (world,)
    return {
        "w": jnp.asarray(rng.normal(size=lead + (300, 17)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=lead + (513,)), jnp.float32),
    }


def _eq(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# fp8 codec
# ---------------------------------------------------------------------------


def test_fp8_reference_roundtrip_properties():
    """e4m3's relative-precision profile: per-chunk max lands exactly on
    the format max, small values keep ~2 significant bits, zero chunks
    decode to exact zeros."""
    comp = Fp8Compressor(chunk=128)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 128)), jnp.float32)
    out = comp.decompress(comp.compress(x))
    assert out.shape == x.shape and out.dtype == x.dtype
    # e4m3 keeps 3 mantissa bits: relative error <= 2^-4 on the bulk
    err = np.abs(np.asarray(out) - np.asarray(x))
    assert np.all(err <= np.abs(np.asarray(x)) * 0.0625 + 1e-6)
    zeros = jnp.zeros((256,), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(comp.decompress(comp.compress(zeros))), np.zeros((256,))
    )


def test_pallas_fp8_interpret_matches_reference():
    comp_i = PallasFp8Compressor(chunk=128, impl="interpret")
    comp_r = Fp8Compressor(chunk=128)
    x = jnp.asarray(
        np.random.default_rng(1).normal(size=(1024,)), jnp.float32
    )
    pi, pr = comp_i.compress(x), comp_r.compress(x)
    # payload bits agree modulo the jit-vs-eager 1-ulp scale difference
    # (XLA folds /448 to a reciprocal multiply under jit); the decoded
    # values are what the wire contract is about
    np.testing.assert_allclose(
        np.asarray(pi.scales), np.asarray(pr.scales), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(comp_i.decompress(pi)),
        np.asarray(comp_r.decompress(pr)),
        rtol=1e-5, atol=1e-6,
    )


def test_fp8_advertises_bucket_alignment_and_fused_wire():
    for comp in (Fp8Compressor(chunk=256), PallasFp8Compressor(chunk=256)):
        assert comp.bucket_alignment() == 256
        assert comp.fused_wire() == "fp8"


# ---------------------------------------------------------------------------
# fused codec: payload/bit parity with the two-step path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", sorted(CODECS))
def test_fused_encode_payload_is_bit_identical_to_codec(fmt):
    """fused encode == compress(x - xhat) + the xhat tracking update,
    payload bits INCLUDED — the wire ships identical bytes."""
    comp = CODECS[fmt](chunk=128, impl="jnp")
    codec = fused_bucket_codec(comp)
    assert isinstance(codec, FusedBucketCodec) and codec.fmt == fmt
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2048,)), jnp.float32)
    h = jnp.asarray(0.3 * rng.normal(size=(2048,)), jnp.float32)
    payload, new_hat = codec.encode(x, h)
    want = comp.compress(x - h)
    np.testing.assert_array_equal(
        np.asarray(payload.data), np.asarray(want.data)
    )
    np.testing.assert_array_equal(
        np.asarray(payload.scales), np.asarray(want.scales)
    )
    np.testing.assert_array_equal(
        np.asarray(new_hat), np.asarray(h + comp.decompress(want))
    )


@pytest.mark.parametrize("fmt", sorted(CODECS))
def test_fused_decode_accumulate_matches_two_step_chain(fmt):
    """fused receive == self-weight multiply + per-neighbor
    decompress_accumulate, in the SAME float-addition order."""
    comp = CODECS[fmt](chunk=128, impl="jnp")
    codec = fused_bucket_codec(comp)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1024,)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(1024,)), jnp.float32)
    q = comp.compress(x)
    weights = (TOPO.self_weight,) + tuple(sh.weight for sh in TOPO.shifts)
    got = codec.decode_accumulate(s, [q] * len(weights), weights)
    recv = weights[0] * comp.decompress(q)
    for w in weights[1:]:
        recv = comp.decompress_accumulate(q, recv, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(s + recv))


def test_fused_bucket_codec_interpret_matches_jnp_impl():
    """The pallas-interpreter kernels and the jnp reference share one
    quantization definition (_fused_quant) — identical payload bits and
    identical accumulate, both jitted."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2048,)), jnp.float32)
    h = jnp.asarray(0.3 * rng.normal(size=(2048,)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(2048,)), jnp.float32)
    for fmt in sorted(CODECS):
        cj = FusedBucketCodec(fmt=fmt, chunk=128, impl="jnp")
        ci = FusedBucketCodec(fmt=fmt, chunk=128, impl="interpret")
        # jit both so XLA's constant-division folding applies equally
        pj, hj = jax.jit(cj.encode)(x, h)
        pi, hi = jax.jit(ci.encode)(x, h)
        np.testing.assert_array_equal(np.asarray(pj.data), np.asarray(pi.data))
        np.testing.assert_array_equal(
            np.asarray(pj.scales), np.asarray(pi.scales)
        )
        np.testing.assert_array_equal(np.asarray(hj), np.asarray(hi))
        aj = jax.jit(
            lambda s, p: cj.decode_accumulate(s, [p, p], (0.5, 0.25))
        )(s, pj)
        ai = jax.jit(
            lambda s, p: ci.decode_accumulate(s, [p, p], (0.5, 0.25))
        )(s, pi)
        np.testing.assert_array_equal(np.asarray(aj), np.asarray(ai))


# ---------------------------------------------------------------------------
# gating: which codecs ride the fused wire
# ---------------------------------------------------------------------------


def test_fused_bucket_codec_gating():
    # composed sparse codec: no fused_wire() tag -> two-step path
    assert fused_bucket_codec(topk_int8_compressor(ratio=0.1, chunk=128)) is None
    # per-chunk quantizers fuse, with the codec's own alignment
    codec = fused_bucket_codec(PallasInt8Compressor(chunk=512))
    assert codec is not None and codec.chunk == 512
    # jnp impl accepts any even alignment; kernel impls need lane multiples
    assert fused_bucket_codec(PallasInt4Compressor(chunk=128, impl="interpret")) is not None


def test_fused_wire_config_validation():
    comp = PallasInt8Compressor(chunk=128, impl="jnp")
    with pytest.raises(ValueError):
        GossipConfig(topology=TOPO, compressor=comp, gamma=0.5, fused_wire="yes")
    with pytest.raises(NotImplementedError):
        GossipConfig(topology=TOPO, fused_wire=True)  # nothing to fuse
    with pytest.raises(NotImplementedError):  # per-leaf wire: no buckets
        GossipConfig(
            topology=TOPO, compressor=comp, gamma=0.5, fused_wire=True,
            bucket_bytes=None,
        )
    with pytest.raises(NotImplementedError):  # codec has no fused kernels
        GossipConfig(
            topology=TOPO, compressor=topk_int8_compressor(ratio=0.1),
            gamma=0.5, fused_wire=True,
        )
    # auto: engages for fused-capable codecs, silently two-step otherwise
    assert ConsensusEngine(
        GossipConfig(topology=TOPO, compressor=comp, gamma=0.5)
    ).fused_wire_active
    assert not ConsensusEngine(
        GossipConfig(
            topology=TOPO, compressor=comp, gamma=0.5, fused_wire=False
        )
    ).fused_wire_active
    assert not ConsensusEngine(
        GossipConfig(
            topology=TOPO, compressor=topk_int8_compressor(ratio=0.1),
            gamma=0.5,
        )
    ).fused_wire_active


def test_resolve_codec_impl():
    # this box has no TPU: "auto" must pick the interpreter (the kernel
    # CODE path), never silently the jnp reference
    assert resolve_codec_impl() in ("pallas", "interpret")
    if jax.default_backend() != "tpu":
        assert resolve_codec_impl() == "interpret"
    assert resolve_codec_impl("jnp") == "jnp"
    assert resolve_codec_impl("pallas") == "pallas"


# ---------------------------------------------------------------------------
# engine rounds: fused wire == two-step path, both backends
# ---------------------------------------------------------------------------


def _engines(fmt: str, impl: str = "jnp"):
    comp = CODECS[fmt](chunk=128, impl=impl)
    mk = lambda fw: ConsensusEngine(
        GossipConfig(
            topology=TOPO, compressor=comp, gamma=0.5,
            bucket_bytes=16 * 1024, fused_wire=fw,
        )
    )
    return mk("auto"), mk(False)


@pytest.mark.parametrize("fmt", sorted(CODECS))
def test_round_simulated_fused_is_bit_exact_vs_unfused(fmt):
    e_f, e_u = _engines(fmt)
    assert e_f.fused_wire_active and not e_u.fused_wire_active
    w = simulated.mixing_matrix(TOPO)
    tree = _tree(5, WORLD)
    st_f = e_f.init_state(tree, world_size=WORLD)
    st_u = e_u.init_state(tree, world_size=WORLD)
    x_f, x_u = tree, tree
    for _ in range(3):
        x_f, st_f = e_f.round_simulated(x_f, st_f, w)
        x_u, st_u = e_u.round_simulated(x_u, st_u, w)
    _eq(x_f, x_u)
    _eq(st_f.xhat, st_u.xhat)
    _eq(st_f.s, st_u.s)


def test_round_collective_fused_matches_simulated():
    """Cross-backend oracle: the fused collective exchange (payloads on
    the ppermute wire) equals the fused stacked exchange (mixing-matrix
    multiply) — the same cross-validation every other wire has."""
    e_f, _ = _engines("int8")
    wmesh = WorkerMesh.create(TOPO, platform="cpu")

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=wmesh.mesh,
        in_specs=P(*TOPO.axis_names),
        out_specs=P(*TOPO.axis_names),
    )
    def run(tree):
        st = e_f.init_state(tree)
        for r in range(2):
            tree, st = e_f.round_collective(tree, st, step=jnp.int32(r))
        return tree

    tree = _tree(6, WORLD)
    got = run(tree)
    w = simulated.mixing_matrix(TOPO)
    want, st = tree, e_f.init_state(tree, world_size=WORLD)
    for _ in range(2):
        want, st = e_f.round_simulated(want, st, w)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=1e-5, atol=1e-6
        )


def test_round_collective_fused_interpret_kernels_run():
    """The pallas-interpreter kernels trace and RUN inside shard_map on
    the CPU mesh (the exact fallback tier-1 depends on), agreeing with
    the unfused two-step round bit-for-bit."""
    e_f, e_u = _engines("int8", impl="interpret")
    assert e_f.fused_wire_active
    wmesh = WorkerMesh.create(TOPO, platform="cpu")

    def mk(engine):
        @jax.jit
        @functools.partial(
            jax.shard_map,
            mesh=wmesh.mesh,
            in_specs=P(*TOPO.axis_names),
            out_specs=P(*TOPO.axis_names),
        )
        def run(tree):
            st = engine.init_state(tree)
            tree, _ = engine.round_collective(tree, st, step=jnp.int32(0))
            return tree

        return run

    tree = _tree(7, WORLD)
    _eq(mk(e_f)(tree), mk(e_u)(tree))


def test_overlap_compressed_fused_rides_the_wire():
    """Overlap+compression on the fused wire: the delayed CHOCO
    correction path engages the fused kernels and stays bit-exact with
    the two-step overlap path."""
    comp = PallasInt8Compressor(chunk=128, impl="jnp")
    mk = lambda fw: ConsensusEngine(
        GossipConfig(
            topology=TOPO, compressor=comp, gamma=0.4, overlap=True,
            bucket_bytes=16 * 1024, fused_wire=fw,
        )
    )
    e_f, e_u = mk("auto"), mk(False)
    w = simulated.mixing_matrix(TOPO)
    z_f, z_u = _tree(8, WORLD), _tree(8, WORLD)
    st_f = e_f.init_state(z_f, world_size=WORLD)
    st_u = e_u.init_state(z_u, world_size=WORLD)
    assert isinstance(st_f, OverlapState) and st_f.choco is not None
    for _ in range(4):
        z_f = e_f.apply_correction(z_f, st_f)
        st_f = e_f.correction_simulated(z_f, w, st_f)
        z_u = e_u.apply_correction(z_u, st_u)
        st_u = e_u.correction_simulated(z_u, w, st_u)
    _eq(z_f, z_u)
    _eq(st_f.correction, st_u.correction)


def test_telemetry_reports_fused_wire():
    e_f, e_u = _engines("int8")
    tree = _tree(9)
    t_f, t_u = e_f.telemetry(tree), e_u.telemetry(tree)
    assert t_f["wire_fused_buckets"] == t_f["gossip_buckets"] > 0
    assert t_f["wire_fused_kernel_calls_per_round"] == (
        2 * t_f["gossip_buckets"] * e_f.config.gossip_steps
    )
    assert t_u["wire_fused_buckets"] == 0.0
    # transport fusion: the bytes accounting must not move
    assert (
        t_f["wire_bytes_per_neighbor"] == t_u["wire_bytes_per_neighbor"]
    )
    assert t_f["gossip_pipeline_depth"] == 1.0


# ---------------------------------------------------------------------------
# pipelined multi-round gossip (GossipConfig.pipeline_depth)
# ---------------------------------------------------------------------------


def test_pipeline_config_validation():
    with pytest.raises(ValueError):
        GossipConfig(topology=TOPO, overlap=True, pipeline_depth=0)
    with pytest.raises(NotImplementedError):  # pipelining IS overlap-mode
        GossipConfig(topology=TOPO, pipeline_depth=2)
    eng = ConsensusEngine(
        GossipConfig(topology=TOPO, overlap=True, pipeline_depth=3)
    )
    st = eng.init_state(_tree(0, WORLD), world_size=WORLD)
    assert isinstance(st, OverlapState) and len(st.pending) == 2
    with pytest.raises(ValueError):  # the queue must thread through
        eng.correction_simulated(
            _tree(0, WORLD), simulated.mixing_matrix(TOPO)
        )


def test_pipeline_depth1_is_bit_exact_with_plain_overlap_recurrence():
    """Depth 1 == the pre-pipeline overlap path: correction (W - I) z
    computed this round, applied next round, nothing queued."""
    eng = ConsensusEngine(GossipConfig(topology=TOPO, overlap=True))
    w = simulated.mixing_matrix(TOPO)
    z = _tree(10, WORLD)
    st = eng.init_state(z, world_size=WORLD)
    assert st.pending == ()
    z_ref = z
    corr = jax.tree.map(jnp.zeros_like, z)
    for _ in range(5):
        z = eng.apply_correction(z, st)
        st = eng.correction_simulated(z, w, st)
        # the PR-1 recurrence, spelled out
        z_ref = jax.tree.map(jnp.add, z_ref, corr)
        mixed = eng._mix_exact_tree_simulated(z_ref, w)
        corr = jax.tree.map(
            lambda m, t: (m - t).astype(t.dtype), mixed, z_ref
        )
        _eq(z, z_ref)
        _eq(st.correction, corr)


@pytest.mark.parametrize("depth", [2, 3])
def test_pipeline_exact_overlap_converges_to_same_mean(depth):
    """Pure pipelined gossip drives every worker to the SAME consensus
    mean as depth 1 (the anticipated-correction recurrence stays on
    x <- W x; a naive delayed correction diverges on a ring at D >= 2),
    and every in-flight correction sums to zero across workers."""
    w = simulated.mixing_matrix(TOPO)
    z0 = _tree(11, WORLD)
    mean0 = {k: np.asarray(v).mean(0) for k, v in z0.items()}

    def run(d, rounds=60):
        eng = ConsensusEngine(
            GossipConfig(topology=TOPO, overlap=True, pipeline_depth=d)
        )
        z = z0
        st = eng.init_state(z, world_size=WORLD)
        for _ in range(rounds):
            z = eng.apply_correction(z, st)
            st = eng.correction_simulated(z, w, st)
        return eng, z, st

    eng1, z1, _ = run(1)
    engd, zd, std = run(depth)
    err1 = float(eng1.consensus_error_simulated(z1))
    errd = float(engd.consensus_error_simulated(zd))
    assert errd < 1e-2, f"depth {depth} failed to contract: {errd}"
    assert errd < 10 * max(err1, 1e-6) + 1e-3
    for k in zd:  # same consensus mean as depth 1, within tol
        np.testing.assert_allclose(
            np.asarray(zd[k]).mean(0), mean0[k], atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(zd[k]).mean(0), np.asarray(z1[k]).mean(0), atol=1e-4
        )
    # mean-exactness of the queue itself
    for p in std.pending + (std.correction,):
        for leaf in jax.tree.leaves(p):
            np.testing.assert_allclose(
                np.asarray(leaf).sum(0), 0.0, atol=1e-4
            )


def test_pipeline_compressed_overlap_converges_and_preserves_mean():
    """Depth-2 pipelining composes with CHOCO overlap+compression on the
    fused wire: contraction holds and the mean is preserved."""
    comp = PallasInt8Compressor(chunk=128, impl="jnp")
    eng = ConsensusEngine(
        GossipConfig(
            topology=TOPO, overlap=True, compressor=comp, gamma=0.4,
            bucket_bytes=16 * 1024, pipeline_depth=2,
        )
    )
    assert eng.fused_wire_active
    w = simulated.mixing_matrix(TOPO)
    z = _tree(12, WORLD)
    mean0 = {k: np.asarray(v).mean(0) for k, v in z.items()}
    err0 = float(eng.consensus_error_simulated(z))
    st = eng.init_state(z, world_size=WORLD)
    assert len(st.pending) == 1 and st.choco is not None
    for _ in range(60):
        z = eng.apply_correction(z, st)
        st = eng.correction_simulated(z, w, st)
    assert float(eng.consensus_error_simulated(z)) < 0.15 * err0
    for k in z:
        np.testing.assert_allclose(
            np.asarray(z[k]).mean(0), mean0[k], atol=1e-4
        )


def test_pipeline_depth_in_train_step():
    """pipeline_depth > 1 threads through the simulated train step: the
    full local-SGD loop runs and keeps contracting."""
    import optax

    from consensusml_tpu.train import (
        LocalSGDConfig,
        init_stacked_state,
        make_simulated_train_step,
    )

    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(16)(x)
            return nn.Dense(4)(nn.relu(x))

    model = Tiny()

    def loss_fn(params, model_state, batch, rng):
        logits = model.apply({"params": params}, batch["x"])
        onehot = jax.nn.one_hot(batch["y"], 4)
        return (
            -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1)),
            model_state,
        )

    cfg = LocalSGDConfig(
        gossip=GossipConfig(
            topology=TOPO, overlap=True, pipeline_depth=2
        ),
        optimizer=optax.sgd(0.05),
        h=2,
    )
    step = make_simulated_train_step(cfg, loss_fn)
    init = lambda r: model.init(r, jnp.zeros((1, 8)))["params"]
    state = init_stacked_state(cfg, init, jax.random.key(0), WORLD)
    rngb = np.random.default_rng(13)
    errs = []
    for _ in range(6):
        batch = {
            "x": jnp.asarray(
                rngb.normal(size=(WORLD, cfg.h, 4, 8)), jnp.float32
            ),
            "y": jnp.asarray(
                rngb.integers(0, 4, size=(WORLD, cfg.h, 4)), jnp.int32
            ),
        }
        state, metrics = step(state, batch)
        errs.append(float(metrics["consensus_error"]))
        assert np.isfinite(float(metrics["loss"]))
    assert errs[-1] < errs[0]


def test_build_fused_plan_rejects_mismatched_alignment():
    comp = PallasInt8Compressor(chunk=128, impl="jnp")
    eng = ConsensusEngine(
        GossipConfig(topology=TOPO, compressor=comp, gamma=0.5)
    )
    leaves = jax.tree.leaves(_tree(0))
    plan = eng._codec_plan(leaves)
    assert build_fused_plan(plan, comp) is not None
    with pytest.raises(ValueError):
        build_fused_plan(plan, PallasInt8Compressor(chunk=256, impl="jnp"))
    # codecs without fused kernels yield None, never an error
    assert build_fused_plan(plan, topk_int8_compressor(ratio=0.1)) is None
