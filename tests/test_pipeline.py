"""Pipeline parallelism must match the sequential layer stack exactly —
forward AND backward (autodiff through the collective schedule) — and
train end-to-end."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from consensusml_tpu.parallel import pipeline_apply, pipeline_last_stage_mean


def _mesh(p):
    return Mesh(np.array(jax.devices("cpu")[:p]), ("pp",))


def _layer(w, x):
    return jnp.tanh(x @ w)


def _stage_fn(stage_params, x):
    # apply this stage's local slice of the layer stack in order
    def body(h, w):
        return _layer(w, h), None

    y, _ = jax.lax.scan(body, x, stage_params)
    return y


def _sequential(all_w, mb):
    def per_mb(x):
        def body(h, w):
            return _layer(w, h), None

        y, _ = jax.lax.scan(body, x, all_w)
        return y

    return jax.vmap(per_mb)(mb)


def _run_pipeline(all_w, mb, p):
    mesh = _mesh(p)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P()
    )
    def f(w, mb):
        outs = pipeline_apply(_stage_fn, w, mb, "pp")
        # replicate the last stage's outputs for comparison
        return pipeline_last_stage_mean(outs, "pp")

    w_sharded = jax.device_put(all_w, NamedSharding(mesh, P("pp")))
    return np.asarray(f(w_sharded, mb))


@pytest.mark.parametrize("p,m", [(2, 4), (4, 8), (8, 8)])
def test_pipeline_matches_sequential_forward(p, m):
    rng = np.random.default_rng(0)
    layers, b, d = 8, 4, 16
    all_w = jnp.asarray(rng.normal(size=(layers, d, d)) * 0.5, jnp.float32)
    mb = jnp.asarray(rng.normal(size=(m, b, d)), jnp.float32)
    want = np.asarray(_sequential(all_w, mb))
    got = _run_pipeline(all_w, mb, p)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pipeline_gradients_match_sequential():
    rng = np.random.default_rng(1)
    layers, m, b, d, p = 8, 8, 2, 8, 4
    all_w = jnp.asarray(rng.normal(size=(layers, d, d)) * 0.5, jnp.float32)
    mb = jnp.asarray(rng.normal(size=(m, b, d)), jnp.float32)
    tgt = jnp.asarray(rng.normal(size=(m, b, d)), jnp.float32)
    mesh = _mesh(p)

    def seq_loss(w):
        return jnp.mean((_sequential(w, mb) - tgt) ** 2)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P("pp"), out_specs=P("pp")
    )
    def pp_grad(w):
        def loss(w):
            outs = pipeline_apply(_stage_fn, w, mb, "pp")
            per = jnp.mean((outs - tgt) ** 2)
            return pipeline_last_stage_mean(per, "pp")

        return jax.grad(loss)(w)

    w_sharded = jax.device_put(all_w, NamedSharding(mesh, P("pp")))
    got = np.asarray(jax.device_get(pp_grad(w_sharded)))
    want = np.asarray(jax.grad(seq_loss)(all_w))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_pipeline_trains():
    """A pipelined deep tanh stack fits a random mapping (loss decreases)."""
    rng = np.random.default_rng(2)
    layers, m, b, d, p = 4, 8, 4, 8, 4
    w = jnp.asarray(rng.normal(size=(layers, d, d)) * 0.3, jnp.float32)
    mb = jnp.asarray(rng.normal(size=(m, b, d)), jnp.float32)
    tgt = jnp.tanh(jnp.asarray(rng.normal(size=(m, b, d)), jnp.float32))
    mesh = _mesh(p)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P("pp"), out_specs=(P("pp"), P())
    )
    def train_step(w):
        def loss(w):
            outs = pipeline_apply(_stage_fn, w, mb, "pp")
            return pipeline_last_stage_mean(jnp.mean((outs - tgt) ** 2), "pp")

        l, g = jax.value_and_grad(loss)(w)
        return w - 0.3 * g, l

    w = jax.device_put(w, NamedSharding(mesh, P("pp")))
    losses = []
    for _ in range(80):
        w, l = train_step(w)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.75


def test_pipeline_rejects_shape_changing_stage():
    mesh = _mesh(2)

    def bad_stage(w, x):
        return jnp.concatenate([x, x], axis=-1)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P()
    )
    def f(w, mb):
        return pipeline_apply(bad_stage, w, mb, "pp")

    w = jnp.zeros((2, 4, 4))
    mb = jnp.zeros((4, 2, 4))
    with pytest.raises(ValueError, match="preserve the activation shape"):
        f(w, mb)


# ---------------------------------------------------------------------------
# PP x gossip-DP composition (VERDICT r3 item 4): pipeline-parallel workers
# inside make_collective_train_step, cross-validated against the simulated
# backend (whose sequential layer scan is the oracle).
# ---------------------------------------------------------------------------


def _pp_loss_fns(layers, d, microbatches):
    """(collective pipelined, simulated sequential) loss_fn pair with
    IDENTICAL math: mean over (M, B/M, d) == mean over (B, d)."""

    def stage_fn(sp, x):
        def body(h, wb):
            w, b = wb
            return jnp.tanh(h @ w + b), None

        return jax.lax.scan(body, x, (sp["w"], sp["b"]))[0]

    def pp_loss(params, model_state, batch, rng):
        x, y = batch["x"], batch["y"]
        mb = x.reshape(microbatches, -1, x.shape[-1])
        yb = y.reshape(microbatches, -1, y.shape[-1])
        outs = pipeline_apply(stage_fn, params["stages"], mb, "pp")
        loss = pipeline_last_stage_mean(jnp.mean((outs - yb) ** 2), "pp")
        return loss, model_state

    def seq_loss(params, model_state, batch, rng):
        def body(h, wb):
            w, b = wb
            return jnp.tanh(h @ w + b), None

        sp = params["stages"]
        out = jax.lax.scan(body, batch["x"], (sp["w"], sp["b"]))[0]
        return jnp.mean((out - batch["y"]) ** 2), model_state

    def init(r):
        kw, kb = jax.random.split(r)
        return {
            "stages": {
                "w": 0.4 * jax.random.normal(kw, (layers, d, d)),
                "b": 0.01 * jax.random.normal(kb, (layers, d)),
            }
        }

    return stage_fn, pp_loss, seq_loss, init


@pytest.mark.parametrize("compressed", [False, True])
def test_pp_composes_with_gossip_dp(compressed):
    """ring(2) x pp=2 over 4 devices: the integrated pipeline-parallel
    train step must match the simulated backend round for round —
    losses, consensus error, and final params."""
    import optax

    from consensusml_tpu.comm import WorkerMesh
    from consensusml_tpu.compress import TopKCompressor
    from consensusml_tpu.consensus import GossipConfig
    from consensusml_tpu.parallel import pipeline_pp_rules
    from consensusml_tpu.topology import RingTopology
    from consensusml_tpu.train import (
        LocalSGDConfig,
        init_stacked_state,
        make_collective_train_step,
        make_simulated_train_step,
    )

    world, layers, d, batch, h, mbs = 2, 4, 16, 8, 2, 4
    topo = RingTopology(world)
    # CHUNK-ALIGNED codec: pp-sharded CHOCO compresses each stage's layer
    # shard locally, so only chunk-local selection (chunk dividing the
    # per-stage leaf size) keeps bit-identical semantics vs the unsharded
    # oracle; a global-per-leaf top-k would select differently per shard
    # (documented in make_collective_train_step). Per-stage w shard =
    # 2*16*16 = 512 = 4 chunks; bias shards stay under one chunk with
    # k >= real elements, so both paths are lossless there.
    from consensusml_tpu.compress import ChunkedTopKCompressor

    comp = (
        ChunkedTopKCompressor(chunk=128, k_per_chunk=64) if compressed else None
    )
    cfg = LocalSGDConfig(
        gossip=GossipConfig(
            topology=topo, compressor=comp, gamma=0.6 if compressed else 1.0
        ),
        optimizer=optax.sgd(0.1),
        h=h,
        # pp shards every state leaf over the stage axis, which CHOCO's
        # flat per-bucket tracking buffers cannot follow: per-leaf wire
        bucket_bytes=0,
    )
    _, pp_loss, seq_loss, init = _pp_loss_fns(layers, d, mbs)
    rules = pipeline_pp_rules()

    wmesh = WorkerMesh.create(
        topo,
        devices=jax.devices()[:4],
        model_axes=(("pp", 2),),
        manual_model_axes=("pp",),
    )
    step_c = make_collective_train_step(cfg, pp_loss, wmesh, rules=rules)
    step_s = make_simulated_train_step(cfg, seq_loss)

    state_c = init_stacked_state(cfg, init, jax.random.key(0), world)
    state_s = init_stacked_state(cfg, init, jax.random.key(0), world)
    state_c = wmesh.shard_stacked(state_c, rules=rules)

    rng = np.random.default_rng(0)
    for r in range(3):
        xs = rng.normal(size=(world, h, batch, d)).astype(np.float32)
        ys = np.tanh(rng.normal(size=(world, h, batch, d))).astype(np.float32)
        b = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
        state_c, mc = step_c(state_c, wmesh.shard_stacked(b))
        state_s, ms = step_s(state_s, b)
        np.testing.assert_allclose(
            float(mc["loss"]), float(ms["loss"]), rtol=2e-5, err_msg=f"round {r}"
        )
        np.testing.assert_allclose(
            float(mc["consensus_error"]),
            float(ms["consensus_error"]),
            rtol=2e-4,
            atol=1e-6,
            err_msg=f"round {r}",
        )
    for pc, ps in zip(
        jax.tree.leaves(state_c.params), jax.tree.leaves(state_s.params)
    ):
        np.testing.assert_allclose(np.asarray(pc), np.asarray(ps), rtol=3e-5, atol=1e-6)


def test_pp_requires_rules():
    import optax

    from consensusml_tpu.comm import WorkerMesh
    from consensusml_tpu.consensus import GossipConfig
    from consensusml_tpu.topology import RingTopology
    from consensusml_tpu.train import LocalSGDConfig, make_collective_train_step

    topo = RingTopology(2)
    wmesh = WorkerMesh.create(
        topo,
        devices=jax.devices()[:4],
        model_axes=(("pp", 2),),
        manual_model_axes=("pp",),
    )
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo), optimizer=optax.sgd(0.1), h=1
    )
    with pytest.raises(ValueError, match="rules"):
        make_collective_train_step(cfg, lambda *a: None, wmesh)


def test_pp_rejects_unsupported_features():
    import optax

    from consensusml_tpu.comm import WorkerMesh
    from consensusml_tpu.consensus import FaultConfig, GossipConfig
    from consensusml_tpu.parallel import pipeline_pp_rules
    from consensusml_tpu.topology import RingTopology
    from consensusml_tpu.train import LocalSGDConfig, make_collective_train_step

    topo = RingTopology(2)
    wmesh = WorkerMesh.create(
        topo,
        devices=jax.devices()[:4],
        model_axes=(("pp", 2),),
        manual_model_axes=("pp",),
    )
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo, faults=FaultConfig(drop_prob=0.1)),
        optimizer=optax.sgd(0.1),
        h=1,
    )
    with pytest.raises(NotImplementedError, match="fault injection"):
        make_collective_train_step(
            cfg, lambda *a: None, wmesh, rules=pipeline_pp_rules()
        )
