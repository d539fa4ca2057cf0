"""End-to-end local-SGD tests — the reference's config 1 and the
collective/simulated cross-validation (SURVEY.md §7 steps 3-4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from consensusml_tpu.comm import WorkerMesh
from consensusml_tpu.compress import TopKCompressor
from consensusml_tpu.consensus import GossipConfig
from consensusml_tpu.data import SyntheticClassification, round_batches
from consensusml_tpu.models import MLP, mlp_loss_fn
from consensusml_tpu.topology import DenseTopology, RingTopology
from consensusml_tpu.train import (
    LocalSGDConfig,
    init_stacked_state,
    make_collective_train_step,
    make_simulated_train_step,
)


def _mlp_setup(topo, h=2, lr=1e-2, compressor=None, gamma=1.0, hidden=32):
    model = MLP(hidden=hidden)
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo, compressor=compressor, gamma=gamma),
        optimizer=optax.adam(lr),
        h=h,
    )
    init = lambda rng: model.init(rng, jnp.zeros((1, 28, 28, 1)))["params"]
    return model, cfg, init


def test_config1_mlp_dense_4workers_end_to_end():
    """BASELINE.json configs[0]: MLP 'MNIST', 4 simulated workers, dense
    gossip, CPU. Loss must fall, accuracy must rise, and dense gossip must
    keep consensus error at ~0 (exact averaging every round)."""
    topo = DenseTopology(4)
    model, cfg, init = _mlp_setup(topo)
    data = SyntheticClassification(n=4096)
    step = make_simulated_train_step(cfg, mlp_loss_fn(model))
    state = init_stacked_state(cfg, init, jax.random.key(0), topo.world_size)

    losses, errs = [], []
    for batch in round_batches(data, topo.world_size, h=cfg.h, batch=64, rounds=50):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        errs.append(float(metrics["consensus_error"]))

    assert losses[-1] < 0.3 * losses[0], f"loss did not fall: {losses[0]} -> {losses[-1]}"
    assert errs[-1] < 1e-3, f"dense gossip should reach exact consensus, err={errs[-1]}"

    # accuracy on held-out-ish data with worker-0 params
    params0 = jax.tree.map(lambda x: x[0], state.params)
    ev = data.eval_batch(512)
    preds = jnp.argmax(model.apply({"params": params0}, ev["image"]), -1)
    acc = float(jnp.mean((preds == ev["label"]).astype(jnp.float32)))
    assert acc > 0.9, f"accuracy {acc}"


def test_step_phases_are_named_in_the_lowered_step():
    """``train.grad`` and ``train.optimizer`` are trace-time spans and
    named scopes afterwards: the device ops of an inner step carry them
    (forward and backward apart inside ``train.grad`` by jvp / transpose)."""
    topo = DenseTopology(2)
    model, cfg, init = _mlp_setup(topo, hidden=8)
    step = make_simulated_train_step(cfg, mlp_loss_fn(model))
    state = init_stacked_state(cfg, init, jax.random.key(0), topo.world_size)
    data = SyntheticClassification(n=64)
    (batch,) = round_batches(data, topo.world_size, h=cfg.h, batch=4, rounds=1)
    text = step.lower(state, batch).as_text(debug_info=True)
    assert "train.inner_loop" in text
    assert "train.grad/jvp" in text and "transpose(train.grad)" in text
    assert "train.optimizer" in text


def test_collective_matches_simulated_trajectory():
    """Same seeds, same data => the shard_map/ppermute backend and the
    mixing-matrix backend produce the same training trajectory."""
    topo = RingTopology(4)
    model, cfg, init = _mlp_setup(topo, h=2, hidden=16)
    data = SyntheticClassification(n=1024)
    loss_fn = mlp_loss_fn(model)

    sim_step = make_simulated_train_step(cfg, loss_fn)
    wmesh = WorkerMesh.create(topo, platform="cpu")
    col_step = make_collective_train_step(cfg, loss_fn, wmesh)

    state = init_stacked_state(cfg, init, jax.random.key(1), topo.world_size)
    sim_state = state
    col_state = wmesh.shard_stacked(state)

    sim_metrics, col_metrics = None, None
    for batch in round_batches(data, topo.world_size, h=cfg.h, batch=32, rounds=5):
        sim_state, sim_metrics = sim_step(sim_state, batch)
        col_state, col_metrics = col_step(col_state, batch)

    assert float(sim_metrics["loss"]) == pytest.approx(
        float(col_metrics["loss"]), rel=1e-4
    )
    assert float(sim_metrics["consensus_error"]) == pytest.approx(
        float(col_metrics["consensus_error"]), rel=1e-3, abs=1e-5
    )
    for a, b in zip(jax.tree.leaves(sim_state.params), jax.tree.leaves(col_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_torus_collective_trajectory():
    """Multi-axis (torus) topology through the collective backend with the
    SAME flat-stacked inputs as the simulated backend — the two must agree
    (BASELINE.json configs[3] is torus gossip)."""
    from consensusml_tpu.topology import TorusTopology

    topo = TorusTopology(2, 4)
    model, cfg, init = _mlp_setup(topo, h=1, hidden=16)
    data = SyntheticClassification(n=1024)
    loss_fn = mlp_loss_fn(model)

    sim_step = make_simulated_train_step(cfg, loss_fn)
    wmesh = WorkerMesh.create(topo, platform="cpu")
    col_step = make_collective_train_step(cfg, loss_fn, wmesh)

    state = init_stacked_state(cfg, init, jax.random.key(9), topo.world_size)
    sim_state, col_state = state, wmesh.shard_stacked(state)
    for batch in round_batches(data, topo.world_size, h=1, batch=16, rounds=3):
        sim_state, sm = sim_step(sim_state, batch)
        col_state, cm = col_step(col_state, batch)
    assert float(sm["loss"]) == pytest.approx(float(cm["loss"]), rel=1e-4)
    for a, b in zip(jax.tree.leaves(sim_state.params), jax.tree.leaves(col_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_int8_small_leaf_wire_stays_small():
    """Regression: int8 chunking must not balloon small tensors (e.g. the
    k values of a top-k payload) to a full zero-padded chunk."""
    from consensusml_tpu.compress import Int8Compressor

    wire = Int8Compressor(chunk=256).wire_bytes((10,), jnp.float32)
    assert wire == 10 + 4  # 10 int8 + one f32 scale — not 256 + 4


def test_local_sgd_h_steps_reduce_comm_rounds():
    """H=4 inner steps: one gossip round per 4 optimizer steps, still
    converges (BASELINE.json configs[2] pattern, small scale)."""
    topo = RingTopology(4)
    model, cfg, init = _mlp_setup(topo, h=4, lr=5e-3)
    data = SyntheticClassification(n=2048)
    step = make_simulated_train_step(cfg, mlp_loss_fn(model))
    state = init_stacked_state(cfg, init, jax.random.key(2), topo.world_size)
    losses = []
    errs = []
    for batch in round_batches(data, topo.world_size, h=4, batch=32, rounds=40, seed=1):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        errs.append(float(m["consensus_error"]))
    assert losses[-1] < 0.5 * losses[0]
    # ring gossip doesn't zero the error, but it must stay bounded and
    # far below the scale of the initial random-init disagreement
    assert errs[-1] < errs[0]


def test_compressed_local_sgd_converges():
    """Top-k compressed gossip (CHOCO) still trains."""
    topo = RingTopology(4)
    model, cfg, init = _mlp_setup(
        topo, h=2, compressor=TopKCompressor(ratio=0.25), gamma=0.5
    )
    data = SyntheticClassification(n=2048)
    step = make_simulated_train_step(cfg, mlp_loss_fn(model))
    state = init_stacked_state(cfg, init, jax.random.key(3), topo.world_size)
    losses = []
    for batch in round_batches(data, topo.world_size, h=2, batch=32, rounds=40, seed=2):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 * losses[0]
