"""Push-sum (ratio consensus) properties:

1. On symmetric topologies without faults it equals plain gossip exactly
   (the mass stays 1).
2. Column stochasticity: the masked operator conserves total mass for ANY
   alive pattern on ANY (directed) topology, so all workers converge to
   the exact initial network mean — the property receive-side masked
   mixing provably lacks on directed graphs.
3. Collective (ppermute) and simulated (matrix) backends agree.
4. End-to-end: local-SGD with faults on a DIRECTED topology (rejected for
   plain gossip) trains under push_sum=True.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest


from consensusml_tpu.comm import WorkerMesh, simulated
from consensusml_tpu.consensus import (
    ConsensusEngine,
    FaultConfig,
    GossipConfig,
    PushSumState,
    pushsum_init,
    pushsum_matrix,
    pushsum_round_collective,
    pushsum_round_simulated,
)
from consensusml_tpu.topology import (
    OnePeerExponentialTopology,
    RingTopology,
    TorusTopology,
    topology_from_name,
)


def _directed_phase(n):
    """A single directed one-peer phase (doubly stochastic, asymmetric)."""
    topo = OnePeerExponentialTopology(n)
    phase = topo.phases[1]  # offset 2: asymmetric for n > 4
    assert not phase.symmetric
    return phase


# ---------------------------------------------------------------------------
# operator-level properties (simulated backend)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["ring", "torus", "dense", "exp"])
def test_pushsum_equals_plain_gossip_when_symmetric(name):
    topo = topology_from_name(name, 8)
    w = simulated.mixing_matrix(topo)
    rng = np.random.default_rng(0)
    x = {"a": jnp.asarray(rng.normal(size=(8, 3, 4)), jnp.float32)}
    state = pushsum_init(8)
    z, new_state = pushsum_round_simulated(x, state, w)
    want = simulated.mix_tree_stacked(x, w)
    np.testing.assert_allclose(np.asarray(z["a"]), np.asarray(want["a"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_state.w), 1.0, rtol=1e-6)


def test_pushsum_matrix_column_stochastic_any_alive_pattern():
    phase = _directed_phase(8)
    w = simulated.mixing_matrix(phase)
    rng = np.random.default_rng(1)
    for _ in range(20):
        alive = jnp.asarray(rng.integers(0, 2, size=8), jnp.float32)
        c = np.asarray(pushsum_matrix(w, alive))
        np.testing.assert_allclose(c.sum(axis=0), 1.0, atol=1e-6)
        assert (c >= -1e-12).all()
        # dead workers keep exactly their own value
        for i in np.where(np.asarray(alive) == 0)[0]:
            want = np.zeros(8)
            want[i] = 1.0
            np.testing.assert_allclose(c[i], want, atol=1e-12)


def test_pushsum_reaches_exact_mean_on_directed_graph_with_faults():
    """Masked push-sum converges to the TRUE initial mean; receive-side
    masked mixing on the same directed sequence drifts away from it."""
    n = 8
    topo = OnePeerExponentialTopology(n)
    # one phase alone (offset 2) is a disconnected graph; the full periodic
    # schedule is connected, so rotate through it like the trainer does
    ws = [simulated.mixing_matrix(p) for p in topo.phases]
    rng = np.random.default_rng(2)
    x0 = jnp.asarray(rng.normal(size=(n, 5)), jnp.float32)
    mean0 = np.asarray(x0).mean(axis=0)

    x, state = {"p": x0}, pushsum_init(n)
    for t in range(300):
        alive = jnp.asarray(rng.integers(0, 2, size=n) | (rng.random(n) < 0.5), jnp.float32)
        # ensure not everyone is dead
        alive = alive.at[t % n].set(1.0)
        x, state = pushsum_round_simulated(x, state, ws[t % len(ws)], alive)
    got = np.asarray(x["p"])
    np.testing.assert_allclose(got, np.broadcast_to(mean0, got.shape), atol=1e-4)


def test_receive_side_masking_biases_mean_on_directed_graph():
    """The counterexample motivating push-sum (documents the engine's
    restriction): receive-side masking on a directed graph moves the mean."""
    from consensusml_tpu.consensus import masked_mixing_matrix

    n = 8
    phase = _directed_phase(n)
    w = simulated.mixing_matrix(phase)
    alive = jnp.asarray([1, 1, 0, 1, 1, 1, 1, 1], jnp.float32)
    wp = np.asarray(masked_mixing_matrix(w, alive))
    # rows sum to 1 (no blow-up) but columns do NOT (mean shifts)
    np.testing.assert_allclose(wp.sum(axis=1), 1.0, atol=1e-6)
    assert not np.allclose(wp.sum(axis=0), 1.0, atol=1e-6)


# ---------------------------------------------------------------------------
# asymmetric, TIME-VARYING alive masks: drop mid-sequence, rejoin later
# ---------------------------------------------------------------------------


def _mask_sequence(n, rounds):
    """Deterministic churn-shaped mask sequence: worker 2 drops at round 3
    and rejoins two rounds later; worker 5 drops at round 6 and rejoins at
    round 8; everyone else stays up."""
    masks = []
    for t in range(rounds):
        a = np.ones(n, np.float32)
        if 3 <= t < 5:
            a[2] = 0.0
        if 6 <= t < 8 and n > 5:
            a[5] = 0.0
        masks.append(jnp.asarray(a))
    return masks


def test_pushsum_mass_conserved_under_time_varying_asymmetric_masks():
    """Mass conservation + weight convexity, round by round, while the
    alive mask CHANGES between rounds of a directed time-varying
    schedule (the swarm drop→rejoin scenario)."""
    n, rounds = 8, 10
    topo = OnePeerExponentialTopology(n)
    ws = [simulated.mixing_matrix(p) for p in topo.phases]
    rng = np.random.default_rng(4)
    x = {"p": jnp.asarray(rng.normal(size=(n, 6)), jnp.float32)}
    state = pushsum_init(n)
    mass_sum0 = float(np.sum(np.asarray(state.w)))
    num_sum0 = np.asarray(x["p"]).astype(np.float64).sum(axis=0)
    for t, alive in enumerate(_mask_sequence(n, rounds)):
        w_mat = ws[t % len(ws)]
        # weight CONVEXITY of the masked operator every round: columns
        # sum to 1 and every entry stays in [0, 1]
        c = np.asarray(pushsum_matrix(w_mat, alive))
        np.testing.assert_allclose(c.sum(axis=0), 1.0, atol=1e-6)
        assert (c >= -1e-12).all() and (c <= 1.0 + 1e-12).all()
        x, state = pushsum_round_simulated(x, state, w_mat, alive)
        # total mass and total (re-biased) numerator are conserved under
        # EVERY mask, including the rounds where membership just changed
        w_now = np.asarray(state.w, np.float64)
        np.testing.assert_allclose(w_now.sum(), mass_sum0, rtol=1e-5)
        num_now = (
            np.asarray(x["p"], np.float64) * w_now[:, None]
        ).sum(axis=0)
        np.testing.assert_allclose(num_now, num_sum0, rtol=1e-4, atol=1e-4)
    # and the de-biased estimates still head for the TRUE initial mean
    mean0 = num_sum0 / n
    for _ in range(120):
        for w_mat in ws:
            x, state = pushsum_round_simulated(x, state, w_mat)
    np.testing.assert_allclose(
        np.asarray(x["p"]), np.broadcast_to(mean0, (n, 6)), atol=1e-3
    )


def test_pushsum_round_collective_time_varying_asymmetric_masks():
    """pushsum_round_collective under the SAME drop-mid-sequence/
    rejoin-two-rounds-later mask sequence: per-round mass conservation,
    cross-backend agreement with the matrix operator, and weight
    positivity for every alive worker."""
    import functools

    from jax.sharding import PartitionSpec as P

    n, rounds = 8, 10
    topo = OnePeerExponentialTopology(n)
    phases = list(topo.phases)
    ws = [simulated.mixing_matrix(p) for p in phases]
    wmesh = WorkerMesh.create(
        phases[0], devices=jax.devices("cpu")[:n]
    )
    worker = P(*phases[0].axis_names)
    shard_map = jax.shard_map

    def one_round(phase):
        @jax.jit
        @functools.partial(
            shard_map,
            mesh=wmesh.mesh,
            in_specs=(worker, worker, worker),
            out_specs=(worker, worker),
        )
        def f(x, w, alive):
            sq = lambda v: v.reshape(v.shape[1:])
            z, st = pushsum_round_collective(
                {"p": sq(x)}, PushSumState(w=sq(w)), phase, sq(alive)
            )
            un = lambda v: v.reshape((1,) + v.shape)
            return un(z["p"]), un(st.w)

        return f

    steps = [one_round(p) for p in phases]
    rng = np.random.default_rng(5)
    x0 = jnp.asarray(rng.normal(size=(n, 6)), jnp.float32)
    x_col, w_col = x0, jnp.ones((n,), jnp.float32)
    x_sim, st_sim = {"p": x0}, pushsum_init(n)
    for t, alive in enumerate(_mask_sequence(n, rounds)):
        x_col, w_col = steps[t % len(phases)](x_col, w_col, alive)
        x_sim, st_sim = pushsum_round_simulated(
            x_sim, st_sim, ws[t % len(ws)], alive
        )
        w_host = np.asarray(w_col, np.float64)
        # mass conserved every round of the asymmetric masked sequence
        np.testing.assert_allclose(w_host.sum(), float(n), rtol=1e-5)
        # weights stay a convex combination: non-negative everywhere,
        # strictly positive for alive workers
        assert (w_host >= -1e-6).all()
        assert (w_host[np.asarray(alive) > 0] > 0).all()
        # the two backends run the identical operator
        np.testing.assert_allclose(
            np.asarray(x_col), np.asarray(x_sim["p"]), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            w_host, np.asarray(st_sim.w), rtol=1e-5, atol=1e-6
        )


# ---------------------------------------------------------------------------
# collective backend agreement
# ---------------------------------------------------------------------------


def _collective_round(topo, x_stacked, w_stacked, alive_stacked):
    wmesh = WorkerMesh.create(topo, devices=jax.devices("cpu")[: topo.world_size])
    from jax.sharding import PartitionSpec as P
    import functools

    worker = P(*topo.axis_names)
    n_axes = len(topo.mesh_shape)

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=wmesh.mesh,
        in_specs=(worker, worker, worker),
        out_specs=(worker, worker),
    )
    def f(x, w, alive):
        sq = lambda t: jax.tree.map(lambda v: v.reshape(v.shape[n_axes:]), t)
        x, w, alive = sq(x), sq(w), sq(alive)
        z, st = pushsum_round_collective(
            {"p": x}, pushsum_init().__class__(w=w), topo, alive
        )
        un = lambda t: jax.tree.map(lambda v: v.reshape((1,) * n_axes + v.shape), t)
        return un(z["p"]), un(st.w)

    to_mesh = lambda v: v.reshape(topo.mesh_shape + v.shape[1:])
    z, wn = f(to_mesh(x_stacked), to_mesh(w_stacked), to_mesh(alive_stacked))
    flat = lambda v: np.asarray(v).reshape((topo.world_size,) + v.shape[n_axes:])
    return flat(z), flat(wn)


@pytest.mark.parametrize(
    "topo",
    [RingTopology(8), TorusTopology(2, 4), topology_from_name("dense", 8),
     _directed_phase(8)],
    ids=["ring", "torus", "dense", "directed"],
)
def test_collective_matches_simulated(topo):
    n = topo.world_size
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(n, 6)), jnp.float32)
    w0 = jnp.asarray(rng.uniform(0.5, 1.5, size=n), jnp.float32)
    alive = jnp.asarray([1, 0, 1, 1, 1, 0, 1, 1], jnp.float32)

    wmat = simulated.mixing_matrix(topo)
    z_sim, st_sim = pushsum_round_simulated(
        {"p": x}, pushsum_init(n).__class__(w=w0), wmat, alive
    )
    z_col, w_col = _collective_round(topo, x, w0, alive)
    np.testing.assert_allclose(z_col, np.asarray(z_sim["p"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w_col, np.asarray(st_sim.w), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# engine + trainer integration
# ---------------------------------------------------------------------------


def test_engine_rejects_directed_faults_without_pushsum_and_accepts_with():
    topo = OnePeerExponentialTopology(8)
    with pytest.raises(NotImplementedError, match="push_sum"):
        GossipConfig(topology=topo, faults=FaultConfig(drop_prob=0.2))
    GossipConfig(topology=topo, faults=FaultConfig(drop_prob=0.2), push_sum=True)


def test_local_sgd_trains_with_pushsum_faults_on_directed_topology():
    from consensusml_tpu.data import SyntheticClassification, round_batches
    from consensusml_tpu.models import MLP, mlp_loss_fn
    from consensusml_tpu.train import (
        LocalSGDConfig,
        init_stacked_state,
        make_simulated_train_step,
    )

    n = 8
    topo = OnePeerExponentialTopology(n)
    model = MLP(hidden=16)
    cfg = LocalSGDConfig(
        gossip=GossipConfig(
            topology=topo, faults=FaultConfig(drop_prob=0.25), push_sum=True
        ),
        optimizer=optax.sgd(0.1),
        h=2,
    )
    step = make_simulated_train_step(cfg, mlp_loss_fn(model))
    state = init_stacked_state(
        cfg,
        lambda r: model.init(r, jnp.zeros((1, 8, 8, 1)))["params"],
        jax.random.key(0),
        n,
    )
    data = SyntheticClassification(n=512, image_shape=(8, 8, 1))
    losses = []
    for batch in round_batches(data, n, h=2, batch=16, rounds=30, seed=0):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1])
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    # push-sum mass stays positive and near 1 on average
    w = np.asarray(state.gossip.w)
    assert (w > 0).all()
    np.testing.assert_allclose(w.mean(), 1.0, atol=1e-3)
