"""Rounds of the shipped ``nemotron_h_ep16`` smoke recipe on the stacked
backend against the benchmark's plain rounds (``benchmarks/reference/
train_nemotron_h.py``): losses, Adam's first moment, the expert layers'
counters and the first step's routes and scan sizes in the round's metrics,
the step size's warm-up — and the reference with each of the benchmark's
faults planted, which has to leave them. Groups 5 and 6 of
``test_nemotron_h.py``, which has the helpers."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from test_nemotron_h import ref, rel, sizes_of

from reference import train_nemotron_h as ref_rounds

from consensusml_tpu import configs
from consensusml_tpu.models import moe
from consensusml_tpu.models.nemotron_h import NemotronHLM, nemotron_h_loss_fn
from consensusml_tpu.obs import get_registry
from consensusml_tpu.train import make_collective_train_step, make_simulated_train_step
from consensusml_tpu.train.local_sgd import TrainState
from consensusml_tpu.train.schedules import build_optimizer


@functools.lru_cache(maxsize=None)
def _smoke_rounds(workers=1, rounds=3, warmup=0):
    bundle = configs.build("nemotron_h_ep16", "smoke", world=workers)
    model = NemotronHLM(config=dataclasses.replace(bundle.model.config, dtype=jnp.float32))
    sizes = sizes_of(bundle.model.config)
    cfg = bundle.cfg
    if warmup:  # as the full recipe states it
        cfg = dataclasses.replace(cfg, optimizer=build_optimizer(
            optax.adam, peak_lr=bundle.base_lr, warmup_steps=warmup))
    step = make_simulated_train_step(cfg, nemotron_h_loss_fn(model))
    seeds = [11 + i for i in range(workers)]
    params = jax.tree.map(lambda *xs: jnp.stack(xs), *[ref.init_params(s, sizes) for s in seeds])
    state = TrainState(
        step=jnp.zeros((workers,), jnp.int32), params=params, model_state={},
        opt_state=jax.vmap(cfg.optimizer.init)(params),
        gossip=cfg.engine().init_state({"params": params, "model_state": {}}, world_size=workers),
        rng=jax.random.split(jax.random.key(0), workers),
    )
    rows = [np.asarray(jax.random.randint(jax.random.key(100 + r), (workers, cfg.h, 2, 32), 0, 64))
            for r in range(rounds)]
    losses, metrics_seen, mu1 = [], [], None
    for r in range(rounds):
        state, metrics = step(state, {"input_ids": jnp.asarray(rows[r])})
        losses.append(float(metrics["loss"]))
        metrics_seen.append(jax.device_get(metrics))
        if r == 0:
            mu1 = jax.tree.map(lambda x: np.asarray(x[0]), state.opt_state[0].mu)
    recipe = {"learning_rate": bundle.base_lr, "adam_b1": 0.9, "adam_b2": 0.999, "adam_eps": 1e-8,
              "warmup_steps": warmup}
    return sizes, seeds, rows, losses, mu1, metrics_seen, recipe, bundle


def test_three_rounds_follow_the_reference():
    sizes, seeds, rows, losses, mu1, metrics, recipe, bundle = _smoke_rounds()
    truth = ref_rounds.follow(ref.init_params(seeds[0], sizes), [r[0] for r in rows], sizes, recipe)
    np.testing.assert_allclose(losses, truth["loss"], atol=3e-5)
    gaps = jax.tree.leaves(jax.tree.map(rel, mu1, truth["mu"]))
    assert max(gaps) < 1e-3
    # the round's metrics carry what the expert layers counted, summed over h
    c = bundle.model.config
    pairs = bundle.cfg.h * 2 * 32 * c.top_k
    for m in metrics:
        assert m["moe_rows"].shape == (len(c.expert_layers), c.held)
        assert (m["moe_rows"].sum(axis=1) + m["moe_absent_pairs"] == pairs).all()


def test_the_step_size_warms_up_as_the_reference_has_it():
    """The full recipe's linear warm-up (here over 4 steps: rounds 1 and 2 warm,
    round 3 at the stated size): the first step moves nothing, and the rounds
    follow the reference, which states the same ramp in its own words."""
    sizes, seeds, rows, losses, mu1, _, recipe, _ = _smoke_rounds(warmup=4)
    truth = ref_rounds.follow(ref.init_params(seeds[0], sizes), [r[0] for r in rows], sizes, recipe)
    np.testing.assert_allclose(losses, truth["loss"], atol=3e-5)
    assert max(jax.tree.leaves(jax.tree.map(rel, mu1, truth["mu"]))) < 1e-3
    constant = _smoke_rounds()[3]
    assert abs(losses[0] - constant[0]) > 1e-4  # round 1's second step saw other weights
    full = configs.build("nemotron_h_ep16", "full")
    assert full.base_warmup_steps == 20_000 and full.base_lr == 1e-4
    tiny_tree = {"w": jnp.ones(3)}
    updates, _ = full.cfg.optimizer.update(tiny_tree, full.cfg.optimizer.init(tiny_tree), tiny_tree)
    assert not np.asarray(updates["w"]).any()  # step 0 of the ramp


def test_the_first_steps_routes_and_scan_sizes_ride_out_of_the_round():
    """``LossAux.first_step``: what the round's FIRST inner step chose and its
    scans put out, one entry a stacked worker, out of the compiled round itself
    (the benchmark compares these with the reference's, not a second program's)."""
    sizes, seeds, rows, _, _, metrics, recipe, bundle = _smoke_rounds(workers=2, rounds=1)
    c, m = bundle.model.config, metrics[0]
    assert m["moe_chosen"].shape == (2, len(c.expert_layers), 2 * 32, c.top_k)
    assert m["ssm_scan_rms"].shape == (2, c.pattern.count("M"), 2, c.mamba_heads)
    for w in range(2):
        truth = ref_rounds.follow(ref.init_params(seeds[w], sizes), [rows[0][w]], sizes, recipe)
        assert ref_rounds.routing_disagreement(list(m["moe_chosen"][w]), truth["routes"]) == 0.0
        assert ref_rounds.scan_rms_gap(list(m["ssm_scan_rms"][w]), truth["scan_rms"]) < 1e-4
    # the other worker's rows and weights choose otherwise: stacked, not summed or mixed
    assert ref_rounds.routing_disagreement(list(m["moe_chosen"][1]), truth["routes"]) == 0.0
    assert ref_rounds.routing_disagreement(list(m["moe_chosen"][0]), truth["routes"]) > 0.1


def test_the_collective_round_hands_out_what_the_stacked_one_does():
    """Counters summed over the mesh's workers, the first step's values gathered
    one entry a worker: the same metrics from ``make_collective_train_step``."""
    from consensusml_tpu.comm import WorkerMesh

    workers = 2
    bundle = configs.build("nemotron_h_ep16", "smoke", world=workers)
    model = NemotronHLM(config=dataclasses.replace(bundle.model.config, dtype=jnp.float32))
    sizes, cfg = sizes_of(bundle.model.config), bundle.cfg

    def state():  # made for each backend: a round donates it
        params = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[ref.init_params(s, sizes) for s in (11, 12)])
        return TrainState(
            step=jnp.zeros((workers,), jnp.int32), params=params, model_state={},
            opt_state=jax.vmap(cfg.optimizer.init)(params),
            gossip=cfg.engine().init_state(
                {"params": params, "model_state": {}}, world_size=workers),
            rng=jax.random.split(jax.random.key(0), workers),
        )

    batch = {"input_ids": jax.random.randint(jax.random.key(100), (workers, cfg.h, 2, 32), 0, 64)}
    loss_fn = nemotron_h_loss_fn(model)
    _, stacked = make_simulated_train_step(cfg, loss_fn)(state(), batch)
    wmesh = WorkerMesh.create(cfg.gossip.topology, platform="cpu")
    _, meshed = make_collective_train_step(cfg, loss_fn, wmesh)(wmesh.shard_stacked(state()), batch)
    for key in ("moe_rows", "moe_absent_pairs", "moe_chosen"):
        np.testing.assert_array_equal(np.asarray(meshed[key]), np.asarray(stacked[key]))
    np.testing.assert_allclose(meshed["ssm_scan_rms"], stacked["ssm_scan_rms"], rtol=1e-5)
    assert float(meshed["loss"]) == pytest.approx(float(stacked["loss"]), rel=1e-5)


def test_counters_sum_over_stacked_workers_and_reach_the_registry():
    _, _, _, _, _, metrics, _, bundle = _smoke_rounds(workers=2, rounds=1)
    c = bundle.model.config
    pairs = 2 * bundle.cfg.h * 2 * 32 * c.top_k
    m = dict(metrics[0])
    assert (m["moe_rows"].sum(axis=1) + m["moe_absent_pairs"] == pairs).all()
    counter = lambda layer, expert: get_registry().counter(
        "consensusml_moe_rows_total", labels={"layer": str(layer), "expert": str(expert)})
    before = counter(c.expert_layers[0], c.held_start).value
    moe.record_expert_counts(m["moe_rows"], m["moe_absent_pairs"], c.expert_layers, c.held_start)
    assert counter(c.expert_layers[0], c.held_start).value - before == metrics[0]["moe_rows"][0, 0]
    absent = get_registry().counter(
        "consensusml_moe_absent_pairs_total", labels={"layer": str(c.expert_layers[-1])})
    assert absent.value >= metrics[0]["moe_absent_pairs"][-1]


@pytest.mark.parametrize("fault", ["top5", "renorm_over_held", "no_state_carry", "half_batch"])
def test_faulty_rounds_leave_the_reference(fault):
    """Group 5 with a fault planted in the reference put in the program's
    place: losses or the first moment move beyond what a sound run reads."""
    sizes, seeds, rows, losses, mu1, _, recipe, _ = _smoke_rounds()
    follow = lambda faults=(): ref_rounds.follow(
        ref.init_params(seeds[0], sizes), [r[0] for r in rows], sizes, recipe, faults=faults)
    truth, side = follow(), follow((fault,))
    sound = max(jax.tree.leaves(jax.tree.map(rel, mu1, truth["mu"])))
    faulty = max(jax.tree.leaves(jax.tree.map(rel, side["mu"], truth["mu"])))
    assert sound < 1e-3 < 0.05 < faulty
    if fault in ("top5", "renorm_over_held"):
        share = ref_rounds.routing_disagreement(side["routes"], truth["routes"])
        assert share >= (1 / sizes["top_k"] if fault == "top5" else 0.0)
