"""Rounds of the shipped ``xing4_ep8`` smoke recipe on the stacked backend
against the benchmark's plain rounds (``benchmarks/reference/train_xing4.py``):
both losses, Adam's first moment, the expert layers' counters and the first
step's routes, latent-attention and stream sizes in the round's metrics, the
recipe through ``train.main`` — and the reference with each of the benchmark's
faults planted, which has to leave them. Then what this configuration may not
move: with ``streams`` = 1 the decoder is the one the other families had, so the
rounds of the recipes that the benchmark's other cells run lower to the StableHLO
they had before it (sha256). ``test_xing4.py`` has the helpers."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_xing4 import FAULTS, ref, ref_rounds, rel, sizes_of

from consensusml_tpu import configs
from consensusml_tpu.models.nemotron_h import NemotronHLM, nemotron_h_loss_fn
from consensusml_tpu.obs import get_registry
from consensusml_tpu.train import make_collective_train_step, make_simulated_train_step
from consensusml_tpu.train.local_sgd import TrainState


def _state(cfg, params, workers):
    return TrainState(
        step=jnp.zeros((workers,), jnp.int32), params=params, model_state={},
        opt_state=jax.vmap(cfg.optimizer.init)(params),
        gossip=cfg.engine().init_state({"params": params, "model_state": {}}, world_size=workers),
        rng=jax.random.split(jax.random.key(0), workers),
    )


@functools.lru_cache(maxsize=None)
def _smoke_rounds(workers=1, rounds=3):
    bundle = configs.build("xing4_ep8", "smoke", world=workers)
    model = NemotronHLM(config=dataclasses.replace(bundle.model.config, dtype=jnp.float32))
    sizes = sizes_of(bundle.model.config)
    cfg = bundle.cfg
    step = make_simulated_train_step(cfg, nemotron_h_loss_fn(model))
    seeds = [11 + i for i in range(workers)]
    params = jax.tree.map(lambda *xs: jnp.stack(xs), *[ref.init_params(s, sizes) for s in seeds])
    state = _state(cfg, params, workers)
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
              "warmup_steps": 0}
    return sizes, seeds, rows, losses, mu1, metrics_seen, recipe, bundle


def test_three_rounds_follow_the_reference():
    sizes, seeds, rows, losses, mu1, metrics, recipe, bundle = _smoke_rounds()
    truth = ref_rounds.follow(ref.init_params(seeds[0], sizes), [r[0] for r in rows], sizes, recipe)
    np.testing.assert_allclose(losses, truth["loss"], atol=3e-5)
    # the two-ahead loss rides out with the counters, summed over the round's inner steps
    np.testing.assert_allclose([m["mtp_loss"] / bundle.cfg.h for m in metrics], truth["mtp_loss"], atol=3e-5)
    gaps = jax.tree.leaves(jax.tree.map(rel, mu1, truth["mu"]))
    assert max(gaps) < 1e-3
    c = bundle.model.config
    pairs = bundle.cfg.h * 2 * 32 * c.top_k
    for m in metrics:
        assert m["moe_rows"].shape == (len(c.expert_layers), c.held) == (2, 4)
        assert (m["moe_rows"].sum(axis=1) + m["moe_absent_pairs"] == pairs).all()
    full = configs.build("xing4_ep8", "full")
    assert full.base_warmup_steps == 20_000 and full.base_lr == 1e-4 and full.cfg.h == 2
    assert full.cfg.gossip.compressor is None and full.model.config.score_correction == "centred"
    assert full.model.config.remat and full.model.config.loss_vocab_chunk == 4096


def test_the_first_steps_routes_and_sizes_ride_out_of_the_round():
    """``LossAux.first_step``: what the round's FIRST inner step chose and its
    latent attentions and streams put out, one entry a stacked worker, out of
    the compiled round itself (the benchmark compares these with the reference's)."""
    sizes, seeds, rows, _, _, metrics, recipe, bundle = _smoke_rounds(workers=2, rounds=1)
    c, m = bundle.model.config, metrics[0]
    assert m["moe_chosen"].shape == (2, len(c.expert_layers), 2 * 32, c.top_k)
    assert m["mla_rms"].shape == (2, c.pattern.count("L") + 1, 2, c.heads)
    assert m["mhc_stream_rms"].shape == (2, len(c.pattern) + 2, 2, c.streams)
    assert "gdn_rms" not in m and "ssm_scan_rms" not in m
    for w in range(2):
        truth = ref_rounds.follow(ref.init_params(seeds[w], sizes), [rows[0][w]], sizes, recipe)
        routes = [r.reshape(2, 32, c.top_k) for r in m["moe_chosen"][w]]
        assert ref_rounds.routing_disagreement(routes, list(truth["routes"])) == 0.0
        assert ref_rounds.rms_gap(list(m["mla_rms"][w]), truth["mla_rms"]) < 1e-4
        assert ref_rounds.rms_gap(list(m["mhc_stream_rms"][w]), truth["stream_rms"]) < 1e-4
    # the other worker's rows and weights choose otherwise: stacked, not summed or mixed
    other = [r.reshape(2, 32, c.top_k) for r in m["moe_chosen"][0]]
    assert ref_rounds.routing_disagreement(other, list(truth["routes"])) > 0.1


def test_the_collective_round_hands_out_what_the_stacked_one_does():
    from consensusml_tpu.comm import WorkerMesh

    workers = 2
    bundle = configs.build("xing4_ep8", "smoke", world=workers)
    model = NemotronHLM(config=dataclasses.replace(bundle.model.config, dtype=jnp.float32))
    sizes, cfg = sizes_of(bundle.model.config), bundle.cfg
    make = lambda: _state(cfg, jax.tree.map(  # made for each backend: a round donates it
        lambda *xs: jnp.stack(xs), *[ref.init_params(s, sizes) for s in (11, 12)]), workers)
    batch = {"input_ids": jax.random.randint(jax.random.key(100), (workers, cfg.h, 2, 32), 0, 64)}
    loss_fn = nemotron_h_loss_fn(model)
    _, stacked = make_simulated_train_step(cfg, loss_fn)(make(), batch)
    wmesh = WorkerMesh.create(cfg.gossip.topology, platform="cpu")
    _, meshed = make_collective_train_step(cfg, loss_fn, wmesh)(wmesh.shard_stacked(make()), batch)
    for key in ("moe_rows", "moe_absent_pairs", "moe_chosen"):
        np.testing.assert_array_equal(np.asarray(meshed[key]), np.asarray(stacked[key]))
    for key in ("mla_rms", "mhc_stream_rms", "mtp_loss"):
        np.testing.assert_allclose(meshed[key], stacked[key], rtol=1e-5)
    assert float(meshed["loss"]) == pytest.approx(float(stacked["loss"]), rel=1e-5)


def test_the_recipe_trains_rounds_through_train_main(capsys):
    import train

    before = get_registry().counter("consensusml_mhc_sinkhorn_iters_total", labels={"layer": "0"}).value
    rc = train.main(["--config", "xing4_ep8", "--device", "cpu", "--rounds", "6"])
    out = capsys.readouterr().out
    assert rc == 0 and "final: loss=" in out
    final = float(out.split("final: loss=")[1].split()[0])
    # Adam at the smoke recipe's 3e-3 learns the chain: a uniform guess reads (1 + 0.3) ln 64 = 5.41
    assert np.isfinite(final) and final < 5.3
    assert "xing4_ep8" in configs.names()
    reg = get_registry()
    assert reg.counter("consensusml_mhc_sinkhorn_iters_total", labels={"layer": "0"}).value > before
    assert reg.counter("consensusml_mla_flash_impl_total", labels={"layer": "0", "impl": "xla"}).value > 0
    assert reg.counter("consensusml_moe_rows_total", labels={"layer": "3", "expert": "0"}).value >= 0
    gauge = reg.gauge("consensusml_mtp_loss").value
    assert 0 < gauge < 4.3  # the mean of a round, not the sum over its inner steps (ln 64 = 4.16)


@pytest.mark.parametrize("fault", FAULTS)
def test_faulty_rounds_leave_the_reference(fault):
    """The rounds with a fault planted in the reference put in the program's
    place: the first moment moves beyond what a sound run reads, and the numbers
    read from the round's own first step see what they were made for."""
    sizes, seeds, rows, losses, mu1, metrics, recipe, _ = _smoke_rounds()
    follow = lambda faults=(): ref_rounds.follow(
        ref.init_params(seeds[0], sizes), [r[0] for r in rows], sizes, recipe, faults=faults)
    truth, side = _truth(follow), follow((fault,))
    sound = max(jax.tree.leaves(jax.tree.map(rel, mu1, truth["mu"])))
    faulty = max(jax.tree.leaves(jax.tree.map(rel, side["mu"], truth["mu"])))
    assert sound < 1e-3 < 0.05 < faulty
    if fault == "top3":
        assert ref_rounds.routing_disagreement(list(side["routes"]), list(truth["routes"])) >= 1 / sizes["top_k"]
    if fault in ("sinkhorn_1", "one_stream"):
        program = ref_rounds.rms_gap(list(metrics[0]["mhc_stream_rms"][0]), truth["stream_rms"])
        assert ref_rounds.rms_gap(side["stream_rms"], truth["stream_rms"]) > 0.01 > 1e-4 > program
    if fault == "no_mtp":
        assert abs(side["loss"][0] - truth["loss"][0]) > 1.0


_TRUTH = []


def _truth(follow):
    if not _TRUTH:
        _TRUTH.append(follow())
    return _TRUTH[0]


# -- what the new configuration may not move --------------------------------------

# sha256 of the lowered round (StableHLO text, smoke sizes, CPU) of the recipes that the
# benchmark's other cells run, as the tree before this configuration lowered them (PR 32's
# tree; test_qwen3_next_rounds.py holds the dense and the hybrid recipes' the same way)
PARENT_ROUNDS = {
    ("qwen3_next_ep16", 1, "simulated"): "dcc1b60e80046691",
    ("qwen3_next_ep16", 1, "collective"): "5c155dd401767857",
    ("qwen3_next_ep16", 2, "simulated"): "31c181ca9ae461ee",
    ("qwen3_next_ep16", 2, "collective"): "900bfe3808023adf",
}


@pytest.mark.parametrize("recipe, workers, backend", sorted(PARENT_ROUNDS))
def test_the_other_pattern_recipes_rounds_lower_as_before(recipe, workers, backend):
    """``streams`` = 1 is the decoder the other families had, the loss with no
    module the loss they had, ``rope_frequencies`` without yarn the table it
    made and attention without a ``scale`` the attention it was: byte for byte."""
    from consensusml_tpu.comm import WorkerMesh

    bundle = configs.build(recipe, "smoke", world=workers)
    cfg = bundle.cfg
    shapes = jax.eval_shape(
        lambda rng: _state(cfg, jax.vmap(bundle.init_params)(jax.random.split(rng, workers)), workers),
        jax.random.key(0))
    batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), next(iter(bundle.batches(1, 0))))
    if backend == "simulated":
        step = make_simulated_train_step(cfg, bundle.loss_fn)
    else:
        step = make_collective_train_step(
            cfg, bundle.loss_fn, WorkerMesh.create(cfg.gossip.topology, platform="cpu"))
    text = step.lower(shapes, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_ROUNDS[recipe, workers, backend]
