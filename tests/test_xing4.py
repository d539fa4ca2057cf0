"""The ``xing4_0`` layer (latent attention with 192-wide keys beside 128-wide
values, a dense or an expert MLP, four hyper-connected residual streams, a
multi-token-prediction module: the pattern decoder's kinds ``L`` and ``D``,
``streams`` and ``mtp``) against the benchmark's plain reference, at tiny sizes
on the CPU.

The reference (``benchmarks/reference/xing4.py``: float32, dense attention over
materialised keys, Sinkhorn as twenty plain steps, a loop over held experts, the
two losses) imports nothing of the program and makes the weights; the program is
handed them. Groups: the whole decoder (logits of both heads, both losses, every
leaf's gradient); Sinkhorn's rows and columns; each planted fault moves its
number; the eight shares of an expert layer add up to the uncut layer; yarn's
frequencies against the formula; the full cut's parameter count; spans and
counters. The rounds of the shipped recipe, and that ``streams`` = 1 is the
decoder the other families had, are in ``test_xing4_rounds.py``.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if _BENCH not in sys.path:
    sys.path.append(_BENCH)

from drivers.train_xing4 import program_sizes as sizes_of  # noqa: E402
from reference import train_xing4 as ref_rounds  # noqa: E402
from reference import xing4 as ref  # noqa: E402
from consensusml_tpu.models import attention, hyper_connections, moe  # noqa: E402
from consensusml_tpu.models import nemotron_h as decoder  # noqa: E402
from consensusml_tpu.models.nemotron_h import NemotronHLM, nemotron_h_loss_fn, xing4_tiny  # noqa: E402
from consensusml_tpu.obs import get_registry, get_tracer  # noqa: E402


def tiny(**overrides) -> NemotronHLM:
    return xing4_tiny(**{"dtype": jnp.float32, "remat": False, **overrides})


def ids_for(model, rows=2, seq=21, seed=0):
    return jax.random.randint(jax.random.key(seed), (rows, seq), 0, model.config.vocab_size)


def rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def reference_side(params, ids, sizes, faults=()):
    """(both heads' logits, (loss, two-ahead loss), what the sub-blocks showed)."""

    def f(p):
        hidden, ahead, shown = ref.hidden_states(p, ids, sizes, faults=faults)
        loss, (_, mtp_loss) = ref.lm_loss(p, ids, sizes, faults=faults, with_shown=True)
        return (ref.logits_of(p, hidden), ref.logits_of(p, ahead)), (loss, mtp_loss), shown

    return jax.jit(f)(params)


# -- 1. the decoder is the reference ------------------------------------------------


@pytest.mark.parametrize(
    "pattern, correction", [("LDLE", "zeros"), ("LDLE", "centred"), ("LDLDLELE", "centred")])
def test_decoder_matches_reference(pattern, correction):
    """Logits of both heads, both losses and every leaf's gradient."""
    model = tiny(pattern=pattern, score_correction=correction)
    sizes = sizes_of(model.config)
    assert sizes["layers"] == len(pattern) // 2 and sizes["dense_layers"] == pattern.count("D")
    params = ref.init_params(7, sizes)
    ids = ids_for(model)
    own = model.init(jax.random.key(1), ids)["params"]
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    (logits, ahead), counts = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    (want, want_ahead), (want_loss, want_mtp), shown = reference_side(params, ids, sizes)
    assert float(jnp.abs(logits - want).max()) < 2e-5 and float(jnp.abs(ahead - want_ahead).max()) < 2e-5
    loss_fn = nemotron_h_loss_fn(model)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {}, {"input_ids": ids}, None), has_aux=True))(params)
    want_grads = jax.jit(jax.grad(lambda p: ref.lm_loss(p, ids, sizes)))(params)
    assert abs(float(loss - want_loss)) < 2e-5 and abs(float(aux.metrics["mtp_loss"] - want_mtp)) < 2e-5
    assert max(jax.tree.leaves(jax.tree.map(rel, grads, want_grads))) < 2e-4
    # what the step shows of itself: latent attention's output, every written stream, the routes
    blocks = len(pattern) + 2  # the module's two ride behind the decoder's
    assert aux.first_step["mhc_stream_rms"].shape == (blocks, 2, 4)
    assert aux.first_step["mla_rms"].shape == (len(pattern) // 2 + 1, 2, model.config.heads)
    assert ref_rounds.rms_gap(list(aux.first_step["mla_rms"]), shown["mla_rms"]) < 1e-4
    assert ref_rounds.rms_gap(list(aux.first_step["mhc_stream_rms"]), shown["stream_rms"]) < 1e-4
    rows = lambda r: r.reshape(2, -1, r.shape[-1])
    assert ref_rounds.routing_disagreement(
        [rows(r) for r in aux.first_step["moe_chosen"]], list(shown["routes"])) == 0.0
    assert aux.metrics["moe_rows"].shape == (len(model.config.expert_layers), model.config.held)


def test_remat_and_chunked_loss_change_nothing():
    plain = tiny()
    lean = tiny(remat=True, loss_vocab_chunk=16)
    params = ref.init_params(3, sizes_of(plain.config))
    batch = {"input_ids": ids_for(plain)}
    a, b = (
        jax.jit(jax.value_and_grad(lambda p, m=m: nemotron_h_loss_fn(m)(p, {}, batch, None)[0]))(params)
        for m in (plain, lean)
    )
    assert abs(float(a[0] - b[0])) < 1e-5
    assert max(jax.tree.leaves(jax.tree.map(rel, b[1], a[1]))) < 1e-4


def test_a_loss_mask_reaches_both_losses():
    model = tiny()
    params = ref.init_params(4, sizes_of(model.config))
    ids = ids_for(model, seq=16)
    loss_fn = jax.jit(lambda p, b: nemotron_h_loss_fn(model)(p, {}, b, None))
    whole, aux = loss_fn(params, {"input_ids": ids})
    half = jnp.concatenate([jnp.ones((2, 8)), jnp.zeros((2, 8))], axis=1)
    masked, aux_half = loss_fn(params, {"input_ids": ids, "loss_mask": half})
    assert abs(float(whole - masked)) > 1e-3
    assert abs(float(aux.metrics["mtp_loss"] - aux_half.metrics["mtp_loss"])) > 1e-4
    # the weighted sum: next-token + 0.3 x two-ahead
    alone = tiny(mtp_lambda=0.0)
    main, _ = jax.jit(lambda p, b: nemotron_h_loss_fn(alone)(p, {}, b, None))(params, {"input_ids": ids})
    assert float(whole) == pytest.approx(float(main) + 0.3 * float(aux.metrics["mtp_loss"]), rel=1e-6)


def test_bfloat16_stays_near_the_reference():
    model = xing4_tiny(remat=False)  # the shipped dtype
    sizes = sizes_of(model.config)
    params = ref.init_params(5, sizes)
    ids = ids_for(model)
    (logits, ahead), counts = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    (want, want_ahead), _, shown = reference_side(params, ids, sizes)
    assert rel(logits, want) < 0.03 and rel(ahead, want_ahead) < 0.03
    np.testing.assert_allclose(counts["mla_rms"], jnp.stack(shown["mla_rms"]), rtol=0.03)
    np.testing.assert_allclose(counts["mhc_stream_rms"], jnp.stack(shown["stream_rms"]), rtol=0.01)


def test_the_full_share_is_the_issues_913_million_parameters():
    model = decoder.xing4_share()
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    sizes = sizes_of(model.config)
    reference = jax.eval_shape(lambda s: ref.init_params(s, sizes), jnp.uint32(0))
    assert jax.tree.map(lambda x: x.shape, shapes) == jax.tree.map(lambda x: x.shape, reference)
    # ISSUE 33's table counts 64 stored correction biases a router: 913,473,668; they are not stored
    assert sum(int(x.size) for x in jax.tree.leaves(shapes)) == 913_473_668 - 5 * 64 == 913_473_348
    c = model.config
    assert (c.hidden, c.heads, c.q_lora_rank, c.kv_lora_rank, c.nope_dim, c.rope_dim, c.v_dim) == (
        3584, 32, 768, 512, 128, 64, 128)
    assert (c.dense_width, c.experts, c.held, c.top_k, c.route_scale, c.expert_width, c.shared_width) == (
        9216, 64, 8, 4, 2.0, 1024, 1024)
    assert (c.streams, c.sinkhorn_iters, c.hc_eps, c.mtp, c.mtp_lambda, c.pattern) == (
        4, 20, 1e-6, True, 0.3, "LDLELELELE")
    assert c.mla.key_dim == 192 and c.mla.score_scale == pytest.approx(192**-0.5 * 1.4159**2, rel=1e-4)


# -- 2. the residual path ------------------------------------------------------------


def test_sinkhorn_makes_rows_and_columns_sum_to_one_after_twenty_and_not_after_one():
    """One iteration leaves the rows tenths off, twenty within 1e-5 (off-diagonal
    entries within a factor of e of each other); at the initialisation the
    configuration states (``B_res`` = N(0, 1) + 2 I, entries a factor of e^6
    apart) twenty bring the median token there and the worst within 1e-2."""
    eye = 2.0 * jnp.eye(4)[:, :, None, None]
    noise = jax.random.normal(jax.random.key(0), (4, 4, 2, 50))
    start = jnp.exp(jnp.clip(0.25 * noise + eye, -30.0, 30.0))
    after = lambda iters, m=start: hyper_connections.sinkhorn(m, iters, 1e-6)
    rows, cols = after(20).sum(axis=1), after(20).sum(axis=0)
    assert float(jnp.abs(rows - 1).max()) < 1e-5 and float(jnp.abs(cols - 1).max()) < 1e-5
    assert float(jnp.abs(after(1).sum(axis=1) - 1).max()) > 0.1
    assert float(jnp.abs(after(1).sum(axis=0) - 1).max()) < 1e-5  # the columns came last
    assert bool((after(20) >= 0).all())
    stated = jnp.abs(after(20, jnp.exp(noise + eye)).sum(axis=1) - 1)
    assert float(jnp.median(stated)) < 1e-5 and float(stated.max()) < 1e-2
    assert float(jnp.abs(after(1, jnp.exp(noise + eye)).sum(axis=1) - 1).max()) > 0.3
    # with B_res = 0 and no input exp(0) is doubly stochastic already: nothing to do, nothing to fail
    flat = jnp.ones((4, 4, 1, 1))
    np.testing.assert_allclose(hyper_connections.sinkhorn(flat, 1, 1e-6), 0.25 * flat, atol=1e-6)
    np.testing.assert_allclose(hyper_connections.sinkhorn(flat, 20, 1e-6), 0.25 * flat, atol=1e-5)


def test_the_maps_and_the_mixing_match_the_references():
    model = tiny()
    sizes = sizes_of(model.config)
    p = ref.init_params(9, sizes)["h_1"]["hc"]
    x = jax.random.normal(jax.random.key(2), (2, 13, 4, 32))  # (B, T, n, hidden): the reference's layout
    y = jax.random.normal(jax.random.key(3), (2, 13, 32))
    h_pre, h_post, h_res = ref.hyper_maps(x, p, sizes)
    streams = jnp.moveaxis(x, 2, 1)  # the program's: (B, n, T, hidden)
    u, got_res, got_post = hyper_connections.HyperConnection(model.config.hc).apply({"params": p}, streams)
    np.testing.assert_allclose(u, jnp.sum(h_pre[..., None] * x, axis=2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.moveaxis(got_res, (0, 1), (2, 3)), h_res, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.moveaxis(got_post, 1, 2), h_post, rtol=1e-5, atol=1e-6)
    out, rms = hyper_connections.hyper_post(streams, got_res, got_post, y)
    want = jnp.einsum("btij,btjh->btih", h_res, x) + h_post[..., None] * y[:, :, None, :]
    np.testing.assert_allclose(jnp.moveaxis(out, 1, 2), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rms, jnp.sqrt(jnp.mean(jnp.square(want), axis=(1, 3))), rtol=1e-5)


def test_one_stream_is_the_plain_residual():
    """``streams`` = 1 builds no maps and adds: the code the other families run."""
    model = tiny(streams=1, mtp=False)
    ids = ids_for(model)
    params = model.init(jax.random.key(0), ids)["params"]
    assert "hc" not in params["h_0"] and "mtp" not in params
    logits, counts = model.apply({"params": params}, ids)
    assert logits.shape == (2, 21, 64) and "mhc_stream_rms" not in counts and "mla_rms" in counts


# -- 3. yarn ---------------------------------------------------------------------------


def test_yarn_frequencies_against_the_formula():
    """ISSUE 33's formula written out: pairs that turn more than 32 times in the
    original 4,096 positions keep their frequency, those that turn less than
    once are divided by 64, a linear ramp between."""
    table = attention.rope_frequencies(
        64, 4096, 10000.0, factor=64.0, beta_fast=32.0, beta_slow=1.0, original_max_len=4096)
    d = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(10000.0))
    lo, hi = math.floor(d(32)), math.ceil(d(1))
    assert (lo, hi) == (10, 23)
    freq = np.empty(32)
    for j in range(32):
        f = 10000.0 ** (-2 * j / 64)
        m = 1 - min(max((j - lo) / (hi - lo), 0.0), 1.0)
        freq[j] = f * m + (f / 64) * (1 - m)
    t = np.arange(4096)[:, None]
    np.testing.assert_allclose(table[..., 0], np.cos(t * freq), atol=2e-3)
    np.testing.assert_allclose(table[..., 1], np.sin(t * freq), atol=2e-3)
    np.testing.assert_allclose(table[:64], np.stack([np.cos(t[:64] * freq), np.sin(t[:64] * freq)], -1), atol=1e-5)
    # the fast pairs are the plain table's, the slow ones 64 times slower; the defaults are today's table
    plain = attention.rope_frequencies(64, 4096, 10000.0)
    np.testing.assert_array_equal(table[:, :10], plain[:, :10])
    assert float(jnp.abs(table[:, 23:] - plain[:, 23:]).max()) > 1e-3
    np.testing.assert_array_equal(attention.rope_frequencies(64, 128, 10000.0, factor=1.0), plain[:128])
    np.testing.assert_allclose(ref.yarn_frequencies(sizes_of(decoder.xing4_share().config)), freq, rtol=1e-5)
    with pytest.raises(ValueError, match="original_max_len"):
        attention.rope_frequencies(64, 16, factor=2.0)


# -- 4. the shares add up ------------------------------------------------------------


@pytest.fixture(params=["xla", "interpret"])
def rows_path(request, monkeypatch):
    monkeypatch.setattr(moe, "_rows_impl", lambda: request.param)
    return request.param


def test_eight_shares_add_up_to_the_uncut_layer(rows_path):
    """The parts that ranks 0 to 7 give (one of 8 experts each here), plus the
    ungated shared expert counted once, are the uncut layer that the reference
    computes with every expert held: sigmoid scores, top-4 scaled 2, the
    centred correction."""
    hidden, experts, top_k, width = 32, 8, 4, 16
    whole = moe.HeldExpertsConfig(
        hidden=hidden, experts=experts, held=experts, top_k=top_k, route_scale=2.0, expert_width=width,
        shared_width=width, scores="sigmoid", activation="swiglu", shared_gate=False,
        score_correction="centred", dtype=jnp.float32)
    k = jax.random.split(jax.random.key(0), 9)
    normal = lambda i, shape, std=0.2: jax.random.normal(k[i], shape) * std
    p = {
        "router": normal(0, (hidden, experts), 1.0),
        "w1": normal(1, (experts, hidden, width)), "w3": normal(2, (experts, hidden, width)),
        "w2": normal(3, (experts, width, hidden)),
        "shared_w1": normal(4, (hidden, width)), "shared_w3": normal(5, (hidden, width)),
        "shared_w2": normal(6, (width, hidden)),
    }
    x = jax.random.normal(k[8], (2, 11, hidden))
    sizes = {"held": experts, "held_start": 0, "experts": experts, "top_k": top_k, "route_scale": 2.0,
             "score_correction": "centred"}
    want, _ = ref.experts_mixer(x, p, sizes)
    only_shared = dict(p, w1=p["w1"] * 0, w2=p["w2"] * 0)
    total = ref.experts_mixer(x, only_shared, sizes)[0]  # the shared expert, once
    rows = 0
    for rank in range(8):
        share = dataclasses.replace(whole, held=1, held_start=rank, shared_width=0)
        mine = {"router": p["router"], **{n: p[n][rank : rank + 1] for n in ("w1", "w2", "w3")}}
        y, counts = moe.HeldExpertsMLP(share).apply({"params": mine}, x)
        total = total + y
        rows += int(counts["rows"].sum())
        assert int(counts["rows"].sum() + counts["absent_pairs"]) == 2 * 11 * top_k
    assert rows == 2 * 11 * top_k  # every pair is held by exactly one share
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


# -- 5. each planted fault moves its number ----------------------------------------------

FAULTS = ["half_batch", "top3", "renorm_over_held", "no_mtp", "sinkhorn_1", "one_stream", "no_rope_key",
          "no_yarn_scale"]


@pytest.fixture(scope="module")
def sound():
    model = tiny()
    sizes = sizes_of(model.config)
    params = ref.init_params(11, sizes)
    # scores of size 1, as at the published widths, so that what makes them shows in attention's output
    for name in ("h_0", "h_2"):
        params[name]["mixer"]["q_b"] = 8.0 * params[name]["mixer"]["q_b"]
        params[name]["mixer"]["kv_a"] = 8.0 * params[name]["mixer"]["kv_a"]
    ids = ids_for(model, rows=2, seq=32, seed=3)
    return model, sizes, params, ids, reference_side(params, ids, sizes)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_moves_its_number(sound, fault):
    model, sizes, params, ids, ((want, _), (loss, mtp), shown) = sound
    (logits, _), (f_loss, f_mtp), f_shown = reference_side(params, ids, sizes, faults=(fault,))
    stream_gap = ref_rounds.rms_gap(f_shown["stream_rms"], shown["stream_rms"])
    mla_gap = ref_rounds.rms_gap(f_shown["mla_rms"], shown["mla_rms"])
    routes = ref_rounds.routing_disagreement(list(f_shown["routes"]), list(shown["routes"]))
    if fault == "half_batch":
        assert abs(float(f_loss - loss)) > 1e-3 and rel(logits, want) < 1e-6
    elif fault == "top3":
        assert routes >= 1 / 3 - 1e-6
    elif fault == "renorm_over_held":
        assert rel(logits, want) > 1e-3 and routes < 0.05  # the same choice here; the module's layer sees other inputs
    elif fault == "no_mtp":
        assert float(loss - f_loss) == pytest.approx(0.3 * float(mtp), rel=1e-5) and float(f_mtp) == float(mtp)
    elif fault == "sinkhorn_1":
        assert stream_gap > 0.1  # whole tenths
    elif fault == "one_stream":
        assert stream_gap > 0.01 and mla_gap > 0.01
    elif fault == "no_rope_key":
        assert mla_gap > 0.01
    else:
        assert mla_gap > 0.01
    # and the program, run soundly, stays where a fault does not
    (got, _), counts = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    assert rel(got, want) < 1e-5
    assert ref_rounds.rms_gap(list(counts["mla_rms"]), shown["mla_rms"]) < 1e-4
    assert ref_rounds.rms_gap(list(counts["mhc_stream_rms"]), shown["stream_rms"]) < 1e-4


# -- 6. spans and counters -------------------------------------------------------------


def test_the_spans_are_recorded_and_the_counters_count():
    model = tiny()
    ids = ids_for(model, rows=2, seq=21)
    reg = get_registry()
    iters = reg.counter("consensusml_mhc_sinkhorn_iters_total", labels={"layer": "0"})
    module = reg.counter("consensusml_mhc_sinkhorn_iters_total", labels={"layer": "5"})  # the module's E block
    impl = reg.counter("consensusml_mla_flash_impl_total", labels={"layer": "0", "impl": "xla"})
    before = iters.value, module.value, impl.value
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        params = ref.init_params(1, sizes_of(model.config))
        jax.eval_shape(lambda p: nemotron_h_loss_fn(model)(p, {}, {"input_ids": ids}, None), params)
        events = tracer.events()
    finally:
        tracer.enabled = was
    names = {e["name"] for e in events}
    assert iters.value - before[0] == 20 and module.value - before[1] == 20
    assert impl.value - before[2] == 1  # off a TPU, and under the dense threshold: XLA's attention
    assert {"mla.q_lora", "mla.kv_lora", "mla.rope", "attn.flash", "mla.out_proj", "mhc.maps", "mhc.sinkhorn",
            "mhc.pre", "mhc.post", "mlp.dense", "mtp.embed_proj", "mtp.block", "mtp.loss", "moe.route",
            "moe.experts", "moe.shared"} <= names
    assert any(e["name"] == "mhc.sinkhorn" and e.get("args", {}).get("iters") == 20 for e in events)
    assert model.config.expert_layers == (3, 5)  # the decoder's E block and the module's
