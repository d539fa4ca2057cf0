"""The ``qwen3_next`` layer (Gated DeltaNet / gated attention with partial
rotary / many small gated experts, the pattern decoder's kinds ``G``, ``A`` and
a softmax-routed SwiGLU ``E``) against the benchmark's plain reference, at tiny
sizes on the CPU.

The reference (``benchmarks/reference/qwen3_next.py``: float32, the delta rule
token by token, a loop over held experts, dense attention with its own rotary)
imports nothing of the program and makes the weights; the program is handed
them. Groups: the chunked delta rule against the recurrence and the triangular
inverse against ``jnp.linalg.inv``; each mixer and the whole decoder (logits,
loss, gradients); the sixteen shares of an expert layer add up to the uncut
layer; no pair is dropped and weights are normalised over the chosen ten;
SwiGLU experts through the interpreted kernels; rotary and 256-wide flash
attention; spans and counters; planted faults fail the same comparisons. The
rounds of the shipped recipe are in ``test_qwen3_next_rounds.py``.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if _BENCH not in sys.path:
    sys.path.append(_BENCH)

from drivers.train_qwen3_next import program_sizes as sizes_of  # noqa: E402
from reference import qwen3_next as ref  # noqa: E402
from consensusml_tpu.models import attention, gated_delta, moe  # noqa: E402
from consensusml_tpu.models import nemotron_h as decoder  # noqa: E402
from consensusml_tpu.models.nemotron_h import NemotronHLM, nemotron_h_loss_fn, qwen3_next_tiny  # noqa: E402
from consensusml_tpu.obs import get_registry, get_tracer  # noqa: E402


def tiny(**overrides) -> NemotronHLM:
    return qwen3_next_tiny(**{"dtype": jnp.float32, "remat": False, **overrides})


def ids_for(model, rows=2, seq=21, seed=0):
    return jax.random.randint(jax.random.key(seed), (rows, seq), 0, model.config.vocab_size)


def rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def reference_logits(params, ids, sizes, faults=()):
    return jax.jit(lambda p: ref.logits_of(p, ref.hidden_states(p, ids, sizes, faults=faults)[0]))(params)


def worst_grad_gap(model, params, ids, sizes) -> tuple:
    """(logits' largest gap, loss gap, worst leaf's relative gradient gap)."""
    logits, _ = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    want = reference_logits(params, ids, sizes)
    loss_fn = nemotron_h_loss_fn(model)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {}, {"input_ids": ids}, None), has_aux=True))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, sizes)))(params)
    gaps = jax.tree.leaves(jax.tree.map(rel, grads, want_grads))
    return float(jnp.abs(logits - want).max()), abs(float(loss - want_loss)), max(gaps)


# -- 1. the chunked delta rule is the recurrence ---------------------------------


def _rule_inputs(seq, decay, heads=3, dk=8, dv=8):
    k = jax.random.split(jax.random.key(seq), 5)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q = unit(jax.random.normal(k[0], (2, seq, heads, dk))) * dk**-0.5
    key = unit(jax.random.normal(k[1], (2, seq, heads, dk)))
    v = jax.random.normal(k[2], (2, seq, heads, dv))
    g = -{"near_zero": 1e-3, "moderate": 1.0, "very_negative": 40.0}[decay] * jax.nn.softplus(
        jax.random.normal(k[3], (2, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (2, seq, heads)))
    return q, key, v, g, beta


@pytest.mark.parametrize("decay", ["near_zero", "moderate", "very_negative"])
@pytest.mark.parametrize("seq", [64, 19, 5, 1])
def test_chunked_delta_rule_matches_recurrence(seq, decay):
    """Values and all five gradients, at lengths that do and do not divide the
    chunk of 16, with a state that hardly decays and one that a token wipes."""
    args = _rule_inputs(seq, decay)
    chunked = lambda *a: gated_delta.gated_delta_chunked(*a, chunk=16)
    plain = lambda *a: ref.delta_rule(*a, jnp.ones((seq,)))
    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(chunked)(*args), jax.jit(plain)(*args)
        probe = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2, 3, 4)))
        d_got, d_want = probe(chunked)(*args), probe(plain)(*args)
    assert got.shape == want.shape == (2, seq, 3, 8) and bool(jnp.isfinite(got).all())
    assert rel(got, want) < 1e-5
    for a, b in zip(d_got, d_want):
        assert bool(jnp.isfinite(a).all())
        assert rel(a, b) < 2e-4 or float(jnp.abs(a - b).max()) < 1e-6


@pytest.mark.parametrize("size", [1, 2, 8, 64])
def test_the_triangular_inverse_by_products_is_the_inverse(size):
    # entries as the rule's: beta (k . k) Gamma, below one in size
    strict = jnp.tril(jax.random.uniform(jax.random.key(size), (3, size, size), minval=-0.3, maxval=0.3), -1)
    got = gated_delta.unit_lower_inverse(strict)
    want = jnp.linalg.inv(jnp.eye(size) - strict)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4 * float(jnp.abs(want).max()))
    assert not np.asarray(jnp.triu(got, 1)).any()  # still lower triangular, the diagonal ones
    np.testing.assert_allclose(jnp.diagonal(got, axis1=-2, axis2=-1), 1.0)



def _worst_case_for_growth(size):
    """Every key the same unit vector, ``beta`` 0.999, decays of 0.9999 a token:
    ``A`` is 0.999 below the diagonal throughout, the powers of the series that
    ``unit_lower_inverse`` sums grow to 1e18, and the inverse's own entries
    shrink slowest, the most a chunk can ask of it (a Gram matrix of unit keys has
    no row of -1s, so the inverse never grows)."""
    gamma = jnp.cumsum(jnp.full((size,), jnp.log(0.9999)))
    return jnp.tril(0.999 * jnp.exp(gamma[:, None] - gamma[None, :]), -1)


@pytest.mark.parametrize("case", ["random_32", "random_64", "random_128", "repeated_keys_64", "not_a_power_of_two_48"])
def test_the_triangular_inverse_by_blocks_is_the_inverse(case):
    """The kernels' inverse (16-wide diagonal blocks by substitution, the blocks
    below them by products at full precision) against the series of
    ``unit_lower_inverse`` and against ``numpy.linalg.inv`` in float64."""
    kind, size = case.rsplit("_", 1)
    size = int(size)
    if kind == "repeated_keys":
        strict = _worst_case_for_growth(size)
    else:
        strict = jnp.tril(jax.random.uniform(jax.random.key(size), (size, size), minval=-0.3, maxval=0.3), -1)
    got = jax.jit(gated_delta.blocked_unit_lower_inverse)(strict)
    want = np.linalg.inv(np.eye(size) + np.asarray(strict, np.float64))
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6 * scale)
    if kind != "repeated_keys":  # there the series' powers reach 1e18 before they cancel: it reads 1e11 off
        np.testing.assert_allclose(got, gated_delta.unit_lower_inverse(-strict), rtol=2e-5, atol=2e-6 * scale)
    assert not np.asarray(jnp.triu(got, 1)).any()  # still lower triangular, the diagonal ones
    np.testing.assert_allclose(jnp.diagonal(got), 1.0)


# -- 2. each mixer, and the whole decoder ------------------------------------------


def test_the_delta_mixer_matches_the_references():
    model = tiny()
    sizes = sizes_of(model.config)
    p = ref.init_params(3, sizes)["h_0"]["mixer"]
    u = jax.random.normal(jax.random.key(1), (2, 21, model.config.hidden))
    mixer = gated_delta.GatedDeltaNetMixer(model.config.gdn)
    own = mixer.init(jax.random.key(0), u)["params"]
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, p)
    # the program's own initialiser draws the step as the reference does: log-uniform in [dt_min, dt_max]
    dt = jax.nn.softplus(own["dt_bias"])
    assert float(dt.min()) >= 0.001 - 1e-6 and float(dt.max()) <= 0.1 + 1e-6
    probe = lambda f: jax.jit(jax.value_and_grad(lambda p, u: jnp.sum(jnp.sin(f(p, u)[0])), argnums=(0, 1)))
    got = probe(lambda p, u: mixer.apply({"params": p}, u))(p, u)
    want = probe(lambda p, u: ref.delta_mixer(u, p, sizes))(p, u)
    assert abs(float(got[0] - want[0])) < 1e-4
    assert max(jax.tree.leaves(jax.tree.map(rel, got[1], want[1]))) < 2e-4
    np.testing.assert_allclose(
        mixer.apply({"params": p}, u)[1], ref.delta_mixer(u, p, sizes)[1], rtol=1e-5)


def test_the_expert_layer_matches_the_references():
    """Softmax scores, SwiGLU experts (three matrices), the shared expert
    behind its scalar gate: values and gradients of every leaf."""
    model = tiny(score_correction="centred")
    sizes = sizes_of(model.config)
    p = ref.init_params(4, sizes)["h_1"]["mixer"]
    u = jax.random.normal(jax.random.key(2), (2, 21, model.config.hidden))
    layer = moe.HeldExpertsMLP(model.config.moe)
    assert jax.tree.map(jnp.shape, layer.init(jax.random.key(0), u)["params"]) == jax.tree.map(jnp.shape, p)
    probe = lambda f: jax.jit(jax.value_and_grad(lambda p, u: jnp.sum(jnp.sin(3 * f(p, u)[0])), argnums=(0, 1)))
    got = probe(lambda p, u: layer.apply({"params": p}, u))(p, u)
    want = probe(lambda p, u: ref.experts_mixer(u, p, sizes))(p, u)
    assert abs(float(got[0] - want[0])) < 1e-4
    assert max(jax.tree.leaves(jax.tree.map(rel, got[1], want[1]))) < 2e-4
    assert float(jnp.abs(got[1][0]["shared_gate"]).max()) > 0 and float(jnp.abs(got[1][0]["w3"]).max()) > 0


@pytest.mark.parametrize(
    "pattern, correction",
    [("GEGEGEAE", "zeros"), ("GEGEGEAE", "centred"), ("GEGEGEAEGEGEGEAE", "centred")],
)
def test_decoder_matches_reference(pattern, correction):
    model = tiny(pattern=pattern, score_correction=correction)
    sizes = sizes_of(model.config)
    assert sizes["layers"] == len(pattern) // 2 and sizes["interval"] == 4
    params = ref.init_params(7, sizes)
    ids = ids_for(model)
    own = model.init(jax.random.key(1), ids)["params"]
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    logits_gap, loss_gap, grad_gap = worst_grad_gap(model, params, ids, sizes)
    assert logits_gap < 2e-5 and loss_gap < 2e-5 and grad_gap < 2e-4


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


def test_bfloat16_stays_near_the_reference():
    model = qwen3_next_tiny(remat=False)  # the shipped dtype
    sizes = sizes_of(model.config)
    params = ref.init_params(5, sizes)
    ids = ids_for(model)
    logits, counts = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    assert rel(logits, reference_logits(params, ids, sizes)) < 0.03
    _, seen = jax.jit(lambda p: ref.hidden_states(p, ids, sizes))(params)
    np.testing.assert_allclose(counts["gdn_rms"], jnp.stack(seen["gdn_rms"]), rtol=0.03)


def test_the_full_share_is_the_issues_625_million_parameters():
    model = decoder.qwen3_next_share()
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(int(x.size) for x in jax.tree.leaves(shapes)) == 625_667_136
    c = model.config
    assert (c.hidden, c.head_dim, c.heads, c.kv_heads, c.rotary_dim, c.rope_theta) == (2048, 256, 16, 2, 64, 1e7)
    assert (c.gdn_key_heads, c.gdn_value_heads, c.gdn_key_dim, c.gdn_value_dim, c.conv_kernel) == (16, 32, 128, 128, 4)
    assert (c.experts, c.held, c.top_k, c.expert_width, c.shared_width) == (512, 32, 10, 512, 512)


# -- 3. the shares add up -------------------------------------------------------


@pytest.fixture(params=["xla", "interpret"])
def rows_path(request, monkeypatch):
    """The layer's row movement by XLA's gathers (what runs off a TPU) and by
    the interpreted row kernels (what runs on one)."""
    monkeypatch.setattr(moe, "_rows_impl", lambda: request.param)
    return request.param


def _gated_layer(hidden, experts, width, key=0):
    k = jax.random.split(jax.random.key(key), 9)
    normal = lambda i, shape, std=0.2: jax.random.normal(k[i], shape) * std
    return {
        "router": normal(0, (hidden, experts), 1.0),
        "w1": normal(1, (experts, hidden, width)), "w3": normal(2, (experts, hidden, width)),
        "w2": normal(3, (experts, width, hidden)),
        "shared_w1": normal(4, (hidden, width)), "shared_w3": normal(5, (hidden, width)),
        "shared_w2": normal(6, (width, hidden)), "shared_gate": normal(7, (hidden,)),
    }, k[8]


def _gated_sizes(experts, held, top_k, held_start=0):
    return {"held": held, "held_start": held_start, "experts": experts, "top_k": top_k}


def test_sixteen_shares_add_up_to_the_uncut_layer(rows_path):
    """The parts that ranks 0 to 15 give (two of 32 experts each), plus the
    gated shared expert counted once, are the uncut 32-expert layer that the
    reference computes with every expert held."""
    hidden, experts, top_k, width = 32, 32, 5, 16
    whole = moe.HeldExpertsConfig(
        hidden=hidden, experts=experts, held=experts, top_k=top_k, route_scale=1.0, expert_width=width,
        shared_width=width, scores="softmax", activation="swiglu", shared_gate=True, dtype=jnp.float32)
    p, kx = _gated_layer(hidden, experts, width)
    x = jax.random.normal(kx, (2, 11, hidden))
    want, _ = ref.experts_mixer(x, p, _gated_sizes(experts, experts, top_k))
    only_shared = dict(p, w1=p["w1"] * 0, w2=p["w2"] * 0)
    total = ref.experts_mixer(x, only_shared, _gated_sizes(experts, experts, top_k))[0]  # the shared expert, once
    rows = 0
    for rank in range(16):
        share = dataclasses.replace(whole, held=2, held_start=2 * rank, shared_width=0, shared_gate=False)
        mine = {"router": p["router"], **{n: p[n][2 * rank : 2 * rank + 2] for n in ("w1", "w2", "w3")}}
        y, counts = moe.HeldExpertsMLP(share).apply({"params": mine}, x)
        total = total + y
        rows += int(counts["rows"].sum())
        assert int(counts["rows"].sum() + counts["absent_pairs"]) == 2 * 11 * top_k
    assert rows == 2 * 11 * top_k  # every pair is held by exactly one share
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


# -- 4. no pair dropped; weights over the chosen, not the held -------------------------


def _forced_layer():
    """A router that sends EVERY token to held expert 1 (and to two absent
    ones): inputs are positive, column 1 of the router large."""
    hidden, experts = 16, 8
    cfg = moe.HeldExpertsConfig(
        hidden=hidden, experts=experts, held=2, held_start=0, top_k=3, route_scale=1.0, expert_width=8,
        shared_width=0, scores="softmax", activation="swiglu", dtype=jnp.float32)
    p, kx = _gated_layer(hidden, 2, 8, key=4)
    router = jnp.zeros((hidden, experts)).at[:, 1].set(1.0).at[:, 5].set(0.3).at[:, 6].set(0.2)
    p = {"router": router.at[:, 0].set(-1.0), "w1": p["w1"], "w2": p["w2"], "w3": p["w3"]}
    x = jnp.abs(jax.random.normal(kx, (3, 40, hidden))) + 0.1
    return cfg, p, x, _gated_sizes(experts, 2, 3)


def test_no_pair_dropped_when_every_token_picks_one_held_expert(rows_path):
    cfg, p, x, sizes = _forced_layer()
    y, counts = moe.HeldExpertsMLP(cfg).apply({"params": p}, x)
    assert counts["rows"].tolist() == [0, 120]  # 1.25 x a fair share would be 56
    assert int(counts["absent_pairs"]) == 240
    blank = {"shared_w1": jnp.zeros((16, 1)), "shared_w3": jnp.zeros((16, 1)),
             "shared_w2": jnp.zeros((1, 16)), "shared_gate": jnp.zeros((16,))}
    want, chosen = ref.experts_mixer(x, {**p, **blank}, sizes)
    assert bool((chosen == 1).any(axis=-1).all())
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0  # no token's row is empty


def test_weights_are_normalised_over_the_chosen_ten():
    """Softmax scores over 512, the ten largest, weights ``p_e / sum of the ten``
    whether an expert is held here or not, and no scale."""
    scores = jax.nn.softmax(jax.random.normal(jax.random.key(0), (64, 512)), axis=-1)
    idx, weights = moe.route_top_k(scores, 10, 1.0)
    np.testing.assert_allclose(weights.sum(axis=-1), 1.0, rtol=1e-6)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    held = idx < 32  # one chip's 32 of 512: most tokens have some choice elsewhere
    assert float(jnp.where(held, weights, 0).sum(axis=-1).mean()) < 0.2
    ref_idx, ref_w = ref.route(scores[None] * 0, {"router": jnp.zeros((1, 512))}, _gated_sizes(512, 32, 10))
    assert ref_idx.shape == (1, 64, 10) and np.allclose(ref_w.sum(-1), 1.0, rtol=1e-5)
    with pytest.raises(ValueError, match="scores"):
        cfg = moe.HeldExpertsConfig(hidden=4, experts=4, held=2, top_k=1, expert_width=4, shared_width=0,
                                    scores="tanh", dtype=jnp.float32)
        moe.HeldExpertsMLP(cfg).init(jax.random.key(0), jnp.ones((1, 2, 4)))


# -- 5. SwiGLU experts through the kernels -------------------------------------------


@pytest.mark.parametrize("live", [0, 1, 17, 40, "all"])
def test_swiglu_experts_through_the_interpreted_kernels_match_ragged_dot(live, monkeypatch):
    """The third stacked matrix goes where the first goes: the layer with
    interpreted megablox products and interpreted row kernels (tiles of 16
    rows) against XLA's gathers and ``lax.ragged_dot``; values and the gradients
    of every leaf and of the input, with none, one, a tile and one, 40 and all
    of the 300 buffer rows live across three held experts."""
    hidden, experts, tokens = 16, 8, 150
    held = experts if live == "all" else 3
    cfg = moe.HeldExpertsConfig(
        hidden=hidden, experts=experts, held=held, held_start=0, top_k=2, route_scale=1.0, expert_width=8,
        shared_width=8, scores="softmax", activation="swiglu", shared_gate=True, dtype=jnp.float32)
    p, kx = _gated_layer(hidden, held, 8, key=11)
    k = jax.random.split(kx, 3)
    # feature 0 selects: a token with it set prefers the three held experts, the others never choose them
    router = (0.3 * jax.random.normal(k[0], (hidden, experts))).at[0].set(0.0).at[0, :3].set(12.0)
    p["router"] = router
    x = jax.random.normal(k[1], (1, tokens, hidden))
    chooses = jax.random.permutation(k[2], tokens) < (0 if live == "all" else -(-live // 2))
    x = x.at[0, :, 0].set(jnp.where(chooses, 1.0, -1.0))
    layer = moe.HeldExpertsMLP(cfg)
    probe = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def run():
        def f(p, x):
            y, counts = layer.apply({"params": p}, x)
            return jnp.sum(y * probe), (y, counts["rows"])

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(p, x)

    (_, (want_y, want_rows)), want_grads = run()
    monkeypatch.setattr(moe, "_GMM_ROWS", 16)
    monkeypatch.setattr(moe, "_TOKEN_TILE", 64)
    monkeypatch.setattr(moe, "_rows_impl", lambda: "interpret")
    monkeypatch.setattr(moe, "grouped_matmul", functools.partial(moe.grouped_matmul, impl="interpret"))
    (_, (got_y, got_rows)), got_grads = run()
    if live != "all":
        assert abs(int(want_rows.sum()) - live) <= 1
    np.testing.assert_array_equal(got_rows, want_rows)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# -- 6. rotary on a part of the head; 256-wide heads ---------------------------------


@pytest.mark.parametrize("width, rot", [(256, 64), (16, 4)])
def test_partial_rotate_half_rotary_matches_the_references(width, rot):
    x = jax.random.normal(jax.random.key(0), (2, 37, 3, width))
    table = attention.rope_frequencies(rot, 37, 1e7)
    got = jnp.concatenate(
        [attention.apply_rope(x[..., :rot], table, rotate_half=True), x[..., rot:]], axis=-1)
    np.testing.assert_allclose(got, ref.rotary(x, rot, 1e7), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])  # the other dimensions stand still
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)  # position 0 turns nothing
    # the pairing differs from the interleaved default's, the lengths do not
    other = attention.apply_rope(x[..., :rot], table)
    assert float(jnp.abs(other - got[..., :rot]).max()) > 0.1
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)


@pytest.mark.parametrize("seq", [512, 300])
def test_flash_attention_at_256_wide_heads_matches_dense(seq):
    from consensusml_tpu.models.flash_attention import flash_attention

    k = jax.random.split(jax.random.key(seq), 4)
    q, key, v = (jax.random.normal(k[i], (1, seq, 2, 256)) for i in range(3))
    probe = jax.random.normal(k[3], (1, seq, 2, 256))

    def run(attend):
        f = lambda q, key, v: jnp.sum(attend(q, key, v).astype(jnp.float32) * probe)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, key, v)

    flash = run(lambda q, key, v: flash_attention(q, key, v, causal=True, dtype=jnp.float32, interpret=True))
    dense = run(lambda q, key, v: attention.dot_product_attention(
        q, key, v, causal=True, dtype=jnp.float32, impl="dense"))
    assert abs(float(flash[0] - dense[0])) < 1e-2 * abs(float(dense[0])) + 1e-2
    for a, b in zip(flash[1], dense[1]):
        assert rel(a, b) < 2e-3


# -- spans and counters ----------------------------------------------------------


def test_the_rule_counts_its_chunks_and_the_spans_are_recorded():
    model = tiny()
    ids = ids_for(model, rows=2, seq=21)
    chunks = get_registry().counter("consensusml_gdn_chunks_total", labels={"layer": "0"})
    before = chunks.value
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        params = ref.init_params(1, sizes_of(model.config))
        jax.eval_shape(lambda p: model.apply({"params": p}, ids), params)
        names = {e["name"] for e in tracer.events()}
    finally:
        tracer.enabled = was
    assert chunks.value - before == 2 * 3  # 21 tokens in chunks of 8, two rows
    assert {"gdn.in_proj", "gdn.conv", "gdn.scan", "gdn.gate_norm", "gdn.out_proj", "attn.qk_norm_rope",
            "attn.flash", "attn.gate", "moe.route", "moe.sort", "moe.experts", "moe.shared",
            "moe.combine"} <= names
    # the new spans become scopes of the device ops; attn.flash alone does not
    text = jax.jit(lambda p: model.apply({"params": p}, ids)[0]).lower(params).as_text(debug_info=True)
    for scope in ("gdn.scan", "gdn.conv", "attn.qk_norm_rope", "attn.gate"):
        assert scope in text
    assert "attn.flash" not in text


def test_the_grouped_products_tile_counter_from_known_rows():
    """32 groups of 160 rows a call at tiles of 256: 48 (group, tile) pairs a
    call (8 groups fill 5 tiles, 4 of them straddle an edge), so the tiles are
    5120 / (48 x 256) = 41.7% full."""
    assert moe.gmm_visited_tiles([160] * 32) == 48
    assert moe.gmm_visited_tiles([0, 1, 0, 600]) == 1 + 3 and moe.gmm_visited_tiles([0, 0]) == 0
    counter = lambda kind, name="consensusml_moe_gmm_tiles_total": get_registry().counter(
        name, labels={"layer": "77", "kind": kind})
    before = counter("visited").value, counter("live", "consensusml_moe_row_tiles_total").value
    rows = np.full((1, 32), 2 * 160)  # a round of two calls
    moe.record_expert_counts(rows, np.asarray([2 * (81920 - 5120)]), [77], calls=2)
    assert counter("visited").value - before[0] == 2 * 48
    assert counter("live", "consensusml_moe_row_tiles_total").value - before[1] == 2 * 20


# -- 7. planted faults fail ---------------------------------------------------------


def _renorm_over_held(held_start, held):
    def route(scores, k, scale, bias=None):
        assert bias is None
        picked, idx = jax.lax.top_k(scores, k)
        here = (idx >= held_start) & (idx < held_start + held)
        total = jnp.sum(jnp.where(here, picked, 0.0), axis=-1, keepdims=True)
        return idx, picked / (total + 1e-20) * scale

    return route


@pytest.mark.parametrize(
    "fault", ["half_batch", "top9", "renorm_over_held", "no_state_carry", "no_delta", "no_attn_gate"])
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    """The comparison of group 2 against the reference with each of the
    benchmark's faults planted in it (the control put in the program's place):
    each reads far outside the limits the sound program keeps. Where the same
    fault can be planted in the PROGRAM (one expert fewer, weights over the held,
    the state lost between chunks, the gate left out), the two faulty sides
    have to agree with each other."""
    model = tiny()
    c = model.config
    sizes = sizes_of(c)
    params = ref.init_params(7, sizes)
    ids = ids_for(model, seq=37)
    sound = worst_grad_gap(model, params, ids, sizes)
    assert sound[0] < 2e-5 and sound[2] < 2e-4
    want_loss, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.lm_loss(p, ids, sizes)))(params)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: ref.lm_loss(p, ids, sizes, faults=(fault,))))(params)
    grad_gap = max(jax.tree.leaves(jax.tree.map(rel, grads, want_grads)))
    assert grad_gap > 100 * 2e-4
    if fault == "half_batch":
        assert abs(float(loss - want_loss)) > 1e-3
        return
    logits_gap = float(jnp.abs(
        reference_logits(params, ids, sizes, faults=(fault,)) - reference_logits(params, ids, sizes)).max())
    assert logits_gap > 10 * max(sound[0], 2e-5)
    if fault == "no_delta":
        return  # the chunked form has no one place where the correction alone could be left out
    if fault == "top9":
        model = tiny(top_k=c.top_k - 1)
    elif fault == "renorm_over_held":
        monkeypatch.setattr(moe, "route_top_k", _renorm_over_held(c.held_start, c.held))
    elif fault == "no_state_carry":
        monkeypatch.setattr(gated_delta, "_carried", lambda state: jnp.zeros_like(state))
    else:
        monkeypatch.setattr(decoder, "_attn_gate", lambda gate: jnp.ones_like(gate))
    logits, _ = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    twin = reference_logits(params, ids, sizes, faults=(fault,))
    np.testing.assert_allclose(logits, twin, rtol=2e-5, atol=2e-5)
