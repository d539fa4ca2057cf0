"""The hybrid decoder (Mamba-2 / experts / attention by a pattern string)
against the benchmark's plain reference, at tiny sizes on the CPU.

The reference (``benchmarks/reference/nemotron_h.py``: float32, the
token-by-token recurrence, a loop over held experts, dense attention) imports
nothing of the program and makes the weights; the program is handed them.
Six groups: the chunked scan against the recurrence; each kind of block and
the whole decoder (logits, loss, gradients); the shares of an expert layer add
up to the uncut layer; no token is dropped and weights are normalised over the
chosen experts; three rounds of the shipped smoke recipe follow the reference's
losses and first moment (``test_nemotron_h_rounds.py``: a file of its own, so
that the suite's workers share the compiles); and planted faults fail the same
comparisons.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if _BENCH not in sys.path:
    sys.path.append(_BENCH)

from drivers.train_nemotron_h import program_sizes as sizes_of  # noqa: E402
from reference import nemotron_h as ref  # noqa: E402
from consensusml_tpu.models import moe, ssm  # noqa: E402
from consensusml_tpu.models.nemotron_h import (  # noqa: E402
    NemotronHLM,
    nemotron_h_loss_fn,
    nemotron_h_tiny,
)
from consensusml_tpu.obs import get_registry, get_tracer  # noqa: E402


def tiny(**overrides) -> NemotronHLM:
    return nemotron_h_tiny(**{"dtype": jnp.float32, "remat": False, **overrides})


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


# -- 1. the chunked scan is the recurrence -----------------------------------


@pytest.mark.parametrize("seq", [16, 19, 5, 1])
def test_chunked_scan_matches_recurrence(seq):
    """Forward and ``jax.grad``, at lengths that are a multiple of the chunk
    (16 = 2 x 8), are not (19), and are shorter than one (5, 1)."""
    b, h, p, g, n, chunk = 2, 4, 8, 2, 16, 8
    k = jax.random.split(jax.random.key(seq), 6)
    x = jax.random.normal(k[0], (b, seq, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, seq, h)))
    a = -jnp.exp(jax.random.normal(k[2], (h,)) * 0.5)
    bm = jax.random.normal(k[3], (b, seq, g, n))
    cm = jax.random.normal(k[4], (b, seq, g, n))
    probe = jax.random.normal(k[5], (b, seq, h, p))

    def chunked(x, dt, a, bm, cm):
        return ssm.ssd_chunked(x, dt, a, bm, cm, chunk=chunk)

    def stepwise(x, dt, a, bm, cm):
        spread = lambda v: jnp.repeat(v, h // g, axis=2)
        return ref.recurrence(x, dt, jnp.exp(dt * a), spread(bm), spread(cm), jnp.ones((seq,)))

    np.testing.assert_allclose(
        jax.jit(chunked)(x, dt, a, bm, cm), jax.jit(stepwise)(x, dt, a, bm, cm), rtol=2e-4, atol=2e-4)
    grad = lambda f: jax.jit(
        jax.grad(lambda *args: jnp.sum(f(*args) * probe), argnums=(0, 1, 2, 3, 4)))
    for got, want in zip(grad(chunked)(x, dt, a, bm, cm), grad(stepwise)(x, dt, a, bm, cm)):
        assert rel(got, want) < 2e-4


# -- 2. each kind of block, and the whole decoder ------------------------------


@pytest.mark.parametrize(
    "pattern, correction",
    [("M", "zeros"), ("E", "zeros"), ("*", "zeros"), ("MEMEM*EME", "zeros"),
     ("E", "centred"), ("MEMEM*EME", "centred")],
)
def test_decoder_matches_reference(pattern, correction):
    model = tiny(pattern=pattern, score_correction=correction)
    sizes = sizes_of(model.config)
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
    model = nemotron_h_tiny(remat=False)  # the shipped dtype
    sizes = sizes_of(model.config)
    params = ref.init_params(5, sizes)
    ids = ids_for(model)
    logits, _ = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    assert rel(logits, reference_logits(params, ids, sizes)) < 0.03


# -- 3. the shares add up -------------------------------------------------------


@pytest.fixture(params=["xla", "interpret"])
def rows_path(request, monkeypatch):
    """The layer's row movement by XLA's gathers (what runs off a TPU) and by
    the interpreted row kernels (what runs on one)."""
    monkeypatch.setattr(moe, "_rows_impl", lambda: request.param)
    return request.param


def test_sixteen_shares_add_up_to_the_uncut_layer(rows_path):
    """Each share's routed part, plus the shared expert once, is the layer the
    reference computes with every expert held."""
    hidden, experts, top_k = 32, 16, 3
    whole = moe.HeldExpertsConfig(
        hidden=hidden, experts=experts, held=experts, top_k=top_k, expert_width=24,
        shared_width=48, dtype=jnp.float32)
    sizes = {"held": experts, "held_start": 0, "experts": experts, "top_k": top_k,
             "route_scale": whole.route_scale}
    k = jax.random.split(jax.random.key(0), 6)
    p = {
        "router": jax.random.normal(k[0], (hidden, experts)),
        "w1": jax.random.normal(k[1], (experts, hidden, 24)) * 0.2,
        "w2": jax.random.normal(k[2], (experts, 24, hidden)) * 0.2,
        "shared_w1": jax.random.normal(k[3], (hidden, 48)) * 0.2,
        "shared_w2": jax.random.normal(k[4], (48, hidden)) * 0.2,
    }
    x = jax.random.normal(k[5], (2, 11, hidden))
    want, _ = ref.experts_mixer(x, p, sizes)
    only_shared = dict(p, w1=p["w1"] * 0, w2=p["w2"] * 0)
    total = ref.experts_mixer(x, only_shared, sizes)[0]  # the shared expert, once
    rows = 0
    for rank in range(16):
        share = dataclasses.replace(whole, held=1, held_start=rank, shared_width=0)
        mine = {"router": p["router"], "w1": p["w1"][rank : rank + 1], "w2": p["w2"][rank : rank + 1]}
        y, counts = moe.HeldExpertsMLP(share).apply({"params": mine}, x)
        total = total + y
        rows += int(counts["rows"].sum())
        assert int(counts["rows"].sum() + counts["absent_pairs"]) == 2 * 11 * top_k
    assert rows == 2 * 11 * top_k  # every pair is held by exactly one share
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


# -- 4. no token dropped; weights over the chosen, not the held --------------------


def _forced_layer():
    """A router that sends EVERY token to held expert 1 (and to two absent
    ones): inputs are positive, column 1 of the router large."""
    hidden, experts = 16, 8
    cfg = moe.HeldExpertsConfig(
        hidden=hidden, experts=experts, held=2, held_start=0, top_k=3, expert_width=8,
        shared_width=0, dtype=jnp.float32)
    k = jax.random.split(jax.random.key(4), 4)
    router = jnp.zeros((hidden, experts)).at[:, 1].set(1.0).at[:, 5].set(0.3).at[:, 6].set(0.2)
    router = router.at[:, 0].set(-1.0)
    p = {"router": router,
         "w1": jax.random.normal(k[0], (2, hidden, 8)), "w2": jax.random.normal(k[1], (2, 8, hidden))}
    x = jnp.abs(jax.random.normal(k[2], (3, 40, hidden))) + 0.1
    sizes = {"held": 2, "held_start": 0, "experts": experts, "top_k": 3, "route_scale": cfg.route_scale}
    return cfg, p, x, sizes


def test_no_token_dropped_when_all_go_to_one_expert(rows_path):
    cfg, p, x, sizes = _forced_layer()
    y, counts = moe.HeldExpertsMLP(cfg).apply({"params": p}, x)
    assert counts["rows"].tolist() == [0, 120]  # 1.25 x a fair share would be 56
    assert int(counts["absent_pairs"]) == 240
    want, chosen = ref.experts_mixer(x, dict(p, shared_w1=jnp.zeros((16, 1)), shared_w2=jnp.zeros((1, 16))), sizes)
    assert bool((chosen == 1).any(axis=-1).all())
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0  # no token's row is empty


def test_weights_are_normalised_over_the_chosen_experts():
    cfg, p, x, _ = _forced_layer()
    scores = jax.nn.sigmoid(x.reshape(-1, 16) @ p["router"])
    idx, weights = moe.route_top_k(scores, 3, cfg.route_scale)
    np.testing.assert_allclose(weights.sum(axis=-1), cfg.route_scale, rtol=1e-6)
    held = idx < 2
    # one of three chosen is held: over the held alone its weight would be the whole scale
    assert float(jnp.where(held, weights, 0).sum(axis=-1).max()) < 0.6 * cfg.route_scale


def test_a_centred_score_correction_takes_the_common_favourites_away():
    """``score_correction="centred"``: the bias added for the choice is minus
    each expert's mean score over the step's tokens. A router that gives two
    experts a head start with every token sends everything to them under a
    bias of zeros; centred, the tokens' own preferences decide and every
    expert gets rows. The weights stay those of the scores themselves,
    normalised over the chosen; the bias takes no gradient."""
    tokens, experts, k = 256, 8, 2
    own = 0.3 * jax.random.normal(jax.random.key(0), (tokens, experts))
    head_start = jnp.asarray([1.5, 1.2, 0, 0, 0, 0, 0, 0])
    scores = jax.nn.sigmoid(own + head_start)
    idx, _ = moe.route_top_k(scores, k, 2.5)
    assert np.isin(np.asarray(idx), [0, 1]).mean() > 0.85
    bias = -jnp.mean(scores, axis=0)
    idx, weights = moe.route_top_k(scores, k, 2.5, bias)
    load = np.bincount(np.asarray(idx).reshape(-1), minlength=experts)
    assert load.min() > 0.5 * load.mean() and load.max() < 1.5 * load.mean()  # a squashed score moves less
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True) * 2.5, rtol=1e-6)
    # in the layer: the same choice, and the gradient is that of the layer with its bias held fixed
    cfg = moe.HeldExpertsConfig(hidden=16, experts=experts, held=4, top_k=k, expert_width=8,
                                shared_width=0, score_correction="centred", dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 24, 16))
    layer = moe.HeldExpertsMLP(cfg)
    params = layer.init(jax.random.key(2), x)["params"]
    y, counts = layer.apply({"params": params}, x)
    s = jax.nn.sigmoid(x.reshape(-1, 16) @ params["router"])
    want, _ = moe.route_top_k(s, k, cfg.route_scale, -jnp.mean(s, axis=0))
    np.testing.assert_array_equal(counts["chosen"], want)
    with pytest.raises(ValueError, match="score_correction"):
        moe.HeldExpertsMLP(dataclasses.replace(cfg, score_correction="learned")).apply({"params": params}, x)


@pytest.mark.parametrize("rows", [[5, 0, 9], [0, 0, 0]])
def test_interpreted_kernels_match_ragged_dot(rows):
    """The grouped product's two implementations, forward and gradients, with
    rows that belong to no group at the end."""
    k = jax.random.split(jax.random.key(2), 2)
    lhs = jax.random.normal(k[0], (20, 24))
    rhs = jax.random.normal(k[1], (3, 24, 40)) * 0.2
    sizes = jnp.asarray(rows, jnp.int32)

    def both(fn):
        return fn("auto"), fn("interpret")

    a, b = both(lambda impl: moe.grouped_matmul(lhs, rhs, sizes, impl))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert not np.asarray(a[sum(rows):]).any()
    a, b = both(lambda impl: jax.grad(
        lambda l, r: jnp.sum(moe.grouped_matmul(l, r, sizes, impl) ** 2), argnums=(0, 1))(lhs, rhs))
    for got, want in zip(b, a):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- 5. the row kernels: only live rows move, and nothing else changes ----------------


def _layer_with_live_rows(live):
    """A layer of ONE held expert (of 8, top-2) and inputs of which exactly
    ``live`` tokens choose it (first, by a selector feature the router reads;
    no second choice can be the same expert), so ``live`` of the 300 buffer
    rows are live; ``live="all"`` holds every expert: all 300 are."""
    hidden, experts, tokens = 16, 8, 150
    held = experts if live == "all" else 1
    cfg = moe.HeldExpertsConfig(
        hidden=hidden, experts=experts, held=held, held_start=0, top_k=2, expert_width=8,
        shared_width=0, dtype=jnp.float32)
    k = jax.random.split(jax.random.key(11), 5)
    router = (0.3 * jax.random.normal(k[0], (hidden, experts))).at[0].set(0.0).at[0, 0].set(12.0)
    params = {"router": router, "w1": jax.random.normal(k[1], (held, hidden, 8)) * 0.3,
              "w2": jax.random.normal(k[2], (held, 8, hidden)) * 0.3}
    x = jax.random.normal(k[3], (1, tokens, hidden))
    chooses = jax.random.permutation(k[4], tokens) < (0 if live == "all" else live)
    x = x.at[0, :, 0].set(jnp.where(chooses, 1.0, -1.0))
    return cfg, params, x


def _value_and_grads(cfg, params, x):
    """``y``, the counts, and the gradients of a probe of ``y`` with respect
    to the parameters (the router's through ``held_w``) and the input."""
    layer = moe.HeldExpertsMLP(cfg)
    probe = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def f(p, x):
        y, counts = layer.apply({"params": p}, x)
        return jnp.sum(y * probe), (y, counts["rows"])

    (_, (y, rows)), grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(params, x)
    return y, rows, grads


def _assert_same(got, want, tol=1e-5):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 16 sorted rows and of 64 tokens, so that a 300-row buffer has
    live, boundary and skipped tiles."""
    monkeypatch.setattr(moe, "_GMM_ROWS", 16)
    monkeypatch.setattr(moe, "_TOKEN_TILE", 64)
    return 16


@pytest.mark.parametrize("live", [0, 1, 15, 16, 17, 18, "all"])
def test_interpreted_row_kernels_match_xlas_gathers(live, small_tiles, monkeypatch):
    """Values and gradients (``x``, ``w1``, ``w2``, the router through
    ``held_w``) with no live row, one, a tile less one, a tile, a tile and one,
    6% of the buffer (18 of 300) and every row live."""
    cfg, params, x = _layer_with_live_rows(live)
    want = _value_and_grads(cfg, params, x)
    assert int(want[1].sum()) == (300 if live == "all" else live)
    monkeypatch.setattr(moe, "_rows_impl", lambda: "interpret")
    got = _value_and_grads(cfg, params, x)
    _assert_same(got, want)
    if live not in (0, "all"):  # the router's gradient is that of the held pairs' weights
        assert float(jnp.abs(got[2][0]["router"]).max()) > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("live", [0, 5, 16, 120])
def test_row_kernels_by_themselves(live, dtype, small_tiles):
    """The two kernels against their definitions, in both row widths (a
    bfloat16 row is half of a 32-bit sublane): the gather (plain; scaled, with
    the dots of its backward use) and the combine (weighted; plain), where the
    source's dead rows are NaN."""
    tokens, k, width = 40, 3, 32
    key = jax.random.split(jax.random.key(live), 6)
    x = jax.random.normal(key[0], (tokens, width)).astype(dtype)
    order = jax.random.permutation(key[1], tokens * k)
    inv, total = jnp.argsort(order), jnp.int32(live)
    f32 = lambda a: np.asarray(a, np.float32)
    out = moe.moe_rows_gather(x, order, total, k, interpret=True)
    np.testing.assert_array_equal(f32(out[:live]), f32(x[order // k][:live]))
    assert not f32(out[live : -(-live // 16) * 16]).any()  # the boundary tile's dead rows
    scale = jax.random.normal(key[2], (tokens * k,))
    mate = jax.random.normal(key[3], (tokens * k, width)).astype(dtype)
    wide = x.astype(jnp.float32)
    out, dots = moe.moe_rows_gather(wide, order, total, k, scale=scale, mate=mate, dtype=dtype, interpret=True)
    np.testing.assert_array_equal(
        f32(out[:live]), f32((wide[order // k] * scale[:, None]).astype(dtype)[:live]))
    np.testing.assert_allclose(
        dots[:live], jnp.sum(wide[order // k] * mate.astype(jnp.float32), axis=1)[:live], rtol=1e-5, atol=1e-5)
    rows = jax.random.normal(key[4], (tokens * k, width)).astype(dtype).at[live:].set(jnp.nan)
    weights = jax.random.normal(key[5], (tokens, k))
    here = (inv < live).reshape(tokens, k)
    pairs = jnp.where(here[:, :, None], rows[inv].reshape(tokens, k, width).astype(jnp.float32), 0.0)
    y = moe.moe_rows_combine(rows, inv, total, k, weights=weights, dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(y, jnp.einsum("tk,tkh->th", weights, pairs), rtol=1e-5, atol=1e-5)
    y = moe.moe_rows_combine(rows, inv, total, k, interpret=True)
    assert y.dtype == dtype
    np.testing.assert_allclose(f32(y), f32(pairs.sum(axis=1).astype(dtype)), rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("product", ["ragged_dot", "megablox"])
def test_poisoned_dead_rows_reach_nothing(product, small_tiles, monkeypatch):
    """The kernel leaves the tiles past the live rows unwritten. Here EVERY
    dead row of what the gather hands out (``xs`` forward, ``d_ys`` backward)
    is NaN, the boundary tile's too: neither grouped product lets one into
    ``y`` or into any gradient."""
    cfg, params, x = _layer_with_live_rows(18)
    want = _value_and_grads(cfg, params, x)
    monkeypatch.setattr(moe, "_rows_impl", lambda: "interpret")
    gather, product_of = moe.moe_rows_gather, moe.grouped_matmul

    def poisoned(src, order, total, k, **kwargs):
        out = gather(src, order, total, k, **kwargs)
        dead = (jnp.arange(order.shape[0]) >= total)[:, None]
        if isinstance(out, tuple):
            return jnp.where(dead, jnp.nan, out[0]), out[1]
        return jnp.where(dead, jnp.nan, out)

    monkeypatch.setattr(moe, "moe_rows_gather", poisoned)
    if product == "megablox":
        monkeypatch.setattr(moe, "grouped_matmul", lambda l, r, g: product_of(l, r, g, "interpret"))
    got = _value_and_grads(cfg, params, x)
    assert all(bool(jnp.isfinite(a).all()) for a in jax.tree.leaves(got))
    _assert_same(got, want, tol=1e-4 if product == "megablox" else 1e-5)


def test_row_kernels_under_vmap_over_workers_with_different_counts(small_tiles, monkeypatch):
    """The stacked backend ``vmap``s the worker: two workers, 5 and 40 live
    rows, each as by itself."""
    cfg, params, x5 = _layer_with_live_rows(5)
    x40 = _layer_with_live_rows(40)[2]
    xs = jnp.stack([x5, x40])
    both = jax.tree.map(lambda a: jnp.stack([a, 1.5 * a]), params)
    layer = moe.HeldExpertsMLP(cfg)

    def grads(p, x):
        def f(p, x):
            y, counts = layer.apply({"params": p}, x)
            return jnp.sum(jnp.sin(y)), counts["rows"]

        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, x)

    want = jax.jit(jax.vmap(grads))(both, xs)
    assert want[0][1].reshape(-1).tolist() == [5, 40]
    monkeypatch.setattr(moe, "_rows_impl", lambda: "interpret")
    _assert_same(jax.jit(jax.vmap(grads))(both, xs), want)


def test_row_kernels_inside_a_checked_shard_map(small_tiles, monkeypatch):
    """The collective backend runs the layer inside ``shard_map`` with the
    check of varying axes on: the row kernels are the repo's own (their
    ``out_shape`` says where they vary) and run there, around XLA's grouped
    product."""
    from jax.sharding import PartitionSpec as P

    cfg, params, x5 = _layer_with_live_rows(5)
    xs = jnp.stack([x5, _layer_with_live_rows(40)[2]])
    both = jax.tree.map(lambda a: jnp.stack([a, 1.5 * a]), params)
    layer = moe.HeldExpertsMLP(cfg)

    def grads(p, x):
        def f(p, x):
            y, _ = layer.apply({"params": jax.tree.map(lambda a: a[0], p)}, x[0])
            return jnp.sum(jnp.sin(y))

        return jax.grad(f, argnums=(0, 1))(p, x)

    mapped = jax.jit(jax.shard_map(
        grads, mesh=jax.make_mesh((2,), ("w",)), in_specs=P("w"), out_specs=P("w")))
    want = mapped(both, xs)
    monkeypatch.setattr(moe, "_rows_impl", lambda: "interpret")
    _assert_same(mapped(both, xs), want)


def test_row_kernels_trace_once_under_the_callers_scope(monkeypatch):
    """Four ``E`` blocks call each kernel forward, recomputed and backward:
    one trace a kernel and operand types serves them all, and every call's
    equation sits under its own block's name and the kernel's own scope (a
    device op takes the innermost scope's name, and ``h_<i>`` alone would
    count it as flash attention)."""
    traces = []
    for name in ("_gather_kernel", "_combine_kernel"):
        real = getattr(moe, name)
        monkeypatch.setattr(
            moe, name, lambda *a, real=real, name=name: (traces.append(name), real(*a))[1])
    monkeypatch.setattr(moe, "_TRACED", {})
    monkeypatch.setattr(moe, "_rows_impl", lambda: "interpret")
    cfg, params, x = _layer_with_live_rows(18)
    layer = moe.HeldExpertsMLP(cfg)

    def two_blocks(p, x):
        for name in ("h_1", "h_3"):
            with jax.named_scope(name):
                x = x + layer.apply({"params": p}, x)[0]
        return jnp.sum(x)

    def pallas_scopes(jaxpr, outer=""):
        for e in jaxpr.eqns:
            here = f"{outer}/{e.source_info.name_stack}".strip("/")
            if e.primitive.name == "pallas_call":
                yield here
            for sub in e.params.values():  # a custom VJP's call holds the kernel
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from pallas_scopes(sub, here)

    jaxpr = jax.make_jaxpr(two_blocks)(params, x)
    scopes = list(pallas_scopes(jaxpr.jaxpr))
    assert sorted(traces) == ["_combine_kernel", "_gather_kernel"]
    assert len(scopes) == 4 and all(
        s.startswith(f"h_{b}/") and s.endswith(f"/{kernel}")
        for s, (b, kernel) in zip(scopes, [(1, "moe.sort/moe_rows_gather"), (1, "moe.combine/moe_rows_combine"),
                                           (3, "moe.sort/moe_rows_gather"), (3, "moe.combine/moe_rows_combine")]))
    traces.clear()
    jax.make_jaxpr(jax.grad(two_blocks))(params, x)
    # backward: the gather's scaled form with its dots, and the combine in the rows' dtype
    assert sorted(traces) == ["_combine_kernel", "_gather_kernel"]


def test_row_tile_counter_from_known_rows():
    """One round of the cell's shape: 2 calls of 8,192 tokens x top-6 = 384
    tiles of 256 rows; 6,200 held rows over the two calls are 13 live tiles a
    call."""
    tiles = lambda kind: get_registry().counter(
        "consensusml_moe_row_tiles_total", labels={"layer": "tiles_test", "kind": kind})
    before = tiles("live").value, tiles("skipped").value
    rows = np.asarray([[800, 700, 900, 750, 760, 740, 780, 770]])
    absent = np.asarray([2 * 8192 * 6 - 6200])
    moe.record_expert_counts(rows, absent, ["tiles_test"], held_start=0, calls=2)
    assert (tiles("live").value - before[0], tiles("skipped").value - before[1]) == (26, 358)
    moe.record_expert_counts(np.zeros((1, 8), int), np.asarray([8192 * 6]), ["tiles_test"])
    assert (tiles("live").value - before[0], tiles("skipped").value - before[1]) == (26, 358 + 192)



# -- spans and counters ----------------------------------------------------------


def test_scan_counts_its_chunks_and_spans_are_recorded():
    model = tiny()
    ids = ids_for(model, rows=2, seq=21)
    chunks = get_registry().counter("consensusml_ssm_chunks_total", labels={"layer": "0"})
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
    assert {"ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj", "moe.route",
            "moe.sort", "moe.experts", "moe.shared", "moe.combine", "attn.flash"} <= names


def test_a_span_without_a_scope_leaves_the_name_stack_alone():
    from consensusml_tpu.obs import span

    def f(x):
        with span("outer"):
            with span("attn.flash", scope=False):
                return x * 2

    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "outer" in text and "attn.flash" not in text


# -- 6. planted faults fail ---------------------------------------------------------


def _renorm_over_held(held_start, held):
    def route(scores, k, scale, bias=None):
        assert bias is None
        picked, idx = jax.lax.top_k(scores, k)
        here = (idx >= held_start) & (idx < held_start + held)
        total = jnp.sum(jnp.where(here, picked, 0.0), axis=-1, keepdims=True)
        return idx, picked / (total + 1e-20) * scale

    return route


@pytest.mark.parametrize("fault", ["top5", "renorm_over_held", "no_state_carry"])
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    """The comparison of group 2, with one expert fewer a token, weights
    normalised over the held experts only, or chunk states not carried across
    chunks planted in the PROGRAM: each has to read far outside its limit —
    and the reference with the same fault planted has to agree with it."""
    model = tiny()
    c = model.config
    sizes = sizes_of(c)
    params = ref.init_params(7, sizes)
    ids = ids_for(model)
    sound = worst_grad_gap(model, params, ids, sizes)
    assert sound[0] < 2e-5 and sound[2] < 2e-4
    if fault == "top5":
        model = tiny(top_k=c.top_k - 1)
    elif fault == "renorm_over_held":
        monkeypatch.setattr(moe, "route_top_k", _renorm_over_held(c.held_start, c.held))
    else:
        monkeypatch.setattr(ssm, "carried_states", lambda states, decay: jnp.zeros_like(states))
    logits_gap, loss_gap, grad_gap = worst_grad_gap(model, params, ids, sizes)
    # the gradients show it a hundred times over; the logits less (a mixer's
    # output matrix starts at 0.02 / sqrt(104), so one block moves them little)
    assert grad_gap > 100 * 2e-4 and logits_gap > 1.5 * max(sound[0], 2e-5)
    # the benchmark's control is the same fault in the reference: the two agree
    logits, _ = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    twin = reference_logits(params, ids, sizes, faults=(fault,))
    np.testing.assert_allclose(logits, twin, rtol=2e-5, atol=2e-5)


def test_inside_a_checked_shard_map_the_grouped_product_is_xlas(monkeypatch):
    """The collective backend runs the layer inside ``shard_map`` with the
    check of varying axes on; megablox builds its own ``out_shape`` without
    ``vma`` and cannot be called there (on the chip: a ValueError at trace
    time), so on a TPU too the layer takes ``lax.ragged_dot`` there."""
    from jax.sharding import PartitionSpec as P

    monkeypatch.setattr(moe, "on_tpu", lambda: True)  # the kernels would refuse to run off a TPU
    k = jax.random.split(jax.random.key(3), 2)
    lhs = jax.random.normal(k[0], (2, 12, 16))
    rhs = jax.random.normal(k[1], (2, 3, 16, 8))
    sizes = jnp.asarray([[4, 0, 6], [1, 2, 3]], jnp.int32)
    mesh = jax.make_mesh((2,), ("w",))
    inside = jax.shard_map(
        lambda l, r, g: moe.grouped_matmul(l[0], r[0], g[0])[None],
        mesh=mesh, in_specs=P("w"), out_specs=P("w"),
    )(lhs, rhs, sizes)
    want = jnp.stack([jax.lax.ragged_dot(lhs[i], rhs[i], sizes[i]) for i in range(2)])
    np.testing.assert_allclose(inside, want, rtol=1e-5, atol=1e-5)
