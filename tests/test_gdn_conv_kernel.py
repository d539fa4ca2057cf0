"""The fused short-convolution pair of the delta-rule layer
(``models/gated_delta.py:gated_delta_conv``, device events ``gdn_conv_fwd`` /
``gdn_conv_bwd``), interpreted on the CPU.

Held against the mixer's own XLA expression (the causal depthwise convolution
over ``[q | k | v]``, SiLU, the unit normalisation of ``q`` and ``k``, the casts):
the outputs, ``jax.vjp`` in ``qkvz`` and the weights, at the delta cell's head
layout cut small (2 key heads x 2 value heads of 128) and in row tiles small
enough that a row holds three or more, so that both halos cross tile
boundaries. What the interpreter cannot see (Mosaic's tiling, scoped VMEM) is
compiled for the described v5e in ``tests/test_flash_compile_tpu.py``; what only
the chip shows is ``tests/kernels_tpu_child.py``'s group ``gdn_conv``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensusml_tpu.models import gated_delta as gd
from consensusml_tpu.models import short_conv
from consensusml_tpu.obs import get_registry
from test_ssd_kernel import rel  # noqa: E402  (relative L2)

KH, R, DK, DV, KW = 2, 2, 128, 128, 4
F32 = jnp.float32


def config(dtype=F32, **sizes):
    return gd.GatedDeltaConfig(
        **{"hidden": 32, "key_heads": KH, "value_heads": KH * R, "key_dim": DK, "value_dim": DV, "dtype": dtype,
           **sizes})


def xla_conv(qkvz, conv_w, c):
    """The mixer's expression before the kernels, and its ``z``: ``q``, ``k``, ``v``, ``z``."""
    bsz, t, _ = qkvz.shape
    kh, vh, dk, dv, kw = c.key_heads, c.value_heads, c.key_dim, c.value_dim, c.conv_kernel
    r = vh // kh
    heads = qkvz.reshape(bsz, t, kh, -1)
    mixed = jnp.concatenate(
        [heads[..., :dk].reshape(bsz, t, kh * dk), heads[..., dk : 2 * dk].reshape(bsz, t, kh * dk),
         heads[..., 2 * dk : 2 * dk + r * dv].reshape(bsz, t, vh * dv)], axis=-1)
    padded = jnp.pad(mixed, ((0, 0), (kw - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j : j + t] * conv_w[j] for j in range(kw)))
    unit = lambda y: y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
    q = unit(mixed[..., : kh * dk].reshape(bsz, t, kh, dk)) * dk**-0.5
    k = unit(mixed[..., kh * dk : 2 * kh * dk].reshape(bsz, t, kh, dk))
    v = mixed[..., 2 * kh * dk :].reshape(bsz, t, vh, dv)
    z = heads[..., 2 * dk + r * dv :].reshape(bsz, t, vh, dv)
    return tuple(x.astype(c.dtype) for x in (q, k, v, z))


def operands(c, rows, seq, seed=0):
    key = jax.random.split(jax.random.key(seed), 2)
    qkvz = jax.random.normal(key[0], (rows, seq, c.key_heads * (2 * c.key_dim + 2 * c.value_heads // c.key_heads
                                                                * c.value_dim)))
    conv_w = jax.random.uniform(key[1], (c.conv_kernel, c.conv_dim), F32, -0.5, 0.5)
    return qkvz, conv_w


def kernels(c):
    return lambda qkvz, conv_w: gd.gated_delta_conv(qkvz, conv_w, c, interpret=True)


def cotangents(outs, seed=1):
    return tuple(jax.random.normal(k, o.shape).astype(o.dtype)
                 for k, o in zip(jax.random.split(jax.random.key(seed), len(outs)), outs))


@pytest.fixture
def tile(monkeypatch):
    monkeypatch.setattr(short_conv, "ROWS", 32)
    return 32


# -- 1. the pair is the mixer's expression -----------------------------------------

# per dtype: (outputs, gradients). Jitted, the forward is the expression's bit
# for bit on this backend (the taps summed in its order); the backward is
# autodiff's chain rule written out, the same float32 operations in another order.
_LIMITS = {F32: (0.0, 2e-6), jnp.bfloat16: (0.0, 2e-6)}


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rows, tiles", [(2, 3), (1, 4), (1, 1)], ids=["two_rows_three_tiles", "four_tiles", "one_tile"])
def test_pair_matches_the_mixers_expression(tile, dtype, rows, tiles):
    c = config(dtype)
    args = operands(c, rows, tiles * tile)
    pair, expression = kernels(c), lambda *a: xla_conv(*a, c)
    got, want = jax.jit(pair)(*args), jax.jit(expression)(*args)
    y_limit, grad_limit = _LIMITS[dtype]
    for name, g, w in zip("qkvz", got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == dtype, name
        assert rel(g, w) <= y_limit, name
    cts = cotangents(got)
    grads = lambda f: jax.jit(lambda cts, *a: jax.vjp(f, *a)[1](cts))(cts, *args)
    for name, g, w in zip(("qkvz", "conv_w"), grads(pair), grads(expression)):
        assert g.shape == w.shape and g.dtype == w.dtype == F32, name
        assert rel(g, w) < grad_limit, name


def test_under_vmap_over_two_workers(tile):
    """The stacked backend: ``vmap`` prepends the worker axis to the grid; each
    worker's weight gradient is summed over its own rows and tiles alone."""
    c = config(jnp.bfloat16)
    stacked = tuple(jnp.stack(pair) for pair in zip(operands(c, 2, 3 * tile, 1), operands(c, 2, 3 * tile, 2)))

    def grads(f):
        def one(qkvz, conv_w):
            outs, vjp = jax.vjp(f, qkvz, conv_w)
            return vjp(cotangents(outs))

        return jax.jit(jax.vmap(one))(*stacked)

    for g, w in zip(grads(kernels(c)), grads(lambda *a: xla_conv(*a, c))):
        assert rel(g, w) < 2e-6


def test_inside_a_checked_shard_map(tile):
    """The collective backend: the kernels' ``out_shape`` says where they vary,
    the custom VJP's cotangents vary as the primals do."""
    from jax.sharding import PartitionSpec as P

    c = config(jnp.bfloat16)
    stacked = tuple(jnp.stack(pair) for pair in zip(operands(c, 1, 3 * tile, 1), operands(c, 1, 3 * tile, 2)))
    probes = cotangents(jax.eval_shape(kernels(c), stacked[0][0], stacked[1][0]))

    def mapped(f):
        def one(qkvz, conv_w):
            loss = lambda *a: sum(jnp.sum(o.astype(F32) * p.astype(F32)) for o, p in zip(f(*a), probes))
            return tuple(g[None] for g in jax.grad(loss, argnums=(0, 1))(qkvz[0], conv_w[0]))

        return jax.jit(jax.shard_map(one, mesh=jax.make_mesh((2,), ("w",)), in_specs=P("w"), out_specs=P("w")))

    for g, w in zip(mapped(kernels(c))(*stacked), mapped(lambda *a: xla_conv(*a, c))(*stacked)):
        assert rel(g, w) < 2e-6


# -- 2. what the taps reach ---------------------------------------------------------


def _reached(out):
    """The rows of an output (b, t, heads, d) that are not zero."""
    return sorted(set(np.nonzero(np.asarray(jnp.abs(out.astype(F32)).sum(axis=(0, 2, 3))))[0].tolist()))


@pytest.mark.parametrize("spike", ["first_row", "last_row_of_a_tile"])
def test_a_spike_reaches_the_next_three_rows_only(tile, spike):
    """Forward: a spike in row ``s`` of the input reaches rows ``s`` to ``s + 3``
    (``kw`` = 4) and no other, across a tile boundary too; backward: a spike
    in row ``s`` of the cotangent reaches the input's rows ``s - 3`` to ``s``."""
    c = config()
    qkvz, conv_w = operands(c, 1, 3 * tile)
    row = 0 if spike == "first_row" else tile - 1
    x = jnp.zeros_like(qkvz).at[:, row].set(qkvz[:, row])
    outs, vjp = jax.vjp(kernels(c), x, conv_w)
    for name, out in zip("qkv", outs):
        assert _reached(out) == list(range(row, row + KW)), name
    at = row + KW - 1  # back from the last row the spike reached: the next tile's third row, or row 3
    cts = tuple(jnp.zeros_like(o).at[:, at].set(g[:, at]) for o, g in zip(outs, cotangents(outs)))
    dx, _ = vjp(cts[:3] + (jnp.zeros_like(cts[3]),))
    assert sorted(set(np.nonzero(np.asarray(jnp.abs(dx).sum(axis=(0, 2))))[0].tolist())) == list(
        range(at - KW + 1, at + 1))


def test_the_gates_columns_carry_only_the_gates_cotangent(tile):
    """``z`` passes through the pair as a cast: from ``q``, ``k`` and ``v``
    alone the cotangent of ``qkvz``'s gate columns is exactly zero, and from
    ``z``'s cotangent alone it is that cotangent in float32, exactly."""
    c = config(jnp.bfloat16)
    args = operands(c, 2, 3 * tile)
    outs, vjp = jax.vjp(kernels(c), *args)
    cts = cotangents(outs)
    dx, _ = vjp(cts[:3] + (jnp.zeros_like(cts[3]),))
    gate = lambda d: d.reshape(2, 3 * tile, KH, -1)[..., 2 * DK + R * DV :]
    assert not np.asarray(gate(dx)).any() and np.asarray(dx).any()
    dx, dw = vjp(tuple(jnp.zeros_like(x) for x in cts[:3]) + (cts[3],))
    np.testing.assert_array_equal(gate(dx), cts[3].astype(F32).reshape(2, 3 * tile, KH, -1))
    assert not np.asarray(dw).any()
    assert not np.asarray(dx.reshape(2, 3 * tile, KH, -1)[..., : 2 * DK + R * DV]).any()


# -- 3. which path runs is observed -------------------------------------------------


@pytest.mark.parametrize(
    "tpu, rows, sizes, want",
    [
        (True, 8192, {}, "pallas"),  # the delta cell's mixer
        (False, 8192, {}, "xla"),  # the same off a TPU
        (True, 256, {}, "pallas"),  # one tile, the whole row
        (True, 8192, {"value_dim": 256}, "pallas"),  # wider value heads: each piece still at a multiple of its width
        (True, 8192, {"key_dim": 64}, "xla"),  # a key width that is no multiple of the lanes
        (True, 8192, {"value_dim": 192}, "xla"),  # nor a value width
        (True, 8192, {"value_dim": 384}, "xla"),  # a value head off its own width's multiples in qkvz
        (True, 1000, {}, "xla"),  # rows that are no multiple of the row tile
        (True, 200, {}, "xla"),  # a row shorter than a tile, no multiple of the bfloat16 sublane tile
        (True, 8192, {"key_heads": 2, "value_heads": 4, "key_dim": 8, "value_dim": 8}, "xla"),  # the smoke recipe's
    ],
)
def test_path_is_observed_from_platform_and_shapes(monkeypatch, tpu, rows, sizes, want):
    monkeypatch.setattr(gd, "on_tpu", lambda: tpu)
    assert gd._conv_impl(rows, config(**sizes)) == want


# -- 4. the mixer around them -------------------------------------------------------


def _mixer(dtype=F32, seq=96):
    c = config(dtype, chunk=32)
    mixer = gd.GatedDeltaNetMixer(c, layer=9)
    u = jax.random.normal(jax.random.key(0), (2, seq, c.hidden))
    return mixer, mixer.init(jax.random.key(1), u)["params"], u


@pytest.mark.parametrize("dtype, limit", [(F32, 2e-6), (jnp.bfloat16, 1e-2)], ids=["float32", "bfloat16"])
def test_mixer_reads_the_same_with_the_conv_as_kernels(monkeypatch, tile, dtype, limit):
    """Output, ``out_rms`` and every parameter's gradient, the rule XLA's on
    both sides: float32 to its rounding, bfloat16 where a cotangent rounded in
    another place moves a bfloat16 operand of the rule's products."""
    mixer, params, u = _mixer(dtype)

    def run(p):
        def loss(p):
            out, out_rms = mixer.apply({"params": p}, u)
            return jnp.sum(jnp.sin(out.astype(F32))), (out, out_rms)

        return jax.value_and_grad(loss, has_aux=True)(p)

    (_, (want, want_rms)), want_grads = jax.jit(run)(params)
    monkeypatch.setattr(gd, "_conv_impl", lambda *a: "interpret")
    (_, (got, got_rms)), got_grads = jax.jit(run)(params)
    assert rel(got, want) < limit and rel(got_rms, want_rms) < limit
    for name in want_grads:
        assert rel(got_grads[name], want_grads[name]) < limit, name


@pytest.mark.parametrize("impl, label", [("interpret", "kernel"), ("xla", "xla")])
def test_counter_says_which_path_a_traced_mixer_took(monkeypatch, tile, impl, label):
    monkeypatch.setattr(gd, "_conv_impl", lambda *a: impl)
    count = lambda l: get_registry().counter("consensusml_gdn_conv_impl_total", labels={"layer": "9", "impl": l}).value
    other = "xla" if label == "kernel" else "kernel"
    mixer, params, u = _mixer()  # its init traces the mixer too
    before = count(label), count(other)
    jax.eval_shape(lambda p: mixer.apply({"params": p}, u), params)
    assert (count(label), count(other)) == (before[0] + 1, before[1])
