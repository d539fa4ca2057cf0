"""The fused short-convolution pair of the state-space layer
(``models/ssm.py:ssd_conv``, device events ``ssd_conv_fwd`` / ``ssd_conv_bwd``),
interpreted on the CPU.

Held against the mixer's own XLA expression (the causal depthwise convolution
over ``xBC``, the bias, SiLU, the cast, the slices into ``x``, ``B`` and ``C``;
``z`` and ``dt``'s columns as they are): the outputs, ``jax.vjp`` in ``proj``,
the weights and the bias, at the hybrid cell's layout cut small (groups of two
or four heads of 64, a state of 128) and in row tiles small enough that a row
holds three or more, so that both halos cross tile boundaries. What the
interpreter cannot see (Mosaic's tiling, scoped VMEM) is compiled for the
described v5e in ``tests/test_flash_compile_tpu.py``; what only the chip shows
is ``tests/kernels_tpu_child.py``'s group ``ssm_conv``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensusml_tpu.models import short_conv
from consensusml_tpu.models import ssm
from consensusml_tpu.obs import get_registry
from test_ssd_kernel import rel  # noqa: E402  (relative L2)

KW = 4
F32 = jnp.float32
# (heads, groups): proj's columns in blocks of 256 (z 2, x 2, B 1, C 1, dt 1) and of 512 (z 2, x 2, B 1, C 1, dt 1)
SIZES = {"blocks_of_256": (8, 2), "blocks_of_512": (16, 4)}


def config(dtype=F32, size="blocks_of_256", **sizes):
    heads, groups = SIZES[size]
    return ssm.Mamba2Config(
        **{"hidden": 32, "heads": heads, "head_dim": 64, "groups": groups, "state": 128, "chunk": 32,
           "dtype": dtype, **sizes})


def xla_conv(proj, conv_w, conv_b, c):
    """The mixer's expression before the kernels, and what it leaves of
    ``proj``: ``z``, ``x``, ``B``, ``C``, ``dt``'s columns."""
    bsz, t, _ = proj.shape
    d_in, gn, kw = c.d_inner, c.groups * c.state, c.conv_kernel
    xbc = proj[..., d_in : d_in + c.conv_dim]
    padded = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j : j + t] * conv_w[j] for j in range(kw)) + conv_b).astype(c.dtype)
    groups = lambda a: a.reshape(bsz, t, c.groups, c.state)
    return (proj[..., :d_in].astype(c.dtype), xbc[..., :d_in].reshape(bsz, t, c.heads, c.head_dim),
            groups(xbc[..., d_in : d_in + gn]), groups(xbc[..., d_in + gn :]), proj[..., d_in + c.conv_dim :])


def operands(c, rows, seq, seed=0):
    key = jax.random.split(jax.random.key(seed), 3)
    proj = jax.random.normal(key[0], (rows, seq, 2 * c.d_inner + 2 * c.groups * c.state + c.heads))
    conv_w = jax.random.uniform(key[1], (c.conv_kernel, c.conv_dim), F32, -0.5, 0.5)
    conv_b = jax.random.normal(key[2], (c.conv_dim,)) * 0.3
    return proj, conv_w, conv_b


def kernels(c):
    return lambda *a: ssm.ssd_conv(*a, c, interpret=True)


def cotangents(outs, seed=1):
    return tuple(jax.random.normal(k, o.shape).astype(o.dtype)
                 for k, o in zip(jax.random.split(jax.random.key(seed), len(outs)), outs))


@pytest.fixture
def tile(monkeypatch):
    monkeypatch.setattr(short_conv, "ROWS", 32)
    return 32


# -- 1. the pair is the mixer's expression -----------------------------------------

# Jitted, the forward is the expression's bit for bit on this backend (the taps
# summed in its order, then the bias); the backward is autodiff's chain rule
# written out, the same float32 operations in another order.
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "size, rows, tiles",
    [("blocks_of_256", 2, 3), ("blocks_of_256", 1, 1), ("blocks_of_512", 1, 4)],
    ids=["blocks_of_256_two_rows_three_tiles", "blocks_of_256_one_tile", "blocks_of_512_four_tiles"])
def test_pair_matches_the_mixers_expression(tile, dtype, size, rows, tiles):
    c = config(dtype, size)
    args = operands(c, rows, tiles * tile)
    pair, expression = kernels(c), lambda *a: xla_conv(*a, c)
    got, want = jax.jit(pair)(*args), jax.jit(expression)(*args)
    for name, g, w in zip(("z", "x", "B", "C", "dt"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    cts = cotangents(got)
    grads = lambda f: jax.jit(lambda cts, *a: jax.vjp(f, *a)[1](cts))(cts, *args)
    for name, g, w in zip(("proj", "conv_w", "conv_b"), grads(pair), grads(expression)):
        assert g.shape == w.shape and g.dtype == w.dtype == F32, name
        assert rel(g, w) < 2e-6, name


def test_under_vmap_over_two_workers(tile):
    """The stacked backend: ``vmap`` prepends the worker axis to the grid; each
    worker's weight and bias gradients are summed over its own rows and tiles alone."""
    c = config(jnp.bfloat16)
    stacked = tuple(jnp.stack(pair) for pair in zip(operands(c, 2, 3 * tile, 1), operands(c, 2, 3 * tile, 2)))

    def grads(f):
        def one(*args):
            outs, vjp = jax.vjp(f, *args)
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
    probes = cotangents(jax.eval_shape(kernels(c), *(a[0] for a in stacked)))

    def mapped(f):
        def one(*args):
            loss = lambda *a: sum(jnp.sum(o.astype(F32) * p.astype(F32)) for o, p in zip(f(*a), probes))
            return tuple(g[None] for g in jax.grad(loss, argnums=(0, 1, 2))(*(a[0] for a in args)))

        return jax.jit(jax.shard_map(one, mesh=jax.make_mesh((2,), ("w",)), in_specs=P("w"), out_specs=P("w")))

    for g, w in zip(mapped(kernels(c))(*stacked), mapped(lambda *a: xla_conv(*a, c))(*stacked)):
        assert rel(g, w) < 2e-6


# -- 2. what the taps reach ---------------------------------------------------------


def _reached(out):
    """The rows of an output (b, t, ...) that are not zero."""
    by_row = jnp.abs(out.astype(F32)).reshape(out.shape[:2] + (-1,)).sum(axis=(0, 2))
    return np.nonzero(np.asarray(by_row))[0].tolist()


@pytest.mark.parametrize("spike", ["first_row", "last_row_of_a_tile"])
def test_a_spike_reaches_the_next_three_rows_only(tile, spike):
    """Forward: a spike in row ``s`` of ``xBC`` reaches rows ``s`` to ``s + 3``
    (``kw`` = 4) of ``x``, ``B`` and ``C`` and no other, across a tile boundary
    too (the bias is zero, so a row the spike does not reach reads SiLU(0) =
    0); backward: a spike in row ``s`` of the cotangent reaches the input's
    rows ``s - 3`` to ``s``."""
    c = config()
    proj, conv_w, conv_b = operands(c, 1, 3 * tile)
    row = 0 if spike == "first_row" else tile - 1
    x = jnp.zeros_like(proj).at[:, row].set(proj[:, row])
    outs, vjp = jax.vjp(kernels(c), x, conv_w, jnp.zeros_like(conv_b))
    for name, out in zip("xBC", outs[1:4]):
        assert _reached(out) == list(range(row, row + KW)), name
    at = row + KW - 1  # back from the last row the spike reached: the next tile's third row, or row 3
    cts = [jnp.zeros_like(o).at[:, at].set(g[:, at]) for o, g in zip(outs, cotangents(outs))]
    dx = vjp((jnp.zeros_like(cts[0]), *cts[1:4], jnp.zeros_like(cts[4])))[0]
    assert _reached(dx) == list(range(at - KW + 1, at + 1))


def test_z_and_dt_columns_carry_only_their_own_cotangents(tile):
    """``z`` passes through the pair as a cast and ``dt``'s columns as they
    are: from ``x``, ``B`` and ``C`` alone the cotangent of ``proj``'s ``z`` and
    ``dt`` columns is exactly zero, and from ``z``'s and ``dt``'s cotangents
    alone it is those cotangents in float32, exactly, and nothing else."""
    c = config(jnp.bfloat16)
    args = operands(c, 2, 3 * tile)
    outs, vjp = jax.vjp(kernels(c), *args)
    cts = cotangents(outs)
    zero = lambda i: jnp.zeros_like(cts[i])
    d_in, tail = c.d_inner, c.d_inner + c.conv_dim
    dx, dw, db = vjp((zero(0),) + cts[1:4] + (zero(4),))
    assert not np.asarray(dx[..., :d_in]).any() and not np.asarray(dx[..., tail:]).any()
    assert np.asarray(dx[..., d_in:tail]).any() and np.asarray(dw).any() and np.asarray(db).any()
    dx, dw, db = vjp((cts[0], zero(1), zero(2), zero(3), cts[4]))
    np.testing.assert_array_equal(dx[..., :d_in], cts[0].astype(F32))
    np.testing.assert_array_equal(dx[..., tail:], cts[4])
    assert not np.asarray(dx[..., d_in:tail]).any() and not np.asarray(dw).any() and not np.asarray(db).any()


# -- 3. which path runs is observed -------------------------------------------------


@pytest.mark.parametrize(
    "tpu, rows, sizes, want",
    [
        (True, 8192, {}, "pallas"),  # the hybrid cell's mixer
        (False, 8192, {}, "xla"),  # the same off a TPU
        (True, 256, {}, "pallas"),  # one tile, the whole row
        (True, 8192, {"head_dim": 32}, "pallas"),  # narrower heads: a group's x columns still whole lane tiles
        (True, 8192, {"head_dim": 40}, "xla"),  # a group's x columns off the lanes
        (True, 8192, {"state": 64}, "xla"),  # a state off the lanes
        (True, 8192, {"heads": 640, "head_dim": 8, "groups": 8}, "xla"),  # dt's columns past one block
        (True, 8192, {"conv_kernel": 10}, "xla"),  # taps that reach past a halo
        (True, 1000, {}, "xla"),  # rows that are no multiple of the row tile
        (True, 200, {}, "xla"),  # a row shorter than a tile, no multiple of the bfloat16 sublane tile
        (True, 8192, {"heads": 8, "head_dim": 8, "groups": 2, "state": 16}, "xla"),  # the smoke recipe's
    ],
)
def test_path_is_observed_from_platform_and_shapes(monkeypatch, tpu, rows, sizes, want):
    monkeypatch.setattr(ssm, "on_tpu", lambda: tpu)
    c = ssm.Mamba2Config(**sizes)
    assert ssm._conv_impl(rows, c) == want


# -- 4. the mixer around them -------------------------------------------------------


def _mixer(dtype=F32, seq=96):
    c = config(dtype)
    mixer = ssm.Mamba2Mixer(c, layer=9)
    u = jax.random.normal(jax.random.key(0), (2, seq, c.hidden))
    params = mixer.init(jax.random.key(1), u)["params"]
    params["conv_bias"] = jax.random.normal(jax.random.key(2), params["conv_bias"].shape) * 0.3  # zeros at init
    return mixer, params, u


@pytest.mark.parametrize("dtype, limit", [(F32, 2e-6), (jnp.bfloat16, 1e-2)], ids=["float32", "bfloat16"])
def test_mixer_reads_the_same_with_the_conv_as_kernels(monkeypatch, tile, dtype, limit):
    """Output, ``scan_rms`` and every parameter's gradient, the scan XLA's on
    both sides: float32 to its rounding, bfloat16 where a cotangent rounded in
    another place moves a bfloat16 operand of the scan's products."""
    mixer, params, u = _mixer(dtype)

    def run(p):
        def loss(p):
            out, scan_rms = mixer.apply({"params": p}, u)
            return jnp.sum(jnp.sin(out.astype(F32))), (out, scan_rms)

        return jax.value_and_grad(loss, has_aux=True)(p)

    (_, (want, want_rms)), want_grads = jax.jit(run)(params)
    monkeypatch.setattr(ssm, "_conv_impl", lambda *a: "interpret")
    (_, (got, got_rms)), got_grads = jax.jit(run)(params)
    assert rel(got, want) < limit and rel(got_rms, want_rms) < limit
    for name in want_grads:
        assert rel(got_grads[name], want_grads[name]) < limit, name


@pytest.mark.parametrize("impl, label", [("interpret", "kernel"), ("xla", "xla")])
def test_counter_says_which_path_a_traced_mixer_took(monkeypatch, tile, impl, label):
    monkeypatch.setattr(ssm, "_conv_impl", lambda *a: impl)
    count = lambda l: get_registry().counter("consensusml_ssm_conv_impl_total", labels={"layer": "9", "impl": l}).value
    other = "xla" if label == "kernel" else "kernel"
    mixer, params, u = _mixer()  # its init traces the mixer too
    before = count(label), count(other)
    jax.eval_shape(lambda p: mixer.apply({"params": p}, u), params)
    assert (count(label), count(other)) == (before[0] + 1, before[1])
