"""The hyper-connected residual's fused kernels (``models/hyper_connections.py``:
``mhc_read_fwd`` / ``mhc_read_bwd``, ``mhc_write_fwd`` / ``mhc_write_bwd``),
interpreted on the CPU.

The kernels' arithmetic is the plain path's (XLA's, what the CPU and
``tests/test_xing4.py`` run) to float32 round-off, so a sub-block's read and
write are held against it: ``u``, the maps, ``X'`` and the stream sizes, and
``jax.grad`` with respect to ``X``, ``y``, ``phi``, ``bias`` and ``gate``, at the
latent cell's four streams cut small (512 tokens x 256: two tiles of the forward
kernels, four of the backward ones). What the interpreter cannot see (Mosaic's
tiling, VMEM) is compiled for the described v5e in
``tests/test_flash_compile_tpu.py``; what only the chip shows is
``tests/kernels_tpu_child.py``'s group ``mhc``.
"""

import jax
import jax.numpy as jnp
import pytest

from consensusml_tpu.models import hyper_connections as hc
from consensusml_tpu.obs import get_registry

F32, BF16 = jnp.float32, jnp.bfloat16
N = 4


def rel(a, b) -> float:
    a, b = a.astype(F32), b.astype(F32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def operands(rows=1, tokens=512, hidden=256, seed=0):
    """A sub-block's module, its parameters (``phi`` and the gates away from
    their initial values, so that every map moves), streams, a sub-block's output
    and three probes (for ``u``, ``X'`` and the stream sizes)."""
    key = jax.random.split(jax.random.key(seed), 7)
    mod = hc.HyperConnection(hc.HyperConfig(hidden=hidden, streams=N), layer=7)
    x = (1.5 * jax.random.normal(key[0], (rows, N, tokens, hidden))).astype(BF16)
    y = jax.random.normal(key[1], (rows, tokens, hidden)).astype(BF16)
    params = mod.init(key[2], x)["params"]
    params = {**params, "phi": 5.0 * params["phi"], "gate": jnp.asarray([0.7, 1.3, 0.9])}
    probes = (jax.random.normal(key[3], (rows, tokens, hidden)), jax.random.normal(key[4], x.shape),
              jax.random.normal(key[5], (rows, N)))
    return mod, params, x, y, probes


def sub_block(mod):
    """The read, the sub-block's ``y`` (given), the write: every output."""

    def run(p, x, y):
        u, h_res, h_post, streams = mod.apply({"params": p}, x, return_streams=True)
        out, stream_rms = hc.hyper_post(streams, h_res, h_post, y)
        return {"u": u, "h_res": h_res, "h_post": h_post, "x_out": out, "stream_rms": stream_rms}

    return run


def probed(mod, probes, sized=True):
    """A scalar of every differentiable output; ``sized``: of the stream sizes too."""

    def loss(p, x, y):
        got = sub_block(mod)(p, x, y)
        total = jnp.sum(got["u"] * probes[0]) + jnp.sum(got["x_out"].astype(F32) * probes[1])
        return total + (100.0 * jnp.sum(got["stream_rms"] * probes[2]) if sized else 0.0)

    return loss


def on_path(monkeypatch, impl, f, *args):
    """``f`` jitted anew (a function jit has not seen) with the path forced."""
    monkeypatch.setattr(hc, "_mix_impl", lambda x: impl)
    return jax.jit(lambda *a: f(*a))(*args)


def flat(grads):
    by_param, dx, dy = grads
    return {**{f"d{k}": v for k, v in by_param.items()}, "dx": dx, "dy": dy}


# what float32 sums in another order leave; ``X'``, ``dX`` and ``dy`` are bfloat16:
# a value at a rounding boundary falls the other way, and ``dX`` is rounded twice
# (the write's share, then the sum) where autodiff rounds the float32 sum once
_VALUE_LIMITS = {"u": 1e-5, "h_res": 1e-5, "h_post": 1e-5, "x_out": 5e-4, "stream_rms": 1e-5}
_GRAD_LIMITS = {"dphi": 2e-5, "dbias": 2e-5, "dgate": 2e-5, "dx": 6e-3, "dy": 1e-3}
SHAPES = pytest.mark.parametrize(
    "rows, tokens, hidden", [(1, 512, 256), (2, 256, 128), (1, 256, 384)],
    ids=["two_tiles", "two_rows_one_tile", "three_lane_tiles"])


# -- 1. the kernels are the plain path ---------------------------------------------


@SHAPES
def test_forward_matches_the_plain_path(monkeypatch, rows, tokens, hidden):
    mod, params, x, y, _ = operands(rows, tokens, hidden)
    want = on_path(monkeypatch, "xla", sub_block(mod), params, x, y)
    got = on_path(monkeypatch, "interpret", sub_block(mod), params, x, y)
    for name, limit in _VALUE_LIMITS.items():
        assert got[name].shape == want[name].shape and got[name].dtype == want[name].dtype, name
        assert rel(got[name], want[name]) < limit, name
    # doubly stochastic to what twenty iterations reach, on either path
    assert float(jnp.max(jnp.abs(jnp.sum(got["h_res"], axis=0) - 1.0))) < 1e-4


@SHAPES
@pytest.mark.parametrize("sized", [False, True], ids=["through_the_streams", "through_the_sizes_too"])
def test_gradients_match_the_plain_path(monkeypatch, rows, tokens, hidden, sized):
    """``sized``: the stream sizes carry a cotangent, so ``mhc_write_bwd`` makes
    ``X'`` in float32 again; without one (the training step) it does not."""
    mod, params, x, y, probes = operands(rows, tokens, hidden)
    grad = jax.grad(probed(mod, probes, sized), argnums=(0, 1, 2))
    want = flat(on_path(monkeypatch, "xla", grad, params, x, y))
    got = flat(on_path(monkeypatch, "interpret", grad, params, x, y))
    for name, limit in _GRAD_LIMITS.items():
        assert got[name].shape == want[name].shape and got[name].dtype == want[name].dtype, name
        assert rel(got[name], want[name]) < limit, name


def test_a_planted_fault_reaches_the_kernels(monkeypatch):
    """One Sinkhorn iteration in place of twenty (the benchmark's ``sinkhorn_1``,
    planted in :func:`hyper_connections.sinkhorn` as its rehearsal does): the
    kernels read it as the plain path does, and far from the twenty."""
    mod, params, x, y, _ = operands()
    whole = on_path(monkeypatch, "interpret", sub_block(mod), params, x, y)
    real = hc.sinkhorn
    monkeypatch.setattr(hc, "sinkhorn", lambda m, iters, eps: real(m, 1, eps))
    want = on_path(monkeypatch, "xla", sub_block(mod), params, x, y)
    got = on_path(monkeypatch, "interpret", sub_block(mod), params, x, y)
    assert rel(got["h_res"], want["h_res"]) < 1e-5 and rel(got["x_out"], want["x_out"]) < 5e-4
    assert rel(got["h_res"], whole["h_res"]) > 1e-2


def test_the_highest_products_terms_are_kept():
    """``HIGHEST`` multiplies float32 operands as three bfloat16 pieces each and
    keeps six of the nine products. ``_split3``'s pieces add up to the operand
    exactly; with ``X`` bfloat16 the three products ``X`` by ``Phi``'s pieces are
    all that is non-zero, and stacked along the contraction as ``_SIX`` says the
    six are one bfloat16 product: both read as XLA's ``HIGHEST`` to float32
    round-off, where one bfloat16 pass reads a thousand times further off."""
    key = jax.random.split(jax.random.key(3), 3)
    a, b = jax.random.normal(key[0], (64, 48)), jax.random.normal(key[1], (48, 256))
    x = jax.random.normal(key[2], (64, 48)).astype(BF16)
    pieces = lambda v: [p.astype(F32) for p in hc._split3(v)]
    assert jnp.array_equal(sum(pieces(a)), a) and jnp.array_equal(hc._split3(x.astype(F32))[0], x)
    exact = lambda u, v: jnp.dot(u, v, precision=jax.lax.Precision.HIGHEST)
    one_pass = lambda u, v: jnp.dot(u.astype(BF16), v.astype(BF16), preferred_element_type=F32)
    stacked = one_pass(
        jnp.concatenate([pieces(a)[i] for i in hc._SIX[0]], axis=1),
        jnp.concatenate([pieces(b)[i] for i in hc._SIX[1]], axis=0))
    assert rel(stacked, exact(a, b)) < 2e-6 < 2e-3 < rel(one_pass(a, b), exact(a, b))
    by_pieces = one_pass(jnp.concatenate([x.astype(F32)] * 3, axis=1), jnp.concatenate(pieces(b), axis=0))
    assert rel(by_pieces, exact(x.astype(F32), b)) < 2e-6


# -- 2. both backends take them, and remat ----------------------------------------


def _two_workers():
    mod, p0, x0, y0, probes = operands(seed=1)
    _, p1, x1, y1, _ = operands(seed=2)
    stack = lambda a, b: jax.tree.map(lambda u, v: jnp.stack([u, v]), a, b)
    return mod, (stack(p0, p1), stack(x0, x1), stack(y0, y1)), probes


def _assert_same(got, want, limits):
    got, want = flat(got), flat(want)
    for name, limit in limits.items():
        assert got[name].shape == want[name].shape and rel(got[name], want[name]) < limit, name


def test_under_vmap_over_two_workers(monkeypatch):
    """The stacked backend: ``vmap`` prepends the worker axis to the grid; each
    worker's ``dPhi`` is added up over its own tiles."""
    mod, stacked, probes = _two_workers()
    grad = jax.grad(probed(mod, probes, sized=False), argnums=(0, 1, 2))
    got = on_path(monkeypatch, "interpret", jax.vmap(grad), *stacked)
    alone = [on_path(monkeypatch, "interpret", grad, *jax.tree.map(lambda v: v[w], stacked)) for w in range(2)]
    _assert_same(got, jax.tree.map(lambda u, v: jnp.stack([u, v]), *alone), dict.fromkeys(_GRAD_LIMITS, 1e-6))
    _assert_same(got, on_path(monkeypatch, "xla", jax.vmap(grad), *stacked), _GRAD_LIMITS)


def test_inside_a_checked_shard_map(monkeypatch):
    """The collective backend: a ``shard_map`` with the check of varying axes
    on. The kernels' ``out_shape`` says where they vary, the custom VJPs'
    cotangents vary as the primals do."""
    from jax.sharding import PartitionSpec as P

    mod, stacked, probes = _two_workers()
    grad = jax.grad(probed(mod, probes, sized=False), argnums=(0, 1, 2))
    one = lambda t: jax.tree.map(lambda a: a[0], t)
    mapped = jax.shard_map(
        lambda *args: jax.tree.map(lambda a: a[None], grad(*one(args))),
        mesh=jax.make_mesh((2,), ("w",)), in_specs=P("w"), out_specs=P("w"))
    _assert_same(
        on_path(monkeypatch, "interpret", mapped, *stacked), on_path(monkeypatch, "xla", mapped, *stacked),
        _GRAD_LIMITS)


def _pallas_scopes(jaxpr, outer=""):
    for e in jaxpr.eqns:
        here = f"{outer}/{e.source_info.name_stack}".strip("/")
        if e.primitive.name == "pallas_call":
            yield here
        for sub in e.params.values():  # a custom VJP's call, a remat's body
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _pallas_scopes(sub, here)


def test_under_checkpoint_the_gradient_is_unchanged_and_nothing_large_is_saved(monkeypatch):
    """``nn.remat(_Block)``: the rematted forward pass runs the read again for the
    sub-block between the two, and the write not at all (its residuals are its
    inputs); the gradient is the same to the bit."""
    mod, params, x, y, probes = operands(tokens=256, hidden=128)
    loss = probed(mod, probes, sized=False)
    plain = on_path(monkeypatch, "interpret", jax.grad(loss, argnums=(0, 1, 2)), params, x, y)
    remat = jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))
    got = on_path(monkeypatch, "interpret", remat, params, x, y)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(plain)):
        assert jnp.array_equal(g, w)
    from jax._src.interpreters import partial_eval as pe

    closed = jax.make_jaxpr(jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1, 2)))(params, x, y)
    live, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    kernels = sorted(s.rsplit("/", 1)[-1] for s in _pallas_scopes(live))
    assert kernels == ["mhc_read_bwd", "mhc_read_fwd", "mhc_read_fwd", "mhc_write_bwd", "mhc_write_fwd"], kernels


# -- 3. which path runs is observed ------------------------------------------------


@pytest.mark.parametrize(
    "tpu, shape, dtype, want",
    [
        (True, (1, 4, 4096, 3584), BF16, "kernel"),  # the latent cell's streams
        (False, (1, 4, 4096, 3584), BF16, "xla"),  # the same off a TPU
        (True, (2, 4, 256, 128), BF16, "kernel"),  # the smallest that tiles
        (True, (1, 4, 4096, 3584), F32, "xla"),  # float32 streams: X would not be one bfloat16 piece
        (True, (2, 4, 32, 64), BF16, "xla"),  # xing4_tiny and the smoke recipe: a hidden size under the lanes
        (True, (1, 4, 4096, 3520), BF16, "xla"),  # a hidden size that is no multiple of the lanes
        (True, (1, 4, 4000, 3584), BF16, "xla"),  # a token count that is no multiple of the tiles
        (True, (1, 4, 128, 3584), BF16, "xla"),  # nor this one: a tile of the forward kernels is 256
    ],
)
def test_path_is_observed_from_platform_and_shapes(monkeypatch, tpu, shape, dtype, want):
    monkeypatch.setattr(hc, "on_tpu", lambda: tpu)
    assert hc._mix_impl(jax.ShapeDtypeStruct(shape, dtype)) == want


@pytest.mark.parametrize("impl, label", [("interpret", "kernel"), ("xla", "xla")])
def test_counter_says_which_path_a_traced_sub_block_took(monkeypatch, impl, label):
    mod, params, x, y, _ = operands(tokens=256, hidden=128)  # its init traces the module too
    monkeypatch.setattr(hc, "_mix_impl", lambda x: impl)
    count = lambda l: get_registry().counter(
        "consensusml_mhc_impl_total", labels={"layer": "7", "impl": l}).value
    iters = get_registry().counter("consensusml_mhc_sinkhorn_iters_total", labels={"layer": "7"})
    other = "xla" if label == "kernel" else "kernel"
    before = count(label), count(other), iters.value
    jax.eval_shape(sub_block(mod), params, x, y)
    assert (count(label), count(other), iters.value) == (before[0] + 1, before[1], before[2] + 20)


def test_tiny_shapes_take_the_plain_path_on_a_tpu_too(monkeypatch):
    """The smoke-scale recipe's streams on a TPU: no kernel is traced."""
    monkeypatch.setattr(hc, "on_tpu", lambda: True)
    mod, params, x, y, _ = operands(rows=2, tokens=32, hidden=64)
    assert "pallas_call" not in str(jax.make_jaxpr(sub_block(mod))(params, x, y))


def test_one_trace_a_kernel_under_the_callers_spans(monkeypatch):
    """Two sub-blocks, forward and backward: one trace of each kernel serves both,
    and every call's equation sits under its own block's name, an ``mhc.*`` span
    (what ``readers/scope_time.py`` finds the device events by) and the kernel's
    own scope, which names the device op (``h_<i>`` alone would count it as flash
    attention)."""
    traces = []
    for name in ("_read_fwd_kernel", "_read_bwd_kernel", "_write_fwd_kernel", "_write_bwd_kernel"):
        real = getattr(hc, name)
        monkeypatch.setattr(hc, name, lambda *a, real=real, name=name: (traces.append(name), real(*a))[1])
    monkeypatch.setattr(hc, "_TRACED", {})
    monkeypatch.setattr(hc, "_mix_impl", lambda x: "interpret")
    mod, params, x, y, probes = operands(tokens=256, hidden=128)

    def two_blocks(p, x, y):
        for name in ("h_0", "h_2"):
            with jax.named_scope(name):
                x = sub_block(mod)(p, x, y)["x_out"]
        return jnp.sum(x.astype(F32) * probes[1])

    scopes = list(_pallas_scopes(jax.make_jaxpr(two_blocks)(params, x, y).jaxpr))
    assert sorted(traces) == ["_read_fwd_kernel", "_write_fwd_kernel"]
    assert [s.split("/", 1)[0] for s in scopes] == ["h_0", "h_0", "h_2", "h_2"], scopes
    assert all(  # (the kernel's name twice: the scope that names the device op, and ``pallas_call(name=)``'s own)
        s.endswith(f"{span}/{name}/{name}")
        for s, (span, name) in zip(scopes, 2 * [("mhc.pre", "mhc_read_fwd"), ("mhc.post", "mhc_write_fwd")])), scopes
    traces.clear()
    text = str(jax.make_jaxpr(jax.grad(two_blocks, argnums=(0, 1, 2)))(params, x, y))
    assert sorted(traces) == ["_read_bwd_kernel", "_write_bwd_kernel"]
    assert text.count("pallas_call") == 8
