"""The fused gated-delta-rule pair (``models/gated_delta.py:gated_delta_scan``),
interpreted on the CPU.

The kernels' arithmetic is ``gated_delta_chunked``'s to the dtype, so they are
held against it AND against the benchmark reference's token-by-token recurrence
(``benchmarks/reference/qwen3_next.py``, which imports nothing of the program):
values and ``jax.grad`` for all five arguments, at the delta cell's head layout
cut small (2 key heads x 2 value heads of 128 x 128, chunks of 64 and of 16).
What the interpreter cannot see (Mosaic's tiling, scoped VMEM) is compiled for
the described v5e in ``tests/test_flash_compile_tpu.py``; what only the chip
shows is ``tests/kernels_tpu_child.py``'s group ``gdn``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if _BENCH not in sys.path:
    sys.path.append(_BENCH)

from reference import qwen3_next as ref  # noqa: E402
from consensusml_tpu.models import gated_delta as gd  # noqa: E402
from consensusml_tpu.obs import get_registry  # noqa: E402
from test_ssd_kernel import _grads, grads_of, rel  # noqa: E402  (the twin's helpers: jitted gradients of a probe, relative L2)

KH, R, DK, DV = 2, 2, 128, 128  # the cell's 16 key heads x 2 value heads, cut to 2 x 2
VH = KH * R
F32 = jnp.float32
NAMES = ("q", "k", "v", "g", "beta")


def operands(seq, dtype, decay=1.0, seed=0, rows=1):
    """Sizes as the mixer hands them over: ``q`` and ``k`` normalised, a key head each."""
    key = jax.random.split(jax.random.key(seed + seq), 6)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q = (unit(jax.random.normal(key[0], (rows, seq, KH, DK))) * DK**-0.5).astype(dtype)
    k = unit(jax.random.normal(key[1], (rows, seq, KH, DK))).astype(dtype)
    v = jax.random.normal(key[2], (rows, seq, VH, DV)).astype(dtype)
    g = -decay * jax.nn.softplus(jax.random.normal(key[3], (rows, seq, VH)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (rows, seq, VH)))
    probe = jax.random.normal(key[5], (rows, seq, VH, DV))
    return (q, k, v, g, beta), probe


def _spread(x):
    return jnp.repeat(x, R, axis=2)


def paths(chunk):
    """The kernels, XLA's chunked rule and the reference's recurrence (float32,
    fed the operands as they are rounded), each over a key head's ``q`` and ``k``."""
    kernels = lambda *a: gd.gated_delta_scan(*a, chunk=chunk, interpret=True)
    chunked = lambda q, k, *rest: gd.gated_delta_chunked(_spread(q), _spread(k), *rest, chunk=chunk)

    def stepwise(q, k, v, g, beta, keep=None):
        keep = jnp.ones((q.shape[1],)) if keep is None else keep
        return ref.delta_rule(_spread(q).astype(F32), _spread(k).astype(F32), v.astype(F32), g, beta, keep)

    return kernels, chunked, stepwise


# -- 1. the kernels are the chunked rule, and the recurrence ----------------------

# per dtype: (values, gradients) against gated_delta_chunked, then against the
# recurrence. float32: the same sums in another order, the inverse by blocks and
# not by the series. bfloat16: the forward kernel rounds where the chunked rule
# rounds (bit for bit on this backend); the backward kernel rounds its MXU
# operands where XLA's default precision does and keeps cotangents float32 where
# autodiff rounds them to bfloat16, so the gradients agree to bfloat16's 2^-8.
_LIMITS = {
    jnp.float32: ((1e-5, 3e-5), (2e-4, 4e-4)),
    jnp.bfloat16: ((1e-4, 1.5e-2), (1e-2, 2e-2)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("decay", [0.01, 3.0], ids=["slow_decay", "fast_decay"])
@pytest.mark.parametrize(
    "chunk, seq", [(64, 64), (64, 192), (64, 150), (16, 300)],
    ids=["one_chunk", "three_chunks", "ragged_tail", "three_grid_steps"])
def test_kernels_match_chunked_rule_and_recurrence(chunk, seq, decay, dtype):
    args, probe = operands(seq, dtype, decay)
    kernels, chunked, stepwise = paths(chunk)
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(kernels)(*args), grads_of(kernels, probe)(*args)
        assert got.dtype == F32 and got.shape == args[2].shape
        assert [g.dtype for g in got_grads] == [a.dtype for a in args]
        assert [g.shape for g in got_grads] == [a.shape for a in args]
        for other, (y_limit, grad_limit) in zip((chunked, stepwise), _LIMITS[dtype]):
            assert rel(got, jax.jit(other)(*args)) < y_limit, other.__name__
            for name, g, want in zip(NAMES, got_grads, grads_of(other, probe)(*args)):
                assert rel(g, want) < grad_limit, (other.__name__, name)


def test_tail_tokens_leave_the_state_alone():
    """A length that is no multiple of the chunk (or of a grid step's chunks) is
    padded with tokens of ``beta = 0`` and ``g = 0``: the first 150 of 192 tokens
    read the same as the 150 alone."""
    args, _ = operands(192, jnp.float32, seed=3)
    kernels = paths(64)[0]
    np.testing.assert_allclose(
        jax.jit(kernels)(*(a[:, :150] for a in args)), jax.jit(kernels)(*args)[:, :150], rtol=1e-5, atol=1e-6)


# -- 2. both backends take them --------------------------------------------------


def _two_workers(seq=128):
    one, probe = operands(seq, jnp.bfloat16, seed=1)
    two, _ = operands(seq, jnp.bfloat16, seed=2)
    return tuple(jnp.stack([u, v]) for u, v in zip(one, two)), probe


def _assert_same(got, want, limit):
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and rel(g, w) < limit, name


def test_under_vmap_over_two_workers():
    """The stacked backend: ``vmap`` prepends the worker axis to the grid; each
    worker's state starts at zero and its gradients are its own."""
    stacked, probe = _two_workers()
    kernels, chunked, _ = paths(64)
    got = jax.jit(jax.vmap(_grads(kernels, probe)))(*stacked)
    alone = [jax.jit(_grads(kernels, probe))(*(v[w] for v in stacked)) for w in range(2)]
    _assert_same(got, [jnp.stack(pair) for pair in zip(*alone)], 1e-6)
    _assert_same(got, jax.jit(jax.vmap(_grads(chunked, probe)))(*stacked), 1.5e-2)


def test_inside_a_checked_shard_map():
    """The collective backend: a ``shard_map`` with the check of varying axes
    on. The kernels' ``out_shape`` says where they vary, the custom VJP's
    cotangents vary as the primals do."""
    from jax.sharding import PartitionSpec as P

    stacked, probe = _two_workers()
    kernels, chunked, _ = paths(64)

    def mapped(f):
        inner = lambda *args: tuple(g[None] for g in _grads(f, probe)(*(v[0] for v in args)))
        return jax.jit(jax.shard_map(
            inner, mesh=jax.make_mesh((2,), ("w",)), in_specs=P("w"), out_specs=P("w")))

    _assert_same(mapped(kernels)(*stacked), mapped(chunked)(*stacked), 1.5e-2)


# -- 3. a planted fault fails --------------------------------------------------


@pytest.mark.parametrize("zeroed", [jnp.zeros_like, lambda state: 0.0 * state], ids=["zeros_like", "times_zero"])
def test_state_not_carried_fails_as_no_state_carry_does(monkeypatch, zeroed):
    """``test_planted_fault_fails_the_comparison`` and the benchmark's rehearsal
    zero ``_carried`` on the chunked path, each in its own words; on this path
    the chunks read the carried state through the same function (no cache of
    traces to clear: it is part of a trace's key). Zeroed, the rule must read far
    off the recurrence, values and gradients, and agree with the reference that
    has the same fault planted (``no_state_carry``: the state reset at every
    chunk's first token)."""
    chunk, seq = 16, 304  # 19 chunks: three grid steps
    args, probe = operands(seq, jnp.float32, decay=0.05, seed=5)
    kernels, _, stepwise = paths(chunk)
    want, want_grads = jax.jit(stepwise)(*args), grads_of(stepwise, probe)(*args)
    assert rel(jax.jit(kernels)(*args), want) < 2e-4
    monkeypatch.setattr(gd, "_carried", zeroed)
    faulted = lambda *a: kernels(*a)  # a function jit has not seen
    got, got_grads = jax.jit(faulted)(*args), grads_of(faulted, probe)(*args)
    assert rel(got, want) > 100 * 2e-4
    assert max(rel(g, w) for g, w in zip(got_grads, want_grads)) > 100 * 2e-4
    keep = (jnp.arange(seq) % chunk != 0).astype(F32)
    twin = lambda *a: stepwise(*a, keep=keep)
    assert rel(got, jax.jit(twin)(*args)) < 2e-4
    _assert_same(got_grads, grads_of(twin, probe)(*args), 4e-4)


# -- 4. which path runs is observed ----------------------------------------------


@pytest.mark.parametrize(
    "tpu, chunk, dk, dv, want",
    [
        (True, 64, 128, 128, "pallas"),  # the delta cell's mixer
        (False, 64, 128, 128, "xla"),  # the same off a TPU
        (True, 16, 128, 256, "pallas"),  # the smallest chunk a bfloat16 tile takes, a wider value head
        (True, 8, 8, 8, "xla"),  # qwen3_next_tiny and the smoke recipe's 8-wide heads
        (True, 64, 64, 128, "xla"),  # a key width that is no multiple of the lanes
        (True, 64, 128, 192, "xla"),  # nor a value width
        (True, 24, 128, 128, "xla"),  # a chunk that is no multiple of the bfloat16 sublane tile
    ],
)
def test_path_is_observed_from_platform_and_shapes(monkeypatch, tpu, chunk, dk, dv, want):
    monkeypatch.setattr(gd, "on_tpu", lambda: tpu)
    assert gd._scan_impl(chunk, dk, dv) == want


def _mixer(dtype=jnp.float32, seq=200, **sizes):
    cfg = gd.GatedDeltaConfig(
        **{"hidden": 32, "key_heads": KH, "value_heads": VH, "key_dim": DK, "value_dim": DV, "chunk": 64,
           "dtype": dtype, **sizes})
    mixer = gd.GatedDeltaNetMixer(cfg, layer=7)
    u = jax.random.normal(jax.random.key(0), (1, seq, cfg.hidden))
    return mixer, mixer.init(jax.random.key(1), u)["params"], u


@pytest.mark.parametrize("impl, label", [("interpret", "kernel"), ("xla", "xla")])
def test_counter_says_which_path_a_traced_mixer_took(monkeypatch, impl, label):
    monkeypatch.setattr(gd, "_scan_impl", lambda *sizes: impl)
    count = lambda l: get_registry().counter(
        "consensusml_gdn_scan_impl_total", labels={"layer": "7", "impl": l}).value
    chunks = get_registry().counter("consensusml_gdn_chunks_total", labels={"layer": "7"})
    other = "xla" if label == "kernel" else "kernel"
    mixer, params, u = _mixer()  # its init traces the mixer too
    before = count(label), count(other), chunks.value
    jax.eval_shape(lambda p: mixer.apply({"params": p}, u), params)
    assert (count(label), count(other), chunks.value) == (before[0] + 1, before[1], before[2] + 4)


def test_tiny_shapes_take_xlas_rule_on_a_tpu_too(monkeypatch):
    """The smoke-scale recipe's mixer on a TPU: no kernel is traced."""
    monkeypatch.setattr(gd, "on_tpu", lambda: True)
    mixer, params, u = _mixer(key_heads=2, value_heads=4, key_dim=8, value_dim=8, chunk=8)
    jaxpr = jax.make_jaxpr(lambda p: mixer.apply({"params": p}, u))(params)
    assert "pallas_call" not in str(jaxpr)


# -- 5. the mixer around them ----------------------------------------------------


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)], ids=["float32", "bfloat16"])
def test_mixer_reads_the_same_on_either_path(monkeypatch, dtype, limit):
    """Output, ``out_rms`` (the mean square of the rule's float32 ``o``) and
    every parameter's gradient; on XLA's path ``q`` and ``k`` are repeated over
    a key head's value heads, on the kernels' the BlockSpec reads the key head."""
    mixer, params, u = _mixer(dtype)

    def run(p):
        def loss(p):
            out, out_rms = mixer.apply({"params": p}, u)
            return jnp.sum(jnp.sin(out.astype(F32))), (out, out_rms)

        return jax.value_and_grad(loss, has_aux=True)(p)

    (_, (want, want_rms)), want_grads = jax.jit(run)(params)
    monkeypatch.setattr(gd, "_scan_impl", lambda *sizes: "interpret")
    (_, (got, got_rms)), got_grads = jax.jit(run)(params)
    assert rel(got, want) < limit and rel(got_rms, want_rms) < limit
    for name in want_grads:
        assert rel(got_grads[name], want_grads[name]) < limit, name


def test_one_trace_a_kernel_under_the_callers_scope(monkeypatch):
    """Two ``G`` blocks, forward and backward: one trace of the forward kernel
    a form (with and without the saved states) and one of the backward kernel
    serve every call, and every call's equation sits under its own block's
    name, the span ``gdn.scan`` and the kernel's own scope, which names the
    device op (``h_<i>`` alone would count it as flash attention)."""
    traces = []
    for name in ("_gdn_fwd_kernel", "_gdn_bwd_kernel"):
        real = getattr(gd, name)
        monkeypatch.setattr(
            gd, name, lambda *a, real=real, name=name: (traces.append(name), real(*a))[1])
    monkeypatch.setattr(gd, "_TRACED", {})
    monkeypatch.setattr(gd, "_scan_impl", lambda *sizes: "interpret")
    mixer, params, u = _mixer(seq=64)

    def two_blocks(p, u):
        for name in ("h_0", "h_2"):
            with jax.named_scope(name):
                u = u + mixer.apply({"params": p}, u)[0]
        return jnp.sum(u)

    def pallas_scopes(jaxpr, outer=""):
        for e in jaxpr.eqns:
            here = f"{outer}/{e.source_info.name_stack}".strip("/")
            if e.primitive.name == "pallas_call":
                yield here
            for sub in e.params.values():  # a custom VJP's call holds the kernel
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from pallas_scopes(sub, here)

    scopes = list(pallas_scopes(jax.make_jaxpr(two_blocks)(params, u).jaxpr))
    assert traces == ["_gdn_fwd_kernel"]
    assert len(scopes) == 2 and all(
        s.startswith(f"h_{b}/") and s.endswith("gdn.scan/gdn_fwd") for s, b in zip(scopes, (0, 2))), scopes
    traces.clear()
    text = str(jax.make_jaxpr(jax.grad(two_blocks))(params, u))
    assert sorted(traces) == ["_gdn_bwd_kernel", "_gdn_fwd_kernel"]  # forward anew: it saves the states
    assert text.count("pallas_call") == 4
