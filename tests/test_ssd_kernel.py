"""The fused SSD scan pair (``models/ssm.py:ssd_scan``), interpreted on the CPU.

The kernels' arithmetic is ``ssd_chunked``'s to the dtype, so they are held
against it AND against the benchmark reference's token-by-token recurrence
(``benchmarks/reference/nemotron_h.py``, which imports nothing of the program):
values and ``jax.grad`` for all five arguments, at the hybrid cell's head
layout cut small (2 groups x 2 heads of 64, state 128, chunk 128). What the
interpreter cannot see (Mosaic's tiling, scoped VMEM) is compiled for the
described v5e in ``tests/test_flash_compile_tpu.py``; what only the chip shows
is ``tests/kernels_tpu_child.py``'s group ``ssd``.
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

from reference import nemotron_h as ref  # noqa: E402
from consensusml_tpu.models import ssm  # noqa: E402
from consensusml_tpu.obs import get_registry  # noqa: E402

G, R, P, N, CHUNK = 2, 2, 64, 128, 128  # the cell's 8 groups x 8 heads, cut to 2 x 2
H = G * R
F32 = jnp.float32


def operands(seq, dtype, seed=0, rows=1):
    """Sizes as the mixer hands them over: ``dt`` a softplus, ``A`` negative."""
    k = jax.random.split(jax.random.key(seed + seq), 6)
    x = jax.random.normal(k[0], (rows, seq, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, seq, H)) - 3.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)) * 0.5)
    bm = (jax.random.normal(k[3], (rows, seq, G, N)) * 0.3).astype(dtype)
    cm = (jax.random.normal(k[4], (rows, seq, G, N)) * 0.3).astype(dtype)
    probe = jax.random.normal(k[5], (rows, seq, H, P))
    return (x, dt, a, bm, cm), probe


def kernels(x, dt, a, bm, cm):
    return ssm.ssd_scan(x, dt, a, bm, cm, chunk=CHUNK, interpret=True)


def chunked(x, dt, a, bm, cm):
    return ssm.ssd_chunked(x, dt, a, bm, cm, chunk=CHUNK)


def stepwise(x, dt, a, bm, cm, keep=None):
    """The reference, float32, fed the operands as they are rounded."""
    spread = lambda v: jnp.repeat(v.astype(F32), R, axis=2)
    keep = jnp.ones((x.shape[1],)) if keep is None else keep
    return ref.recurrence(x.astype(F32), dt, jnp.exp(dt * a), spread(bm), spread(cm), keep)


def grads_of(f, probe):
    return jax.jit(jax.grad(lambda *args: jnp.sum(f(*args) * probe), argnums=(0, 1, 2, 3, 4)))


def rel(a, b) -> float:
    a, b = a.astype(F32), b.astype(F32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


# -- 1. the kernels are the chunked scan, and the recurrence ---------------------

# per dtype: (values, gradients) against ssd_chunked, then against the recurrence.
# float32: the same sums in another order. bfloat16: the forward kernel rounds
# where ssd_chunked rounds, the backward kernel rounds its MXU operands where
# XLA's default precision does and keeps cotangents float32 where autodiff
# rounds them to bfloat16, so the gradients agree to bfloat16's 2^-8 and no closer.
_LIMITS = {
    jnp.float32: ((1e-5, 2e-5), (2e-4, 2e-4)),
    jnp.bfloat16: ((1e-4, 1.5e-2), (1e-2, 2e-2)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("seq", [128, 384, 300], ids=["one_chunk", "three_chunks", "ragged_tail"])
def test_kernels_match_chunked_scan_and_recurrence(seq, dtype):
    args, probe = operands(seq, dtype)
    got, got_grads = jax.jit(kernels)(*args), grads_of(kernels, probe)(*args)
    assert got.dtype == F32 and got.shape == args[0].shape
    assert [g.dtype for g in got_grads] == [a.dtype for a in args]
    for other, (y_limit, grad_limit) in zip((chunked, stepwise), _LIMITS[dtype]):
        assert rel(got, jax.jit(other)(*args)) < y_limit, other.__name__
        for name, g, want in zip(("x", "dt", "a", "b", "c"), got_grads, grads_of(other, probe)(*args)):
            assert rel(g, want) < grad_limit, (other.__name__, name)


def test_tail_steps_leave_the_state_alone():
    """A length that is no multiple of the chunk is padded with ``dt = 0``
    steps: the first 300 of 384 tokens read the same as the 300 alone."""
    args, _ = operands(384, jnp.float32, seed=3)
    short = tuple(v[:, :300] if v.ndim > 1 else v for v in args)
    np.testing.assert_allclose(
        jax.jit(kernels)(*short), jax.jit(kernels)(*args)[:, :300], rtol=1e-5, atol=1e-6)


# -- 2. both backends take them --------------------------------------------------


def _two_workers(seq=256):
    one, probe = operands(seq, jnp.bfloat16, seed=1)
    two, _ = operands(seq, jnp.bfloat16, seed=2)
    return tuple(jnp.stack([u, v]) for u, v in zip(one, two)), probe


def _grads(f, probe):
    return jax.grad(lambda *args: jnp.sum(f(*args) * probe), argnums=(0, 1, 2, 3, 4))


def _assert_same(got, want, limit):
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        assert g.shape == w.shape and rel(g, w) < limit, name


def test_under_vmap_over_two_workers():
    """The stacked backend: ``vmap`` prepends the worker axis to the grid; each
    worker's state starts at zero and its gradients are its own."""
    stacked, probe = _two_workers()
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

    def mapped(f):
        inner = lambda *args: tuple(g[None] for g in _grads(f, probe)(*(v[0] for v in args)))
        return jax.jit(jax.shard_map(
            inner, mesh=jax.make_mesh((2,), ("w",)), in_specs=P("w"), out_specs=P("w")))

    _assert_same(mapped(kernels)(*stacked), mapped(chunked)(*stacked), 1.5e-2)


# -- 3. a planted fault fails --------------------------------------------------


def test_state_not_carried_fails_as_no_state_carry_does(monkeypatch):
    """``test_planted_fault_fails_the_comparison`` zeroes ``carried_states`` on
    the chunked path; on this path the chunks read the carried state through
    ``_carried``. Zeroed, the scan must read far off the recurrence, values and
    gradients, and agree with the reference that has the same fault planted
    (``no_state_carry``: the state reset at every chunk's first token)."""
    args, probe = operands(384, jnp.float32, seed=5)
    want, want_grads = jax.jit(stepwise)(*args), grads_of(stepwise, probe)(*args)
    assert rel(jax.jit(kernels)(*args), want) < 2e-4
    monkeypatch.setattr(ssm, "_carried", jnp.zeros_like)
    monkeypatch.setattr(ssm, "_TRACED", {})
    faulted = lambda *a: kernels(*a)  # a function jit has not seen
    got, got_grads = jax.jit(faulted)(*args), grads_of(faulted, probe)(*args)
    assert rel(got, want) > 100 * 2e-4
    assert max(rel(g, w) for g, w in zip(got_grads, want_grads)) > 100 * 2e-4
    keep = (jnp.arange(384) % CHUNK != 0).astype(F32)
    twin = lambda *a: stepwise(*a, keep=keep)
    assert rel(got, jax.jit(twin)(*args)) < 2e-4
    _assert_same(got_grads, grads_of(twin, probe)(*args), 2e-4)


# -- 4. which path runs is observed ----------------------------------------------


@pytest.mark.parametrize(
    "tpu, chunk, p, n, r, want",
    [
        (True, 128, 64, 128, 8, "pallas"),  # the hybrid cell's mixer
        (False, 128, 64, 128, 8, "xla"),  # the same off a TPU
        (True, 128, 64, 128, 2, "pallas"),  # this file's cut
        (True, 256, 128, 256, 1, "pallas"),  # a 128-wide head fills its lane tile alone
        (True, 8, 8, 16, 2, "xla"),  # nemotron_h_tiny and the smoke recipe's 8-wide shapes
        (True, 64, 64, 128, 8, "xla"),  # a chunk that is no multiple of the lanes
        (True, 128, 64, 64, 8, "xla"),  # nor a state
        (True, 128, 64, 128, 1, "xla"),  # one 64-wide head a group half-fills a lane tile
        (True, 128, 48, 128, 8, "xla"),  # a head width the lanes do not divide by
    ],
)
def test_path_is_observed_from_platform_and_shapes(monkeypatch, tpu, chunk, p, n, r, want):
    monkeypatch.setattr(ssm, "on_tpu", lambda: tpu)
    assert ssm._scan_impl(chunk, p, n, r) == want


def _mixer(dtype=jnp.float32, **sizes):
    cfg = ssm.Mamba2Config(
        **{"hidden": 32, "heads": H, "head_dim": P, "groups": G, "state": N, "chunk": CHUNK,
           "dtype": dtype, **sizes})
    mixer = ssm.Mamba2Mixer(cfg, layer=7)
    u = jax.random.normal(jax.random.key(0), (1, 200, cfg.hidden))
    return mixer, mixer.init(jax.random.key(1), u)["params"], u


@pytest.mark.parametrize("impl, label", [("interpret", "kernel"), ("xla", "xla")])
def test_counter_says_which_path_a_traced_mixer_took(monkeypatch, impl, label):
    monkeypatch.setattr(ssm, "_scan_impl", lambda *sizes: impl)
    count = lambda l: get_registry().counter(
        "consensusml_ssm_scan_impl_total", labels={"layer": "7", "impl": l}).value
    chunks = get_registry().counter("consensusml_ssm_chunks_total", labels={"layer": "7"})
    other = "xla" if label == "kernel" else "kernel"
    mixer, params, u = _mixer()  # its init traces the mixer too
    before = count(label), count(other), chunks.value
    jax.eval_shape(lambda p: mixer.apply({"params": p}, u), params)
    assert (count(label), count(other), chunks.value) == (before[0] + 1, before[1], before[2] + 2)


def test_tiny_shapes_take_xlas_scan_on_a_tpu_too(monkeypatch):
    """The smoke-scale recipe's mixer on a TPU: no kernel is traced."""
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    mixer, params, u = _mixer(heads=4, head_dim=8, groups=2, state=16, chunk=8)
    jaxpr = jax.make_jaxpr(lambda p: mixer.apply({"params": p}, u))(params)
    assert "pallas_call" not in str(jaxpr)


# -- 5. the mixer around them ----------------------------------------------------


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)], ids=["float32", "bfloat16"])
def test_mixer_reads_the_same_on_either_path(monkeypatch, dtype, limit):
    """Output, ``scan_rms`` (the ``D x`` skip and the mean square read the same
    float32 ``S C + D x``) and every parameter's gradient."""
    mixer, params, u = _mixer(dtype)

    def run(p):
        def loss(p):
            out, scan_rms = mixer.apply({"params": p}, u)
            return jnp.sum(jnp.sin(out.astype(F32))), (out, scan_rms)

        return jax.value_and_grad(loss, has_aux=True)(p)

    (_, (want, want_rms)), want_grads = jax.jit(run)(params)
    monkeypatch.setattr(ssm, "_scan_impl", lambda *sizes: "interpret")
    (_, (got, got_rms)), got_grads = jax.jit(run)(params)
    assert rel(got, want) < limit and rel(got_rms, want_rms) < limit
    for name in want_grads:
        assert rel(got_grads[name], want_grads[name]) < limit, name


def test_one_trace_a_kernel_under_the_callers_scope(monkeypatch):
    """Two ``M`` blocks, forward and backward: one trace of the forward kernel
    a form (with and without the saved states) and one of the backward kernel
    serve every call, and every call's equation sits under its own block's
    name, the span ``ssm.scan`` and the kernel's own scope, which names the
    device op (``h_<i>`` alone would count it as flash attention)."""
    traces = []
    for name in ("_ssd_fwd_kernel", "_ssd_bwd_kernel"):
        real = getattr(ssm, name)
        monkeypatch.setattr(
            ssm, name, lambda *a, real=real, name=name: (traces.append(name), real(*a))[1])
    monkeypatch.setattr(ssm, "_TRACED", {})
    monkeypatch.setattr(ssm, "_scan_impl", lambda *sizes: "interpret")
    mixer, params, u = _mixer()

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
    assert traces == ["_ssd_fwd_kernel"]
    assert len(scopes) == 2 and all(
        s.startswith(f"h_{b}/") and s.endswith("ssm.scan/ssd_fwd") for s, b in zip(scopes, (0, 2))), scopes
    traces.clear()
    text = str(jax.make_jaxpr(jax.grad(two_blocks))(params, u))
    assert sorted(traces) == ["_ssd_bwd_kernel", "_ssd_fwd_kernel"]  # forward anew: it saves the states
    assert text.count("pallas_call") == 4
