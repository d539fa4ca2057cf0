"""Pallas flash-attention kernel parity (interpreter mode on the CPU
mesh; the compiled-on-TPU check lives in test_kernels_tpu.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensusml_tpu.models.attention import dot_product_attention
from consensusml_tpu.models import flash_attention as fa_mod
from consensusml_tpu.models.flash_attention import flash_attention


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    # interpreter mode is slow: shrink the (TPU-tuned 512) blocks so
    # multi-block paths are exercised at test-sized sequences
    monkeypatch.setattr(fa_mod, "_BQ", 64)
    monkeypatch.setattr(fa_mod, "_BK", 64)


@pytest.fixture(params=["unrolled", "looped"])
def schedule(request, monkeypatch):
    """Both tile schedules on the same cases: a head's tiles as
    straight-line code (what these sizes get), or one program per block
    looping over its tiles (what a long sequence gets)."""
    if request.param == "looped":
        monkeypatch.setattr(fa_mod, "_UNROLL_TILES", 0)
    return request.param


def _qkv(rng, b, s, h, d, dtype=jnp.float32):
    return tuple(
        jnp.asarray(rng.normal(size=(b, s, h, d)), dtype) for _ in range(3)
    )


# float32 inputs are cast nowhere, so their tolerances are the old ones;
# bfloat16 inputs are the MXU operands as training hands them over, held to
# the dense path fed the same bfloat16 tensors (both round p to bfloat16)
_FWD_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
_GRAD_TOL = {jnp.float32: 2e-4, jnp.bfloat16: 4e-2}


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [128, 100])  # exact blocks and padded tail
def test_flash_forward_matches_dense(causal, s, dtype, schedule):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, 2, s, 2, 64, dtype)
    want = dot_product_attention(q, k, v, causal=causal, dtype=dtype, impl="dense")
    got = flash_attention(q, k, v, causal=causal, dtype=dtype, interpret=True)
    assert got.dtype == dtype
    tol = _FWD_TOL[dtype]
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=tol, atol=tol)


def _grads(fn, q, k, v):
    loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _assert_grads_match_dense(q, k, v, causal, tol):
    dtype = q.dtype
    gf = _grads(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, dtype=dtype, interpret=True
        ),
        q, k, v,
    )
    gd = _grads(
        lambda q, k, v: dot_product_attention(
            q, k, v, causal=causal, dtype=dtype, impl="dense"
        ),
        q, k, v,
    )
    for name, a, b in zip("qkv", gf, gd):
        assert a.dtype == dtype
        scale = max(1.0, float(np.max(np.abs(_f32(b))))) if dtype != jnp.float32 else 1.0
        np.testing.assert_allclose(
            _f32(a) / scale, _f32(b) / scale, rtol=tol, atol=tol,
            err_msg=f"d{name}",
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_dense(causal, dtype, schedule):
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, 1, 128, 2, 64, dtype)
    _assert_grads_match_dense(q, k, v, causal, _GRAD_TOL[dtype])


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "d_k, d_v, scale",
    [(64, 64, None), (64, 64, 0.2), (192, 128, 192**-0.5 * 1.4159**2), (24, 40, 0.3)],
    ids=["64x64", "64x64_scaled", "192x128_latent", "24x40"],
)
def test_key_and_value_widths_and_an_explicit_scale_match_dense(d_k, d_v, scale, causal, schedule):
    """Keys (and queries) one width, values another, the scores times a given
    scale: latent attention's 192 / 128 at its yarn scale beside the square
    widths; forward and all three gradients, s = a block and a padded tail."""
    rng = np.random.default_rng(d_k + d_v)
    s = 64 + 37
    q, k = (jnp.asarray(rng.normal(size=(1, s, 2, d_k)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, s, 2, d_v)), jnp.float32)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, dtype=jnp.float32, interpret=True, scale=scale)
    dense = lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal, dtype=jnp.float32, impl="dense", scale=scale)
    got, want = flash(q, k, v), dense(q, k, v)
    assert got.shape == want.shape == (1, s, 2, d_v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    if scale is not None:  # the scale is used, not the default d_k^-1/2
        plain = dot_product_attention(q, k, v, causal=causal, dtype=jnp.float32, impl="dense")
        assert float(jnp.abs(plain - want).max()) > 1e-3
    for name, a, b in zip("qkv", _grads(flash, q, k, v), _grads(dense, q, k, v)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_blockwise_takes_the_widths_and_the_scale_too():
    from consensusml_tpu.models.attention import blockwise_attention

    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.normal(size=(2, 70, 2, 24)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 70, 2, 16)), jnp.float32)
    want = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense", scale=0.3)
    got = blockwise_attention(q, k, v, causal=True, dtype=jnp.float32, block_kv=32, scale=0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    grads = lambda f: _grads(lambda q, k, v: f(q, k, v), q, k, v)
    for a, b in zip(
        grads(lambda q, k, v: blockwise_attention(q, k, v, causal=True, dtype=jnp.float32, block_kv=32, scale=0.3)),
        grads(lambda q, k, v: dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense", scale=0.3)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_plain_masked_padded_and_skipped_tiles_in_one_call(schedule):
    """s = 3 tiles + a tail, causal: row 2 of the 4 x 4 grid runs two plain
    tiles, its diagonal one and skips one; the last row's diagonal tile
    holds the padded keys."""
    s = 3 * 64 + 20
    plan = fa_mod.tile_plan(256, s, 64, 64, True)
    assert plan["fwd"] == {"plain": 6, "masked": 4, "skipped": 6}
    assert plan["dkv"] == plan["fwd"]
    rng = np.random.default_rng(9)
    q, k, v = _qkv(rng, 1, s, 2, 64)
    want = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense")
    got = flash_attention(q, k, v, causal=True, dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    _assert_grads_match_dense(q, k, v, True, 2e-4)


@pytest.mark.parametrize(
    "block, computed, tiles, masked", [(512, 3, 4, 2), (256, 10, 16, 4), (128, 36, 64, 8)]
)
def test_tile_plan_counts_the_causal_triangle(block, computed, tiles, masked):
    plan = fa_mod.tile_plan(1024, 1024, block, block, True)
    for kernel in ("fwd", "dq", "dkv"):
        kinds = plan[kernel]
        assert sum(kinds.values()) == tiles, kernel
        assert kinds["plain"] + kinds["masked"] == computed, kernel
        assert kinds["masked"] == masked, kernel


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bq, bk", [(64, 64), (32, 64), (64, 32), (48, 16)])
@pytest.mark.parametrize("s_real", [192, 150, 97])
def test_tile_runs_agree_with_the_positions_they_stand_for(causal, bq, bk, s_real):
    """Rows (``_kv_runs``: forward, dq) and columns (``_q_runs``: dk/dv)
    sort every tile as its positions say: skipped iff wholly above the
    diagonal, masked iff the diagonal crosses it or it holds padded keys."""
    s_pad = 192
    nq, nk = s_pad // bq, s_pad // bk

    def kind(i, j):
        q_lo, q_hi = i * bq, (i + 1) * bq - 1
        k_lo, k_hi = j * bk, (j + 1) * bk - 1
        if causal and k_lo > q_hi:
            return "skipped"
        return "masked" if (causal and k_hi > q_lo) or k_hi >= s_real else "plain"

    for i in range(nq):
        plain, end = fa_mod._kv_runs(np, i, bq, bk, nk, s_real, causal, False)
        for j in range(nk):
            got = "plain" if j < plain else "masked" if j < end else "skipped"
            # a row may mask a padded tile that lies above the diagonal
            # (runs are contiguous), never the other way round
            assert got == kind(i, j) or (got, kind(i, j)) == ("skipped", "masked"), (i, j)
    for j in range(nk):
        start, masked_end = fa_mod._q_runs(np, j, bq, bk, nq, s_real, causal, False)
        for i in range(nq):
            got = "skipped" if i < start else "masked" if i < masked_end else "plain"
            assert got == kind(i, j), (i, j)


def test_a_kv_mask_sends_every_tile_through_the_masked_body():
    plan = fa_mod.tile_plan(256, 256, 64, 64, False, has_mask=True)
    assert plan["fwd"] == plan["dkv"] == {"plain": 0, "masked": 16, "skipped": 0}
    assert fa_mod.tile_plan(256, 256, 64, 64, False)["dq"]["plain"] == 16


def test_tiles_counter_follows_the_plan(schedule):
    from consensusml_tpu.obs import get_registry

    def read():
        return {
            (kernel, kind): get_registry().counter(
                "consensusml_flash_tiles_total",
                labels={"kernel": kernel, "kind": kind},
            ).value
            for kernel in ("fwd", "dq", "dkv")
            for kind in ("plain", "masked", "skipped")
        }

    rng = np.random.default_rng(10)
    q, k, v = _qkv(rng, 1, 192, 2, 64)
    before = read()
    _grads(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, dtype=jnp.float32, interpret=True
        ),
        q, k, v,
    )
    after = read()
    bq, bk = fa_mod._tiles(192, 64)
    plan = fa_mod.tile_plan(192, 192, bq, bk, True)
    for kernel, kinds in plan.items():
        assert kinds == {"plain": 3, "masked": 3, "skipped": 3}
        if schedule == "looped":  # one body: every computed tile masks
            kinds.update(plain=0, masked=6)
    for (kernel, kind), n in after.items():
        assert n - before[(kernel, kind)] == 2 * plan[kernel][kind], (kernel, kind)


@pytest.mark.parametrize("s_pad, tiles, unrolled", [
    (1024, (256, 256), True), (1536, (512, 512), True), (2048, (512, 512), True),
    (2560, (512, 512), False), (8192, (512, 512), False),
])
def test_tiles_and_schedule_follow_the_padded_length(monkeypatch, s_pad, tiles, unrolled):
    """At the shipped limits (512): half tiles while they leave four blocks
    a side, whole ones beyond; straight-line code while the head's grid
    has at most ``_UNROLL_TILES`` tiles (2560 x 128-wide heads ran out of
    scoped VMEM unrolled on the described v5e)."""
    monkeypatch.setattr(fa_mod, "_BQ", 512)
    monkeypatch.setattr(fa_mod, "_BK", 512)
    assert fa_mod._tiles(s_pad, 64) == tiles
    got = fa_mod._schedule("fwd", True, 1, s_pad, s_pad, 64, True, False)
    assert got == (*tiles, unrolled)
    # dynamic offsets (the ring path): always the loop, at the largest tile
    got = fa_mod._schedule("fwd", False, 1, s_pad, s_pad, 64, True, False)
    assert got == (512, 512, False)


def test_flash_rejects_cross_attention():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    with pytest.raises(ValueError, match="k as wide as q"):
        flash_attention(q, k, q, causal=False)
    with pytest.raises(ValueError, match="v's rows and heads q's"):  # the error names the tensor at fault
        flash_attention(q, q, k, causal=False)
    narrow = q[..., :32]
    with pytest.raises(ValueError, match="k as wide as q"):
        flash_attention(q, narrow, q, causal=False)
    assert flash_attention(q, q, narrow, causal=False, interpret=True).shape == narrow.shape  # v's width is free


def test_auto_dispatch_never_picks_flash_off_tpu():
    # the CPU test mesh must route long sequences to blockwise, not the
    # TPU kernel
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 1024, 1, 64)), jnp.bfloat16)
    auto = dot_product_attention(q, q, q, causal=True)
    blk = dot_product_attention(q, q, q, causal=True, impl="blockwise")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(blk))


def test_explicit_flash_rejects_bias():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 64, 1, 64)), jnp.float32)
    bias = jnp.zeros((1, 1, 1, 64), jnp.float32)
    with pytest.raises(ValueError, match="bias"):
        dot_product_attention(q, q, q, bias=bias, impl="flash")


def test_non_dividing_blocks_pad_to_common_multiple(monkeypatch, schedule):
    # _BQ=64, _BK=48 at s=100: a _BQ-only pad would drop tail keys
    monkeypatch.setattr(fa_mod, "_BK", 48)
    rng = np.random.default_rng(5)
    q, k, v = _qkv(rng, 1, 100, 1, 64)
    want = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense")
    got = flash_attention(q, k, v, causal=True, dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# two combos cover both axes (causal interplay; padded-tail blocks)
# without quadrupling a ~7-15 s interpret-mode parity run
@pytest.mark.parametrize("causal,s", [(False, 128), (True, 100)])
def test_flash_kv_mask_matches_dense_bias(causal, s, schedule):
    """Per-key padding mask (the BERT attention_mask form) against the
    dense path's additive-bias formulation, fwd + grads."""
    rng = np.random.default_rng(6)
    q, k, v = _qkv(rng, 2, s, 2, 64)
    # ragged "sequence lengths" incl. one full row: 1=attend, 0=padding
    kv_mask = jnp.asarray(
        np.stack([np.arange(s) < s, np.arange(s) < (3 * s // 5)]), jnp.float32
    )
    bias = jnp.where(kv_mask[:, None, None, :] > 0, 0.0, -1e30)

    def flash_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, kv_mask=kv_mask, dtype=jnp.float32,
            interpret=True,
        )
        return jnp.sum(o**2), o

    def dense_loss(q, k, v):
        o = dot_product_attention(
            q, k, v, causal=causal, bias=bias, dtype=jnp.float32, impl="dense"
        )
        return jnp.sum(o**2), o

    (_, got), gf = jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), gd = jax.value_and_grad(dense_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name}",
        )


def test_kv_mask_batch_rows_are_independent():
    """The (b // heads) index map must hand each batch its OWN mask row —
    a batch-0-only bug would be invisible to single-batch parity tests."""
    rng = np.random.default_rng(7)
    b, s, h, d = 3, 64, 2, 64
    q, k, v = _qkv(rng, b, s, h, d)
    lens = [64, 40, 17]
    kv_mask = jnp.asarray(
        np.stack([np.arange(s) < n for n in lens]), jnp.float32
    )
    got = flash_attention(
        q, k, v, kv_mask=kv_mask, dtype=jnp.float32, interpret=True
    )
    for i, n in enumerate(lens):
        # each batch row must equal its OWN single-batch masked attention
        want = flash_attention(
            q[i : i + 1], k[i : i + 1], v[i : i + 1],
            kv_mask=kv_mask[i : i + 1], dtype=jnp.float32, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want[0]), rtol=2e-5, atol=2e-5,
            err_msg=f"batch {i} (len {n})",
        )


def test_dot_product_attention_kv_mask_across_impls():
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, 2, 96, 2, 64)
    kv_mask = jnp.asarray(
        np.stack([np.arange(96) < 70, np.arange(96) < 33]), jnp.float32
    )
    dense = dot_product_attention(
        q, k, v, kv_mask=kv_mask, dtype=jnp.float32, impl="dense"
    )
    blk = dot_product_attention(
        q, k, v, kv_mask=kv_mask, dtype=jnp.float32, impl="blockwise"
    )
    np.testing.assert_allclose(
        np.asarray(blk), np.asarray(dense), rtol=2e-5, atol=2e-5
    )
    with pytest.raises(ValueError, match="not both"):
        dot_product_attention(
            q, k, v, kv_mask=kv_mask, bias=jnp.zeros((2, 1, 1, 96))
        )
    with pytest.raises(ValueError, match="kv_mask must be"):
        dot_product_attention(q, k, v, kv_mask=kv_mask[:, :10])


def test_kernels_trace_once_and_keep_the_callers_scope(monkeypatch):
    """24 layers call the same kernel on the same types: one trace serves
    them all, and each call's equation still sits under its own layer's
    name (the device op is found by it: PERF.md section 7)."""
    traces = []
    real = fa_mod._fwd_kernel
    monkeypatch.setattr(
        fa_mod, "_fwd_kernel", lambda *a: (traces.append(1), real(*a))[1]
    )
    monkeypatch.setattr(fa_mod, "_TRACED", {})
    rng = np.random.default_rng(11)
    q, k, v = _qkv(rng, 1, 128, 2, 64)

    def two_layers(q, k, v):
        for name in ("h_0", "h_1"):
            with jax.named_scope(name):
                q = flash_attention(q, k, v, causal=True, dtype=jnp.float32, interpret=True)
        return q

    def pallas_scopes(jaxpr, outer=""):
        for e in jaxpr.eqns:
            here = f"{outer}/{e.source_info.name_stack}".strip("/")
            if e.primitive.name == "pallas_call":
                yield here
            for sub in e.params.values():  # the custom VJP's call holds the kernel
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from pallas_scopes(sub, here)

    jaxpr = jax.make_jaxpr(two_layers)(q, k, v)
    assert len(traces) == 1
    assert list(pallas_scopes(jaxpr.jaxpr)) == ["h_0", "h_1"]
