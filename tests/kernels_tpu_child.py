"""Every Pallas kernel in the tree, COMPILED on the chip at full width and
checked against its reference — the child process of test_kernels_tpu.py
(the pytest parent is pinned to the CPU mesh and never touches the chip).

    python tests/kernels_tpu_child.py            # all groups, one JSON line
    python tests/kernels_tpu_child.py flash      # one group
    python tests/kernels_tpu_child.py flash mla --against old/flash_attention.py

Each group runs in its own try: a kernel the compiler refuses is recorded
with the compiler's words under ``"error"`` and the rest still run, so
one call fills the whole table. ``--against`` names another
``flash_attention.py`` (an older commit's, unpacked beside): ``flash`` and
``mla`` then time its backward beside this tree's at the same operands and
say whether the gradients are the same bits.
"""

import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from consensusml_tpu.compile_cache import enable_compile_cache

RNG = np.random.default_rng(0)
AGAINST = {}  # {"module": another flash_attention module}, from ``--against``


def _normal(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


def codec():
    """int8 / int4 / fp8 quantizers, chunked top-k, chunk scatter."""
    from consensusml_tpu.compress.kernels import (
        chunk_scatter, chunked_topk, dequantize_fp8, dequantize_int4,
        dequantize_int8, quantize_fp8, quantize_int4, quantize_int8,
    )
    from consensusml_tpu.compress.reference import (
        Fp8Compressor, Int4Compressor, chunk_for_quantization,
    )

    out = {}
    chunks = _normal((1024, 512))
    q, s = quantize_int8(chunks)
    refc, refs, inv, _ = chunk_for_quantization(chunks, 512)
    q_ref = np.clip(
        np.rint(np.asarray(refc) * np.asarray(inv)[:, None]), -127, 127
    ).astype(np.int8)
    out["quant_exact"] = bool(np.array_equal(np.asarray(q), q_ref))
    out["scales_exact"] = bool(np.allclose(np.asarray(s), np.asarray(refs)))
    d = dequantize_int8(q, s)
    out["dequant_exact"] = bool(
        np.allclose(np.asarray(d), np.asarray(q, np.float32) * np.asarray(s)[:, None])
    )

    chunks4 = _normal((96, 256))
    p4, s4 = quantize_int4(chunks4)
    ref4 = Int4Compressor(chunk=256).compress(chunks4.reshape(-1))
    out["int4_pack_exact"] = bool(
        np.array_equal(np.asarray(p4).reshape(-1), np.asarray(ref4.data))
    )
    d4 = dequantize_int4(p4, s4)
    ref_dec = Int4Compressor(chunk=256).decompress(ref4)
    out["int4_roundtrip_ok"] = bool(
        np.allclose(np.asarray(d4).reshape(-1), np.asarray(ref_dec), atol=1e-5)
    )

    chunks8 = _normal((256, 512))
    q8, s8 = quantize_fp8(chunks8)
    ref8 = Fp8Compressor(chunk=512).compress(chunks8.reshape(-1))
    out["fp8_exact"] = bool(
        np.array_equal(
            np.asarray(q8.astype(jnp.float32)).reshape(-1),
            np.asarray(ref8.data.astype(jnp.float32)),
        )
    )
    d8 = dequantize_fp8(q8, s8)
    out["fp8_roundtrip_ok"] = bool(
        np.allclose(
            np.asarray(d8).reshape(-1),
            np.asarray(Fp8Compressor(chunk=512).decompress(ref8)),
            atol=1e-6,
        )
    )

    ok_topk = True
    for rows, cols, k in [(1024, 512, 8), (1024, 512, 16), (37, 256, 5), (8, 128, 128)]:
        c = _normal((rows, cols))
        v, i = chunked_topk(c, k)
        _, li = jax.lax.top_k(jnp.abs(c), k)
        vref = np.take_along_axis(np.asarray(c), np.asarray(li), axis=1)
        ok_topk &= bool(np.array_equal(np.asarray(i), np.asarray(li)))
        ok_topk &= bool(np.allclose(np.asarray(v), vref))
    out["topk_exact"] = ok_topk

    rows, chunk, k = 513, 512, 8
    sv = _normal((rows, k))
    si = jnp.asarray(
        np.stack([RNG.choice(chunk, size=k, replace=False) for _ in range(rows)]),
        jnp.int32,
    )
    acc = _normal((rows, chunk))
    got_sc = chunk_scatter(sv, si, chunk, acc, weight=0.25)
    want_sc = np.asarray(acc).copy()
    np.put_along_axis(
        want_sc,
        np.asarray(si),
        np.take_along_axis(np.asarray(acc), np.asarray(si), axis=1)
        + 0.25 * np.asarray(sv),
        axis=1,
    )
    out["scatter_exact"] = bool(np.allclose(np.asarray(got_sc), want_sc, atol=1e-6))
    return out


def fused_wire():
    """The one-pass bucketed wire: encode + decode per format, compiled.
    Bucket-sized rows (a 4 MiB-payload int8 bucket is ~8k chunks of 512)
    and the chunk-128 geometry the int4 half-chunk slice is narrowest at.

    Two things are pinned per format: the payload is the same BYTES the
    plain-ops path ships, and the tracking update is exactly what a
    receiver reconstructs — ``xhat' == xhat + decode(payload)`` with the
    decode run as its own program on the stored payload (CHOCO's
    invariant). ``jnp_*`` rows say whether XLA's own lowering of the same
    math agrees bit for bit; where it does not, ``*_max_diff`` says by
    how much."""
    from consensusml_tpu.compress.kernels import FusedBucketCodec

    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    same = lambda a, b: bool(np.array_equal(f32(a), f32(b)))
    out = {}
    for fmt, chunk, nchunks in [
        ("int8", 512, 8192), ("int4", 512, 8192), ("fp8", 512, 8192),
        ("int8", 128, 300), ("int4", 128, 300), ("fp8", 128, 300),
    ]:
        x = _normal((nchunks * chunk,))
        xhat = _normal((nchunks * chunk,), scale=0.1)
        s = _normal((nchunks * chunk,))
        kern = FusedBucketCodec(fmt=fmt, chunk=chunk, impl="pallas")
        ref = FusedBucketCodec(fmt=fmt, chunk=chunk, impl="jnp")
        pk, hk = jax.jit(kern.encode)(x, xhat)
        pr, hr = jax.jit(ref.encode)(x, xhat)
        received = xhat + jax.jit(ref.decode)(pk)  # what a peer rebuilds
        weights = (0.5, 0.25, 0.25)
        dk = jax.jit(lambda s, p: kern.decode_accumulate(s, [p, p, p], weights))(s, pk)
        dr = jax.jit(lambda s, p: ref.decode_accumulate(s, [p, p, p], weights))(s, pk)
        out[f"{fmt}/{chunk}"] = {
            "payload_exact": same(pk.data, pr.data) and same(pk.scales, pr.scales),
            "xhat_tracks_decode": same(hk, received),
            "decode_exact": same(dk, dr),
            "jnp_xhat_exact": same(hk, hr),
            "jnp_xhat_tracks_decode": same(hr, received),
            "jnp_xhat_max_diff": float(np.max(np.abs(f32(hk) - f32(hr)))),
        }
    return out


def flash():
    """Flash attention fwd + bwd at the GPT-2-medium shape family, and the
    backward alone at the dense, hybrid and delta cells' calls."""
    from consensusml_tpu.models.attention import dot_product_attention
    from consensusml_tpu.models.flash_attention import flash_attention

    out = {}
    b, s, h, d = 2, 1024, 4, 64
    q, k, v = (_normal((b, s, h, d)) for _ in range(3))
    want = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense")
    got = flash_attention(q, k, v, causal=True, dtype=jnp.float32)
    # default TPU matmul precision is bf16-class; both paths share it
    out["fwd_max_err"] = float(jnp.max(jnp.abs(got - want)))
    gf = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, causal=True, dtype=jnp.float32) ** 2))(q)
    gd = jax.grad(lambda q: jnp.sum(dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense") ** 2))(q)
    out["dq_rel_err"] = float(jnp.max(jnp.abs(gf - gd))) / max(float(jnp.max(jnp.abs(gd))), 1e-9)

    # per-key padding mask (the BERT path) — compiled, vs dense additive bias
    kv_mask = jnp.asarray(np.stack([np.arange(s) < s, np.arange(s) < 700]), jnp.float32)
    bias = jnp.where(kv_mask[:, None, None, :] > 0, 0.0, -1e30)
    want_m = dot_product_attention(q, k, v, bias=bias, dtype=jnp.float32, impl="dense")
    got_m = flash_attention(q, k, v, kv_mask=kv_mask, dtype=jnp.float32)
    out["masked_fwd_max_err"] = float(jnp.max(jnp.abs(got_m - want_m)))
    gm = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, kv_mask=kv_mask, dtype=jnp.float32) ** 2))(q)
    gb = jax.grad(lambda q: jnp.sum(dot_product_attention(q, k, v, bias=bias, dtype=jnp.float32, impl="dense") ** 2))(q)
    out["masked_dq_rel_err"] = float(jnp.max(jnp.abs(gm - gb))) / max(float(jnp.max(jnp.abs(gb))), 1e-9)

    # the benchmark cell's own call: bf16 (8, 1024, 16, 64), causal, the
    # operands as training hands them to the MXU; against dense fed the same
    bf = jnp.bfloat16
    qb, kb, vb, wb = (_normal((8, 1024, 16, 64), bf) for _ in range(4))
    f32 = lambda x: x.astype(jnp.float32)
    rel = lambda a, b: float(jnp.max(jnp.abs(f32(a) - f32(b)))) / max(float(jnp.max(jnp.abs(f32(b)))), 1e-9)
    dense = lambda q, k, v: dot_product_attention(q, k, v, causal=True, dtype=bf, impl="dense")
    flash_bf = lambda q, k, v: flash_attention(q, k, v, causal=True, dtype=bf)
    loss = lambda fn: (lambda q, k, v: jnp.sum(f32(fn(q, k, v)) * f32(wb)))
    out["bf16_fwd_rel_err"] = rel(jax.jit(flash_bf)(qb, kb, vb), jax.jit(dense)(qb, kb, vb))
    g_flash = jax.jit(jax.grad(loss(flash_bf), argnums=(0, 1, 2)))(qb, kb, vb)
    g_dense = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(qb, kb, vb)
    for name, a, b_ in zip("qkv", g_flash, g_dense):
        out[f"bf16_d{name}_rel_err"] = rel(a, b_)
    # the backward alone at the cells' calls: the dense cell's (8 x 1,024, 16
    # heads of 64, straight-line), the hybrid's (8,192 tokens, 32 of 128) and
    # the delta cell's (8,192, 16 of 256), both looped
    out["bwd_dense"] = _bwd_alone(8, 1024, 16, 64, 64, 64**-0.5)
    out["bwd_hybrid"] = _bwd_alone(1, 8192, 32, 128, 128, 128**-0.5)
    out["bwd_delta"] = _bwd_alone(1, 8192, 16, 256, 256, 256**-0.5)
    return out


def _bwd_alone(b, s, h, d_k, d_v, scale, reps=20, interpret=False):
    """Flash attention's backward by itself (``_bwd``: ``delta`` and the
    kernels, from the forward's residuals), causal, bfloat16, at one call's
    shape: ms a call, host fence included, and the gradients' sizes; with
    ``--against``, the other module's beside it on the same operands, and
    whether ``dk`` and ``dv`` are the same bits and how far ``dq`` is."""
    import time

    from consensusml_tpu.models import flash_attention as fa

    bf, f32 = jnp.bfloat16, jnp.float32
    q3, k3 = (fa.fold_pad(_normal((b, s, h, d_k), bf), 512) for _ in range(2))
    v3, do3 = (fa.fold_pad(_normal((b, s, h, d_v), bf), 512) for _ in range(2))
    o3, lse = jax.jit(lambda q, k, v: fa._fwd(q, k, v, True, s, scale, interpret))(q3, k3, v3)
    out, grads = {"shape": [b, s, h, d_k, d_v]}, {}
    for side, mod in [("this", fa)] + [("against", m) for m in AGAINST.values()]:
        bwd = jax.jit(lambda q, k, v, o, lse, do, mod=mod: mod._bwd(True, s, scale, interpret, h, (q, k, v, None, o, lse), do))
        args = (q3, k3, v3, o3, lse, do3)
        grads[side] = jax.block_until_ready(bwd(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = bwd(*args)
        jax.block_until_ready(res)
        out[f"{side}_ms"] = (time.perf_counter() - t0) / reps * 1e3
    norm = lambda x: float(jnp.linalg.norm(x.astype(f32)))
    out.update({f"d{n}_norm": norm(g) for n, g in zip("qkv", grads["this"])})
    if "against" in grads:
        (dq, dk, dv), (dq0, dk0, dv0) = grads["this"], grads["against"]
        out["dk_same_bits"] = bool(jnp.array_equal(dk, dk0))
        out["dv_same_bits"] = bool(jnp.array_equal(dv, dv0))
        out["dq_rel_diff"] = norm(dq.astype(f32) - dq0.astype(f32)) / norm(dq0)
        out["dq_max_abs_diff"] = float(jnp.max(jnp.abs(dq.astype(f32) - dq0.astype(f32))))
        out["dq_max_abs"] = float(jnp.max(jnp.abs(dq0.astype(f32))))
        out["speedup"] = out["against_ms"] / out["this_ms"]
    return out


def ring_flash():
    """The compiled ring-flash path INSIDE shard_map (production
    settings: check_vma on) over every device found — a ring of one on a
    single chip still compiles the offset kernels under the manual axis."""
    import functools

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from consensusml_tpu.models.attention import dot_product_attention
    from consensusml_tpu.parallel import ring_flash_attention

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    b, s, h, d = 1, 1024 * n, 16, 64  # 1024 tokens per device, medium heads
    q, k, v = (_normal((b, s, h, d)) for _ in range(3))
    shard = NamedSharding(mesh, P(None, "sp"))
    sm = functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp")
    )

    @jax.jit
    @sm
    def fwd(q, k, v):
        return ring_flash_attention(q, k, v, "sp", causal=True)

    @jax.jit
    @sm
    def grads(q, k, v):
        loss = lambda q, k, v: jnp.sum(
            ring_flash_attention(q, k, v, "sp", causal=True).astype(jnp.float32) ** 2
        )
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    args = [jax.device_put(x, shard) for x in (q, k, v)]
    got = fwd(*args)
    want = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense")
    out = {"devices": n, "fwd_max_err": float(jnp.max(jnp.abs(got - want)))}
    g_ring = grads(*args)
    g_dense = jax.grad(
        lambda q, k, v: jnp.sum(
            dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense") ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, a, b_ in zip("qkv", g_ring, g_dense):
        out[f"d{name}_rel_err"] = float(jnp.max(jnp.abs(a - b_))) / max(
            float(jnp.max(jnp.abs(b_))), 1e-9
        )
    return out


def _paged_case(num_slots, max_len, block_size, w, impl):
    """One decode (w=1) or verify-window (w>1) call at GPT-2-medium head
    shapes over a pool sized like the engine sizes it (same seed per
    call, so two impls see the same pool)."""
    from consensusml_tpu.models.paged_attention import (
        fused_paged_attention, fused_paged_attention_window,
    )

    rng = np.random.default_rng(7)
    bf16 = lambda shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    h, d = 16, 64
    nb = max_len // block_size
    n_blocks = num_slots * nb + 1
    k_pages = bf16((n_blocks, block_size, h, d))
    v_pages = bf16((n_blocks, block_size, h, d))
    table = jnp.asarray(
        1 + rng.permutation(n_blocks - 1)[: num_slots * nb].reshape(num_slots, nb),
        jnp.int32,
    )
    q = bf16((num_slots, w, h, d))
    lengths = jnp.asarray(rng.integers(w, max_len - w, size=num_slots), jnp.int32)
    if w == 1:
        return jax.jit(
            lambda q, kp, vp, t, l: fused_paged_attention(
                q, kp, vp, t, lengths=l, dtype=jnp.bfloat16, impl=impl
            )
        )(q, k_pages, v_pages, table, lengths)
    positions = lengths[:, None] + jnp.arange(w)[None, :]
    return jax.jit(
        lambda q, kp, vp, t, p: fused_paged_attention_window(
            q, kp, vp, t, positions=p, dtype=jnp.bfloat16, impl=impl
        )
    )(q, k_pages, v_pages, table, positions)


def paged_attention():
    """fused_paged_attn_w1 / w{k+1}: a pool the engine would really
    allocate for GPT-2-medium (8 slots x 1024 tokens, 8-token blocks =
    1025 blocks of (8, 16, 64) bf16 per layer), then a toy pool — to tell
    "the body does not lower" from "the pool does not fit"."""
    out = {}
    for name, (slots, max_len, bs) in {
        "pool_8x1024": (8, 1024, 8),
        "pool_2x64": (2, 64, 8),
    }.items():
        for w in (1, 4):
            try:
                got = _paged_case(slots, max_len, bs, w, "pallas").astype(jnp.float32)
                want = _paged_case(slots, max_len, bs, w, "gather").astype(jnp.float32)
                out[f"{name}/w{w}"] = {
                    "bit_exact": bool(np.array_equal(np.asarray(got), np.asarray(want))),
                    "max_err": float(jnp.max(jnp.abs(got - want))),
                }
            except Exception as e:  # the compiler's words, per geometry
                out[f"{name}/w{w}"] = {"error": f"{type(e).__name__}: {str(e)[:1200]}"}
    return out


def fused_bn():
    from consensusml_tpu.models.fused_bn import fused_batch_norm

    errs = {}
    for name, (m, c) in {"wide": (4096, 256), "packed": (4096, 64)}.items():
        x = _normal((m, c))
        gamma = _normal((c,), scale=0.3) + 1.0
        beta = _normal((c,), scale=0.1)
        w = _normal((c,))

        def loss(x, gamma, beta, impl):
            y, mean, var = fused_batch_norm(x, gamma, beta, act="relu", impl=impl)
            return jnp.sum(jnp.sin(y) * w)

        vg = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)), static_argnums=3)
        l_p, g_p = vg(x, gamma, beta, "pallas")
        l_j, g_j = vg(x, gamma, beta, "jnp")
        errs[name] = {
            "loss": abs(float(l_p - l_j)),
            "dx": float(jnp.max(jnp.abs(g_p[0] - g_j[0]))),
            "dgamma": float(jnp.max(jnp.abs(g_p[1] - g_j[1]))),
            "dbeta": float(jnp.max(jnp.abs(g_p[2] - g_j[2]))),
        }
    return errs


def fused_ln():
    from consensusml_tpu.models.fused_ln import fused_layer_norm

    errs = {}
    # gpt2-medium row shape and a bert-ish one
    for name, (m, h) in {"gpt2": (4096, 1024), "bert": (2048, 256)}.items():
        x = _normal((m, h), jnp.bfloat16, scale=2.0) + 0.5
        gamma = _normal((h,), scale=0.3) + 1.0
        beta = _normal((h,), scale=0.1)
        w = _normal((m, h))

        def loss(x, gamma, beta, impl):
            y = fused_layer_norm(x, gamma, beta, 1e-6, jnp.float32, impl)
            return jnp.sum(jnp.sin(y) * w)

        vg = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)), static_argnums=3)
        l_p, g_p = vg(x, gamma, beta, "pallas")
        l_j, g_j = vg(x, gamma, beta, "jnp")
        errs[name] = {
            "loss": abs(float(l_p - l_j)),
            "dx": float(jnp.max(jnp.abs(jnp.asarray(g_p[0] - g_j[0], jnp.float32)))),
            "dgamma": float(jnp.max(jnp.abs(g_p[1] - g_j[1]))),
            "dbeta": float(jnp.max(jnp.abs(g_p[2] - g_j[2]))),
        }
    return errs


def ssd():
    """The fused SSD scan pair (``models/ssm.py:ssd_scan``) at the hybrid
    cell's shapes (1 x 8,192 tokens, 64 heads of 64 in 8 groups, state 128,
    chunk 128, bfloat16 operands), compiled: values and all five gradients
    against the benchmark reference's token-by-token recurrence (float32 at
    ``highest``, fed the same bfloat16-rounded operands) and against
    ``ssd_chunked`` (XLA's fusions, the same roundings), and what a call of
    each costs, host fence included."""
    import time

    sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    from reference import nemotron_h as ref

    from consensusml_tpu.models import ssm

    b, t, h, p, g, n, chunk = 1, 8192, 64, 64, 8, 128, 128
    bf, f32 = jnp.bfloat16, jnp.float32
    x = _normal((b, t, h, p), bf, 0.5)
    bm, cm = _normal((b, t, g, n), bf, 0.3), _normal((b, t, g, n), bf, 0.3)
    dt = jnp.asarray(np.exp(RNG.uniform(np.log(1e-3), np.log(0.1), (b, t, h))), f32)
    a = -jnp.asarray(RNG.uniform(1.0, 16.0, (h,)), f32)
    probe = _normal((b, t, h, p))

    def stepwise(x, dt, a, bm, cm):
        spread = lambda v: jnp.repeat(v.astype(f32), h // g, axis=2)
        return ref.recurrence(x.astype(f32), dt, jnp.exp(dt * a), spread(bm), spread(cm), jnp.ones((t,)))

    paths = {
        "kernel": lambda *args: ssm.ssd_scan(*args, chunk=chunk),
        "xla": lambda *args: ssm.ssd_chunked(*args, chunk=chunk),
        "recurrence": stepwise,
    }
    args = (x, dt, a, bm, cm)
    rel = lambda u, v: float(jnp.linalg.norm(u.astype(f32) - v.astype(f32)) / (jnp.linalg.norm(v.astype(f32)) + 1e-30))
    fwd = {k: jax.jit(f) for k, f in paths.items()}
    grad = {k: jax.jit(jax.grad(lambda *args, f=f: jnp.sum(f(*args) * probe), argnums=(0, 1, 2, 3, 4)))
            for k, f in paths.items()}
    ys = {k: f(*args) for k, f in fwd.items()}
    gs = {k: f(*args) for k, f in grad.items()}
    out = {}
    for other in ("recurrence", "xla"):
        out[f"y_vs_{other}"] = rel(ys["kernel"], ys[other])
        for name, got, want in zip(("x", "dt", "a", "b", "c"), gs["kernel"], gs[other]):
            out[f"d{name}_vs_{other}"] = rel(got, want)
    out["xla_y_vs_recurrence"] = rel(ys["xla"], ys["recurrence"])
    for name, got, want in zip(("x", "dt", "a", "b", "c"), gs["xla"], gs["recurrence"]):
        out[f"xla_d{name}_vs_recurrence"] = rel(got, want)

    def ms(f, reps=20):
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            r = f(*args)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / reps * 1e3

    for k in ("kernel", "xla"):
        out[f"{k}_fwd_ms"] = ms(fwd[k])
        out[f"{k}_fwd_bwd_ms"] = ms(grad[k])
    return out


def gdn():
    """The fused gated-delta-rule pair (``models/gated_delta.py:gated_delta_scan``)
    at the delta cell's shapes (1 x 8,192 tokens, 16 key and 32 value heads of
    128, chunk 64, bfloat16 operands), compiled: values and all five gradients
    against the benchmark reference's token-by-token recurrence (float32 at
    ``highest``, fed the same bfloat16-rounded operands) and against
    ``gated_delta_chunked`` (XLA's fusions, the same roundings), and what a call
    of each costs, host fence included."""
    import time

    sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    from reference import qwen3_next as ref

    from consensusml_tpu.models import gated_delta as gd

    b, t, kh, vh, dk, dv, chunk = 1, 8192, 16, 32, 128, 128, 64
    r, bf, f32 = vh // kh, jnp.bfloat16, jnp.float32
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = jnp.asarray(unit(RNG.normal(size=(b, t, kh, dk))) * dk**-0.5, bf)
    k = jnp.asarray(unit(RNG.normal(size=(b, t, kh, dk))), bf)
    v = _normal((b, t, vh, dv), bf)
    dt = np.exp(RNG.uniform(np.log(1e-3), np.log(0.1), (b, t, vh)))
    g = jnp.asarray(-RNG.uniform(1.0, 16.0, (vh,)) * dt, f32)
    beta = jnp.asarray(1.0 / (1.0 + np.exp(-RNG.normal(size=(b, t, vh)))), f32)
    probe = _normal((b, t, vh, dv))
    spread = lambda x: jnp.repeat(x, r, axis=2)

    def stepwise(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return ref.delta_rule(spread(q).astype(f32), spread(k).astype(f32), v.astype(f32), g, beta, jnp.ones((t,)))

    paths = {
        "kernel": lambda *args: gd.gated_delta_scan(*args, chunk=chunk),
        "xla": lambda q, k, *rest: gd.gated_delta_chunked(spread(q), spread(k), *rest, chunk=chunk),
        "recurrence": stepwise,
    }
    args = (q, k, v, g, beta)
    rel = lambda u, w: float(jnp.linalg.norm(u.astype(f32) - w.astype(f32)) / (jnp.linalg.norm(w.astype(f32)) + 1e-30))
    fwd = {n: jax.jit(f) for n, f in paths.items()}
    grad = {n: jax.jit(jax.grad(lambda *args, f=f: jnp.sum(f(*args) * probe), argnums=(0, 1, 2, 3, 4)))
            for n, f in paths.items()}
    ys = {n: f(*args) for n, f in fwd.items()}
    gs = {n: f(*args) for n, f in grad.items()}
    names = ("q", "k", "v", "g", "beta")
    out = {}
    for other in ("recurrence", "xla"):
        out[f"y_vs_{other}"] = rel(ys["kernel"], ys[other])
        for name, got, want in zip(names, gs["kernel"], gs[other]):
            out[f"d{name}_vs_{other}"] = rel(got, want)
    out["xla_y_vs_recurrence"] = rel(ys["xla"], ys["recurrence"])
    for name, got, want in zip(names, gs["xla"], gs["recurrence"]):
        out[f"xla_d{name}_vs_recurrence"] = rel(got, want)

    def ms(f, reps=20):
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = f(*args)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / reps * 1e3

    for n in ("kernel", "xla"):
        out[f"{n}_fwd_ms"] = ms(fwd[n])
        out[f"{n}_fwd_bwd_ms"] = ms(grad[n])
    return out


def gdn_conv():
    """The delta-rule layer's short convolution as the fused pair
    (``models/gated_delta.py:gated_delta_conv``, ``gdn_conv_fwd`` /
    ``gdn_conv_bwd``) at the delta cell's shapes (1 x 8,192 tokens, 16 key and
    32 value heads of 128, ``kw`` 4, float32 ``qkvz`` in, bfloat16 out),
    compiled: the outputs ``q``, ``k``, ``v``, ``z`` and both gradients against
    the mixer's XLA expression, largest absolute and relative differences, and
    what a call of each costs, host fence included, forward and forward +
    backward, at row tiles of 256, 512 (the program's) and 1,024."""
    import time

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_gdn_conv_kernel import xla_conv

    from consensusml_tpu.models import gated_delta as gd
    from consensusml_tpu.models import short_conv

    c = gd.GatedDeltaConfig()
    b, t, f32 = 1, 8192, jnp.float32
    width = c.key_heads * (2 * c.key_dim + 2 * c.value_heads // c.key_heads * c.value_dim)
    qkvz = _normal((b, t, width))
    conv_w = jnp.asarray(RNG.uniform(-0.5, 0.5, (c.conv_kernel, c.conv_dim)), f32)
    paths = {"kernel": lambda x, w: gd.gated_delta_conv(x, w, c), "xla": lambda x, w: xla_conv(x, w, c)}
    probes = [_normal(o.shape) for o in jax.eval_shape(paths["xla"], qkvz, conv_w)]
    loss = lambda f: lambda x, w: sum(jnp.sum(o.astype(f32) * p) for o, p in zip(f(x, w), probes))
    fwd = {n: jax.jit(f) for n, f in paths.items()}
    grad = {n: jax.jit(jax.grad(loss(f), argnums=(0, 1))) for n, f in paths.items()}
    ys = {n: f(qkvz, conv_w) for n, f in fwd.items()}
    gs = {n: f(qkvz, conv_w) for n, f in grad.items()}
    rel = lambda u, w: float(jnp.linalg.norm(u.astype(f32) - w.astype(f32)) / (jnp.linalg.norm(w.astype(f32)) + 1e-30))
    most = lambda u, w: float(jnp.max(jnp.abs(u.astype(f32) - w.astype(f32))))
    out = {}
    for name, got, want in zip(("q", "k", "v", "z", "dqkvz", "dconv_w"), (*ys["kernel"], *gs["kernel"]),
                               (*ys["xla"], *gs["xla"])):
        out[f"{name}_max_abs"], out[f"{name}_rel"] = most(got, want), rel(got, want)

    def ms(f, reps=20):
        jax.block_until_ready(f(qkvz, conv_w))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = f(qkvz, conv_w)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / reps * 1e3

    out["xla_fwd_ms"], out["xla_fwd_bwd_ms"] = ms(fwd["xla"]), ms(grad["xla"])
    for rows in (256, 512, 1024):  # 1,024: the backward kernel's stack outgrows VMEM on the chip (not here)
        short_conv.ROWS = rows
        kernel = lambda x, w: gd.gated_delta_conv(x, w, c)
        try:
            out[f"kernel_fwd_ms_{rows}"] = ms(jax.jit(kernel))
            out[f"kernel_fwd_bwd_ms_{rows}"] = ms(jax.jit(jax.grad(loss(kernel), argnums=(0, 1))))
        except Exception as e:
            out[f"error_{rows}"] = f"{type(e).__name__}: {str(e)[:200]}"
    short_conv.ROWS = 512
    return out


def ssm_conv():
    """The state-space layer's short convolution as the fused pair
    (``models/ssm.py:ssd_conv``, ``ssd_conv_fwd`` / ``ssd_conv_bwd``) at the
    hybrid cell's shapes (1 x 8,192 tokens, 64 heads of 64 in 8 groups, state
    128, ``kw`` 4, float32 ``proj`` in, bfloat16 out), compiled: the outputs
    ``z``, ``x``, ``B``, ``C``, ``dt``'s columns and the three gradients against
    the mixer's XLA expression, largest absolute and relative differences; what
    a call of each costs, host fence included, forward and forward + backward,
    at row tiles of 256, 512 (the program's) and 1,024, each tile's fault
    recorded and the sweep going on; then each kernel's own device time at 512
    rows (the median of ten events in a trace) against the bytes it has to move
    (HBM peak 819 GB/s)."""
    import tempfile
    import time

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    import xtrace
    from test_ssm_conv_kernel import xla_conv

    from consensusml_tpu.models import short_conv, ssm

    c = ssm.Mamba2Config()
    b, t, f32, bf = 1, 8192, jnp.float32, jnp.bfloat16
    d_in, gn = c.d_inner, c.groups * c.state
    proj = _normal((b, t, 2 * d_in + 2 * gn + c.heads))
    conv_w = jnp.asarray(RNG.uniform(-0.5, 0.5, (c.conv_kernel, c.conv_dim)), f32)
    conv_b = _normal((c.conv_dim,), scale=0.3)
    args = (proj, conv_w, conv_b)
    paths = {"kernel": lambda *a: ssm.ssd_conv(*a, c), "xla": lambda *a: xla_conv(*a, c)}
    probes = [_normal(o.shape) for o in jax.eval_shape(paths["xla"], *args)]
    loss = lambda f: lambda *a: sum(jnp.sum(o.astype(f32) * p) for o, p in zip(f(*a), probes))
    fwd = {n: jax.jit(f) for n, f in paths.items()}
    grad = {n: jax.jit(jax.grad(loss(f), argnums=(0, 1, 2))) for n, f in paths.items()}
    ys = {n: f(*args) for n, f in fwd.items()}
    gs = {n: f(*args) for n, f in grad.items()}
    rel = lambda u, w: float(jnp.linalg.norm(u.astype(f32) - w.astype(f32)) / (jnp.linalg.norm(w.astype(f32)) + 1e-30))
    most = lambda u, w: float(jnp.max(jnp.abs(u.astype(f32) - w.astype(f32))))
    out = {"impl": ssm._conv_impl(t, c)}
    names = ("z", "x", "B", "C", "dt", "dproj", "dconv_w", "dconv_b")
    for name, got, want in zip(names, (*ys["kernel"], *gs["kernel"]), (*ys["xla"], *gs["xla"])):
        out[f"{name}_max_abs"], out[f"{name}_rel"] = most(got, want), rel(got, want)

    def ms(f, reps=20):
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = f(*args)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / reps * 1e3

    out["xla_fwd_ms"], out["xla_fwd_bwd_ms"] = ms(fwd["xla"]), ms(grad["xla"])
    for rows in (256, 512, 1024):  # 1,024 outgrew VMEM in the delta layer's pair on the chip
        short_conv.ROWS = rows
        kernel = lambda *a: ssm.ssd_conv(*a, c)
        try:
            out[f"kernel_fwd_ms_{rows}"] = ms(jax.jit(kernel))
            out[f"kernel_fwd_bwd_ms_{rows}"] = ms(jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2))))
        except Exception as e:
            out[f"error_{rows}"] = f"{type(e).__name__}: {str(e)[:200]}"
    short_conv.ROWS = 512
    # each kernel alone, its operands as the mixer hands them over; bytes: xBC in float32 and
    # x, B, C out in bfloat16 forward; backward xBC again, the cotangents of z, x, B, C (bfloat16)
    # and dt (float32) in, proj's whole float32 cotangent out (the halos' few rows left out)
    wb = jnp.concatenate([conv_w, conv_b[None]], axis=0)
    cts = [_normal(o.shape, o.dtype) for o in jax.eval_shape(lambda p, w: ssm._conv_fwd(p, w, c, False), proj, wb)]
    xbc, cols = t * c.conv_dim * 4, t * proj.shape[2] * 4
    alone = {
        "ssd_conv_fwd": (lambda p, w: ssm._conv_fwd(p, w, c, False)[1:4], (proj, wb), xbc + xbc // 2),
        "ssd_conv_bwd": (lambda p, w, *g: ssm._conv_vjp_bwd(c, False, (p, w), g), (proj, wb, *cts),
                         xbc + xbc // 2 + t * d_in * 2 + t * c.heads * 4 + cols),
    }
    jitted = {name: (jax.jit(f), a) for name, (f, a, _) in alone.items()}
    for f, a in jitted.values():
        jax.block_until_ready(f(*a))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for f, a in jitted.values():
            for _ in range(10):
                res = f(*a)
            jax.block_until_ready(res)
        jax.profiler.stop_trace()
        events = next(iter(xtrace.load_xplane(trace_dir)["planes"].values()))[xtrace.OPS_LINE]
    for name, (_, _, moved) in alone.items():
        took = sorted(d for event, _, d in events if event.lstrip("%").startswith(name))
        took = took[len(took) // 2] / 1e6
        out[name] = {"ms": took, "gb": moved / 1e9, "hbm_roofline_pct": 100 * moved / 819e9 / (took / 1e3)}
    return out


def mla():
    """Flash attention at latent attention's widths, the cell's call (1 x 4,096
    tokens, 32 heads, keys 192 wide, values 128, the yarn scale, bfloat16),
    compiled: the output and all three gradients against the blockwise XLA path
    fed the same operands, and what a call costs, host fence included, with
    the 192-wide blocks as they are and with q and k zero-padded to 256 (two
    whole lane tiles; the products are the same, the MXU's passes too); and
    the backward alone at that call (:func:`_bwd_alone`)."""
    import time

    from consensusml_tpu.models.attention import blockwise_attention
    from consensusml_tpu.models.flash_attention import flash_attention

    b, t, h, dk, dv = 1, 4096, 32, 192, 128
    scale = dk**-0.5 * (0.1 * np.log(64.0) + 1.0) ** 2
    bf, f32 = jnp.bfloat16, jnp.float32
    q, k, v = _normal((b, t, h, dk), bf), _normal((b, t, h, dk), bf), _normal((b, t, h, dv), bf)
    probe = _normal((b, t, h, dv))
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 256 - dk)))
    paths = {
        "native": lambda q, k, v: flash_attention(q, k, v, causal=True, scale=scale),
        "padded": lambda q, k, v: flash_attention(pad(q), pad(k), v, causal=True, scale=scale),
        "xla": lambda q, k, v: blockwise_attention(q, k, v, causal=True, scale=scale),
    }
    rel = lambda u, w: float(jnp.linalg.norm(u.astype(f32) - w.astype(f32)) / (jnp.linalg.norm(w.astype(f32)) + 1e-30))
    fwd = {n: jax.jit(f) for n, f in paths.items()}
    grad = {n: jax.jit(jax.grad(lambda q, k, v, f=f: jnp.sum(f(q, k, v).astype(f32) * probe), argnums=(0, 1, 2)))
            for n, f in paths.items()}
    ys = {n: f(q, k, v) for n, f in fwd.items()}
    gs = {n: f(q, k, v) for n, f in grad.items()}
    out = {"shape": [b, t, h, dk, dv], "scale": scale}
    for n in ("native", "padded"):
        out[f"{n}_y_vs_xla"] = rel(ys[n], ys["xla"])
        for name, got, want in zip("qkv", gs[n], gs["xla"]):
            out[f"{n}_d{name}_vs_xla"] = rel(got, want)

    def ms(f, reps=20):
        jax.block_until_ready(f(q, k, v))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = f(q, k, v)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / reps * 1e3

    for n in paths:
        out[f"{n}_fwd_ms"] = ms(fwd[n])
        out[f"{n}_fwd_bwd_ms"] = ms(grad[n])
    out["bwd"] = _bwd_alone(b, t, h, dk, dv, scale)
    return out


def mhc():
    """The hyper-connected residual's four kernels (``models/hyper_connections.py``)
    at the latent cell's shapes (1 row x 4 streams x 4,096 tokens x 3584, bfloat16
    streams), compiled: ``u``, the maps, ``X'`` and the stream sizes, and the
    gradients of a probed sum with respect to ``X``, ``y``, ``phi``, ``bias`` and
    ``gate``, against the plain XLA path fed the same operands; the two paths' forward
    and forward + backward for the sub-block, host fence included; then each
    kernel's own device time (the median of ten events in a trace) against the
    bytes it has to move (HBM peak 819 GB/s)."""
    import time

    from consensusml_tpu.models import hyper_connections as hc

    b, n, s, hidden = 1, 4, 4096, 3584
    bf, f32 = jnp.bfloat16, jnp.float32
    c = hc.HyperConfig(hidden=hidden, streams=n)
    mod = hc.HyperConnection(c)
    x, y = _normal((b, n, s, hidden), bf), _normal((b, s, hidden), bf)
    params = jax.jit(lambda: mod.init(jax.random.key(0), x)["params"])()
    probes = _normal((b, s, hidden)), _normal((b, n, s, hidden)), _normal((b, n))

    def sub_block(p, x, y):  # the read, a stand-in for the sub-block (y itself), the write
        u, h_res, h_post, streams = mod.apply({"params": p}, x, return_streams=True)
        out, rms = hc.hyper_post(streams, h_res, h_post, y)
        return u, h_res, h_post, out, rms

    def loss(p, x, y):
        u, _, _, out, rms = sub_block(p, x, y)
        return jnp.sum(u * probes[0]) + jnp.sum(out.astype(f32) * probes[1]) + jnp.sum(rms * probes[2])

    real = hc._mix_impl
    rel = lambda u, w: float(jnp.linalg.norm(u.astype(f32) - w.astype(f32)) / (jnp.linalg.norm(w.astype(f32)) + 1e-30))
    runs, out = {}, {"shape": [b, n, s, hidden], "impl": real(x)}
    for path in ("kernel", "xla"):
        hc._mix_impl = real if path == "kernel" else (lambda x: "xla")
        try:
            runs[path] = (jax.jit(lambda *a: sub_block(*a)), jax.jit(jax.grad(lambda *a: loss(*a), argnums=(0, 1, 2))))
            runs[path] += (runs[path][0](params, x, y), runs[path][1](params, x, y))
        finally:
            hc._mix_impl = real
    for name, got, want in zip(("u", "h_res", "h_post", "x_out", "stream_rms"), runs["kernel"][2], runs["xla"][2]):
        out[f"{name}_vs_xla"] = rel(got, want)
    (gp, gx, gy), (wp, wx, wy) = runs["kernel"][3], runs["xla"][3]
    out.update({f"d{k}_vs_xla": rel(gp[k], wp[k]) for k in ("phi", "bias", "gate")})
    out.update(dx_vs_xla=rel(gx, wx), dy_vs_xla=rel(gy, wy))

    def ms(f, *args, reps=30):
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = f(*args)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / reps * 1e3

    for path in ("kernel", "xla"):
        out[f"{path}_fwd_ms"] = ms(runs[path][0], params, x, y)
        out[f"{path}_fwd_bwd_ms"] = ms(runs[path][1], params, x, y)
    # each kernel alone, its operands as the sub-block hands them over
    k = (2 + n) * hc._GROUP
    rows = jnp.swapaxes(hc._grouped(params["phi"], n), 1, 2)
    beside = [hc._grouped(v, n)[:, None] for v in (params["bias"], jnp.ones((c.maps,), f32))]
    u, maps, raw, inv_rms = jax.jit(lambda *a: hc._read_call(*a, c, False))(x, rows, *beside)
    cols = jnp.swapaxes(jnp.pad(maps, ((0, 0), (0, hc._LANE - k), (0, 0))), 1, 2)
    zero = jax.custom_derivatives.SymbolicZero(jax.core.ShapedArray((), f32))
    streams, vector = x.size * 2, y.size * 2  # bytes: a pass over the streams, over one bfloat16 vector a token
    alone = {
        "mhc_read_fwd": (lambda x: hc._read_call(x, rows, *beside, c, False), (x,), streams + 2 * vector),
        "mhc_write_fwd": (lambda x, y: hc._write_call(x, y, cols, False), (x, y), 2 * streams + vector),
        "mhc_write_bwd": (lambda x, y, d: hc._write_vjp_bwd(False, (x, y, cols), (d, zero)), (x, y, x),
                          3 * streams + 2 * vector),
        "mhc_read_bwd": (lambda x, du, d: hc._read_vjp_bwd(c, False, (x, rows, *beside, raw, inv_rms), (du, maps, d)),
                         (x, u, x), 3 * streams + 2 * vector),
    }
    # device time from a trace (a kernel of a third of a millisecond is over before the host's next dispatch)
    import tempfile

    sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    import xtrace

    jitted = {name: (jax.jit(f), args) for name, (f, args, _) in alone.items()}
    for f, args in jitted.values():
        jax.block_until_ready(f(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for f, args in jitted.values():
            for _ in range(10):
                res = f(*args)
            jax.block_until_ready(res)
        jax.profiler.stop_trace()
        events = next(iter(xtrace.load_xplane(trace_dir)["planes"].values()))[xtrace.OPS_LINE]
    for name, (_, _, moved) in alone.items():
        took = sorted(d for event, _, d in events if event.lstrip("%").startswith(name))
        took = took[len(took) // 2] / 1e6
        out[name] = {"ms": took, "gb": moved / 1e9, "hbm_roofline_pct": 100 * moved / 819e9 / (took / 1e3)}
    return out


def hbm_sampler():
    """Does the allocator count a RUNNING program's temporaries, and where? Two
    jitted programs whose temporaries XLA cannot fuse away (float32 arrays made
    inside them from two vectors, one rolled and mixed 120 times as a loop's
    carry: 4.3 GB and a second and a half of device time for the first, a
    quarter of both for the second), a thread reading every key of
    ``memory_stats()`` every 5 ms while each runs. Reported: the compiler's
    ``memory_analysis()`` of each, the allocator's keys before it was built,
    once built, at their largest while it ran and after, and again once the
    first program was dropped; ``sampled_over_compiled`` and
    ``peak_over_compiled``: ``bytes_in_use`` while the first ran, and the
    lifetime ``peak_bytes_in_use`` after it, over what was in use before, as
    shares of its temporaries + outputs (within 10% of 1 if the runtime counts
    them there, near 0 if it hides them); ``reserved_over_compiled``: the same
    share for ``bytes_reserved``, where this runtime keeps them."""
    import threading
    import time

    from consensusml_tpu.obs.memviz import device_memory_stats

    def stats():
        return {k: int(v) for k, v in (device_memory_stats() or {}).items()}

    def build(rows, cols, iters=120):
        def prog(a, b):
            first = a[:, None] * b[None, :]

            def body(_, c):
                return 0.5 * jnp.roll(c, 1, axis=0) + 0.5 * first

            return jnp.sum(jax.lax.fori_loop(0, iters, body, first), axis=1)

        args = jax.block_until_ready((_normal((rows,)), _normal((cols,))))
        before = stats()
        compiled = jax.jit(prog).lower(*args).compile()
        ma = compiled.memory_analysis()
        work = int(ma.temp_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        return compiled, args, {
            "temp_bytes": int(ma.temp_size_in_bytes), "output_bytes": int(ma.output_size_in_bytes),
            "argument_bytes": int(ma.argument_size_in_bytes), "work_bytes": work,
            "before_it_was_built": before, "once_built": stats(),
        }

    def run(compiled, args):
        largest, stop = {}, threading.Event()

        def watch():
            while not stop.is_set():
                for k, v in stats().items():
                    largest[k] = max(largest.get(k, v), v)
                largest["samples"] = largest.get("samples", 0) + 1
                time.sleep(0.005)

        before = stats()
        t0 = time.perf_counter()
        res = compiled(*args)
        thread = threading.Thread(target=watch, daemon=True)
        thread.start()  # after the dispatch: every sample is of the running program
        jax.block_until_ready(res)
        stop.set()
        seconds = time.perf_counter() - t0
        thread.join(timeout=10)
        del res
        return {"run_s": seconds, "before": before, "largest_while_running": largest, "after": stats()}

    big, big_args, out_big = build(16384, 32768)  # 2 GiB an array
    out_big["first_run"] = run(big, big_args)
    out_big["second_run"] = run(big, big_args)
    small, small_args, out_small = build(8192, 16384)  # 0.5 GiB an array
    out_small["first_run"] = run(small, small_args)
    out_big["third_run_beside_the_small_program"] = run(big, big_args)
    del big
    import gc

    gc.collect()
    out_small["once_the_big_program_was_dropped"] = stats()
    out_small["second_run"] = run(small, small_args)
    ran = out_big["second_run"]
    held = ran["before"]["bytes_in_use"]
    share = lambda v: (v - held) / out_big["work_bytes"]
    return {
        "big": out_big, "small": out_small,
        "sampled_over_compiled": share(ran["largest_while_running"]["bytes_in_use"]),
        "peak_over_compiled": share(ran["after"]["peak_bytes_in_use"]),
        "reserved_over_compiled": ran["largest_while_running"].get("bytes_reserved", 0) / out_big["work_bytes"],
    }


GROUPS = {
    "codec": codec,
    "fused_wire": fused_wire,
    "flash": flash,
    "ring_flash": ring_flash,
    "paged_attention": paged_attention,
    "fused_bn": fused_bn,
    "fused_ln": fused_ln,
    "ssd": ssd,
    "gdn": gdn,
    "gdn_conv": gdn_conv,
    "ssm_conv": ssm_conv,
    "mla": mla,
    "mhc": mhc,
    "hbm_sampler": hbm_sampler,
}


def main(argv) -> int:
    if "--against" in argv:
        import importlib.util

        at = argv.index("--against")
        spec = importlib.util.spec_from_file_location("flash_attention_against", argv[at + 1])
        AGAINST["module"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(AGAINST["module"])
        argv = argv[:at] + argv[at + 2 :]
    if jax.default_backend() != "tpu":
        print(f"no TPU: default backend is {jax.default_backend()!r}", file=sys.stderr)
        return 2
    enable_compile_cache()
    dev = jax.devices()[0]
    out = {
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
        }
    }
    for name in argv or list(GROUPS):
        try:
            out[name] = GROUPS[name]()
        except Exception as e:
            traceback.print_exc()
            out[name] = {"error": f"{type(e).__name__}: {str(e)[:1200]}"}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
