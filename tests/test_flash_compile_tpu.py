"""The flash-attention kernels compiled for a DESCRIBED TPU v5e — no chip.

The interpreter tests cannot see what Mosaic and the TPU compiler refuse:
a slice off the (8, 128) tiling, a block spec the chip cannot hold, a
straight-line schedule whose spills outgrow scoped VMEM (2560 x 128-wide
heads did, unrolled, while every interpreter test passed). The TPU
compiler is installed here and compiles for a topology that is described
and not attached; nothing runs, so these say nothing about results or
times (tests/test_kernels_tpu.py does, on the chip).

The topology is described inside a fixture, never at import: one process
at a time may hold the TPU library, and the suite's workers all import
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from consensusml_tpu.models import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _grads(causal, masked):
    def f(q, k, v, *mask):
        def loss(q, k, v):
            o = fa.flash_attention(
                q, k, v, causal=causal, kv_mask=mask[0] if masked else None
            )
            return jnp.sum(o.astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return f


@pytest.mark.parametrize(
    "shape, dtype, causal, masked",
    [
        # the benchmark cell's call: GPT-2-medium, 8 x 1024, 16 heads of 64
        ((8, 1024, 16, 64), jnp.bfloat16, True, False),
        ((2, 1024, 4, 64), jnp.float32, True, False),
        # padded tail, 128-wide heads (llama), still straight-line code
        ((1, 2000, 8, 128), jnp.bfloat16, True, False),
        # past the straight-line budget: a program per block, looping
        ((1, 2560, 8, 128), jnp.bfloat16, True, False),
        ((1, 8192, 8, 128), jnp.bfloat16, True, False),
        # 256-wide heads (gated attention with partial rotary): straight-line, and looped
        ((1, 1024, 4, 256), jnp.bfloat16, True, False),
        ((1, 8192, 4, 256), jnp.bfloat16, True, False),
        # BERT: not causal, every tile masked by the key row
        ((2, 1024, 4, 64), jnp.bfloat16, False, True),
        ((1, 3000, 4, 64), jnp.bfloat16, False, True),
    ],
)
def test_forward_and_backward_compile_for_v5e(one_chip, shape, dtype, causal, masked):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = [x, x, x]
    if masked:
        args.append(jax.ShapeDtypeStruct(shape[:2], jnp.float32, sharding=one_chip))
    text = jax.jit(_grads(causal, masked)).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("s_real", [1024, 1000])
def test_ring_offset_kernels_compile_for_v5e(one_chip, s_real):
    """The three kernels as ``parallel.ring_attention`` calls them: traced
    offsets in SMEM, the full loop, padded keys in the last tile."""
    blk = jax.ShapeDtypeStruct((16, 1024, 64), jnp.bfloat16, sharding=one_chip)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def step(q, k, v, do, q_off, k_off):
        kw = dict(q_offset=q_off, k_offset=k_off)
        o, lse = fa._fwd(q, k, v, True, s_real, 0.125, False, **kw)
        dq = fa._bwd_dq(q, k, v, do, o, lse, True, s_real, 0.125, False, **kw)
        dk, dv = fa._bwd_dkv(
            q, k, v, do, lse, fa.delta_rows(do, o), True, s_real, 0.125, False, **kw
        )
        return o, dq, dk, dv

    text = jax.jit(step).lower(blk, blk, blk, blk, off, off).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_the_hybrid_cells_call_compiles_stacked_for_v5e(one_chip, monkeypatch):
    """32 heads of 128 over 8,192 tokens under the stacked backend's ``vmap``
    (a worker axis of 1): the dk/dv kernel keeps a head's whole q, do and
    lane-replicated lse, 16 MB double-buffered, and the compiler refused it
    at the default scoped VMEM (17.00M of 16.00M) until the launch asked for
    what it keeps. The benchmark's 1,024-token call asks for nothing."""
    asked = []
    real = fa.pl.pallas_call

    def spy(*args, **kwargs):
        asked.append(getattr(kwargs.get("compiler_params"), "vmem_limit_bytes", None))
        return real(*args, **kwargs)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    monkeypatch.setattr(fa, "_TRACED", {})  # trace anew: the spy sees every launch
    x = jax.ShapeDtypeStruct((1, 1, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.vmap(_grads(True, False))).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert len(asked) == 3 and all(a is not None and a > 16 * 2**20 for a in asked)
    del asked[:]
    short = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16, sharding=one_chip)
    jax.jit(_grads(True, False)).lower(short, short, short)
    assert asked == [None, None, None]


def _latent_grads(q, k, v):
    """Latent attention's call: keys 192 wide, values 128, the yarn scale."""
    scale = 192**-0.5 * 1.4159**2

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, scale=scale).astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("seq, wrap", [(4096, "alone"), (4096, "vmap"), (4096, "remat"), (1024, "alone")])
def test_keys_192_wide_beside_values_128_wide_compile_for_v5e(one_chip, monkeypatch, seq, wrap):
    """The latent cell's call (1 x 4,096 tokens, 32 heads, d_k 192 = a lane tile
    and a half, d_v 128), alone, under the stacked backend's ``vmap`` and under
    remat, and the straight-line schedule at 1,024 tokens: 192-wide blocks are
    as wide as their arrays, so Mosaic takes them as they are, inside the VMEM
    the launch asks for."""
    monkeypatch.setattr(fa, "_TRACED", {})
    lead = (1,) if wrap == "vmap" else ()
    qk = jax.ShapeDtypeStruct(lead + (1, seq, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct(lead + (1, seq, 32, 128), jnp.bfloat16, sharding=one_chip)
    f = {"alone": _latent_grads, "vmap": jax.vmap(_latent_grads),
         "remat": jax.checkpoint(_latent_grads)}[wrap]
    text = jax.jit(f).lower(qk, qk, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_the_latent_cells_attention_block_compiles_for_v5e(one_chip, monkeypatch, backend):
    """The ``L`` block of ``xing4_ep8.solo_4k`` on four hyper-connected streams,
    rematted forward and backward, as both backends run it (the stacked one's
    ``vmap``, the collective one's checked ``shard_map``): flash
    attention's kernels (the forward one twice under remat) keep the BLOCK's
    name ``h_<i>``: the spans around them (``mla.rope``, ``mla.out_proj``,
    ``mhc.pre``, ``mhc.post``) may not become their scope. The residual path's
    four kernels (``models/hyper_connections.py``) are told apart by their own
    names, at the tiles and the VMEM they ask for."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from consensusml_tpu.models import attention, hyper_connections
    from consensusml_tpu.models import nemotron_h as decoder

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(hyper_connections, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_TRACED", {})
    c = decoder.xing4_share().config
    block = decoder._Block(c, "L", 2, name="h_2")
    x = jax.ShapeDtypeStruct((1, 1, c.streams, 4096, c.hidden), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: jax.vmap(lambda k: block.init(k, jnp.zeros(x.shape[1:], x.dtype))["params"])(
            jax.random.split(jax.random.key(0), 1)))
    monkeypatch.setattr(hyper_connections, "_TRACED", {})  # (the initialisation traced the forward kernels too)

    def grads(p, x):
        run = jax.checkpoint(lambda p, x: block.apply({"params": p}, x)[0])
        return jax.grad(lambda p, x: jnp.sum(run(p, x).astype(jnp.float32) ** 2), argnums=(0, 1))(p, x)

    if backend == "vmap":
        step, sharding = jax.vmap(grads), one_chip
    else:
        mesh = Mesh(np.asarray(list(one_chip.device_set)), ("w",))
        one = lambda t: jax.tree.map(lambda a: a[0], t)
        step = jax.shard_map(
            lambda p, x: jax.tree.map(lambda a: a[None], grads(one(p), one(x))),
            mesh=mesh, in_specs=P("w"), out_specs=P("w"))
        sharding = NamedSharding(mesh, P("w"))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), t)
    compiled = jax.jit(step).lower(place(params), place(x)).compile()
    kernels = _kernel_names(compiled.as_text())
    flash = [k for k in kernels if re.fullmatch(r"(vmap_)?(jvp_)?h_\d*_*", k)]
    # the residual path's: the read twice (forward and rematted), the write ONCE (its residuals are its
    # inputs, so the rematted one has no live output), named for themselves: none reads as ``h_<i>``
    mixing = sorted(k for k in kernels if k not in flash)
    assert len(flash) == 4, kernels
    assert mixing == ["mhc_read_bwd", "mhc_read_fwd", "mhc_read_fwd", "mhc_write_bwd", "mhc_write_fwd"], kernels
    assert not any(re.match(r"h_\d", k) for k in mixing)
    assert sorted(k[0] for k in hyper_connections._TRACED) == [
        "mhc_read_bwd", "mhc_read_fwd", "mhc_write_bwd", "mhc_write_fwd"]  # one trace each
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def _kernel_names(text):
    """The Mosaic custom calls of a compiled program, by instruction name less its number."""
    import re

    return [
        re.sub(r"[.\d]+$", "", name)
        for name, rest in re.findall(r"%([\w.\-]+) = ([^\n]*)", text)
        if " custom-call(" in rest and "tpu_custom_call" in rest
    ]


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_the_delta_cells_attention_block_compiles_for_v5e(one_chip, monkeypatch, backend):
    """The ``A`` block of ``qwen3_next_ep16.solo_8k`` (8,192 tokens, 16 query
    heads of 256 on 2 KV heads, rotary on 64 of 256, the output gated),
    rematted forward and backward, as both backends run it: flash attention's
    three kernels (the forward one twice under remat) take d = 256 inside the
    VMEM they ask for, and their device ops keep the BLOCK's name ``h_<i>``,
    which is how ``flash_attn_roofline.train`` finds them: the spans around
    them (``attn.qk_norm_rope``, ``attn.gate``) may not become their scope."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from consensusml_tpu.models import attention
    from consensusml_tpu.models import nemotron_h as decoder

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_TRACED", {})
    c = decoder.qwen3_next_share().config
    block = decoder._Block(c, "A", 6, name="h_6")
    x = jax.ShapeDtypeStruct((1, 1, 8192, c.hidden), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: jax.vmap(lambda k: block.init(k, jnp.zeros(x.shape[1:], x.dtype))["params"])(
            jax.random.split(jax.random.key(0), 1)))

    def grads(p, x):
        run = jax.checkpoint(lambda p, x: block.apply({"params": p}, x)[0])
        return jax.grad(lambda p, x: jnp.sum(run(p, x).astype(jnp.float32) ** 2), argnums=(0, 1))(p, x)

    if backend == "vmap":
        step, sharding = jax.vmap(grads), one_chip
    else:
        mesh = Mesh(np.asarray(list(one_chip.device_set)), ("w",))
        one = lambda t: jax.tree.map(lambda a: a[0], t)
        step = jax.shard_map(
            lambda p, x: jax.tree.map(lambda a: a[None], grads(one(p), one(x))),
            mesh=mesh, in_specs=P("w"), out_specs=P("w"))
        sharding = NamedSharding(mesh, P("w"))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), t)
    kernels = _kernel_names(jax.jit(step).lower(place(params), place(x)).compile().as_text())
    # ("vmap_jvp_h_6__": this test's bare jax.checkpoint; under the decoder's nn.remat all read h_6)
    assert len(kernels) == 4 and all(re.fullmatch(r"(vmap_)?(jvp_)?h_\d*_*", k) for k in kernels), kernels


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_the_delta_cells_mixer_compiles_for_v5e(one_chip, monkeypatch, backend):
    """A ``G`` block's mixer of the same cell (8,192 tokens, 16 key and 32 value
    heads of 128, the convolution over 8,192 channels, chunks of 64), forward,
    rematted forward and backward, as both backends run it: the delta rule is
    the fused pair, three kernels in all (the forward one twice: the rematted
    one saves the state that enters each chunk), named for their own scopes and
    not for the block (``h_<i>`` is how flash attention's are found); the
    blocked inverse's products at full float32 precision and its substitution
    steps inside what Mosaic takes, at the default scoped VMEM."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from consensusml_tpu.models import gated_delta

    asked = []
    real = gated_delta.pl.pallas_call

    def spy(*args, **kwargs):
        asked.append(kwargs.get("compiler_params"))
        return real(*args, **kwargs)

    monkeypatch.setattr(gated_delta.pl, "pallas_call", spy)
    monkeypatch.setattr(gated_delta, "on_tpu", lambda: True)
    monkeypatch.setattr(gated_delta, "_TRACED", {})
    mixer = gated_delta.GatedDeltaNetMixer(gated_delta.GatedDeltaConfig())
    u = jax.ShapeDtypeStruct((1, 1, 8192, 2048), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: jax.vmap(lambda k: mixer.init(k, jnp.zeros(u.shape[1:], u.dtype))["params"])(
            jax.random.split(jax.random.key(0), 1)))
    monkeypatch.setattr(gated_delta, "_TRACED", {})

    def grads(p, u):
        @jax.checkpoint
        def block(p, u):
            with jax.named_scope("h_0"):
                return mixer.apply({"params": p}, u)[0]

        return jax.grad(lambda p, u: jnp.sum(block(p, u).astype(jnp.float32) ** 2), argnums=(0, 1))(p, u)

    if backend == "vmap":
        step, sharding = jax.vmap(grads), one_chip
    else:
        mesh = Mesh(np.asarray(list(one_chip.device_set)), ("w",))
        one = lambda t: jax.tree.map(lambda a: a[0], t)
        step = jax.shard_map(
            lambda p, u: jax.tree.map(lambda a: a[None], grads(one(p), one(u))),
            mesh=mesh, in_specs=P("w"), out_specs=P("w"))
        sharding = NamedSharding(mesh, P("w"))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), t)
    compiled = jax.jit(step).lower(place(params), place(u)).compile()
    assert sorted(_kernel_names(compiled.as_text())) == ["gdn_bwd", "gdn_fwd", "gdn_fwd"]
    assert asked and all(a is None for a in asked)  # the default limit
    assert sorted(k[0] for k in gated_delta._TRACED) == ["gdn_bwd", "gdn_fwd", "gdn_fwd"]  # one trace each
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2**30


def _cells_expert_layer(cell):
    from consensusml_tpu.models import moe

    if cell == "hybrid":  # 8,192 tokens x top-6 = 49,152 buffer rows of 2,688, 8 of 128 experts held
        return moe.HeldExpertsConfig(held=8, score_correction="centred")
    # 8,192 x top-10 = 81,920 buffer rows of 2,048, 32 of 512 small gated experts held: three
    # stacked matrices, groups of about 160 rows under tiles of 256, 81,920 int32 twice in SMEM
    return moe.HeldExpertsConfig(
        hidden=2048, experts=512, held=32, top_k=10, route_scale=1.0, expert_width=512, shared_width=512,
        scores="softmax", activation="swiglu", shared_gate=True, score_correction="centred")


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
@pytest.mark.parametrize("cell", ["hybrid", "delta"])
def test_the_cells_expert_block_compiles_for_v5e(one_chip, monkeypatch, cell, backend):
    """An ``E`` block of the hybrid cell (8,192 tokens x top-6 = 49,152 buffer
    rows of 2,688, 8 of 128 experts held) and of ``qwen3_next_ep16.solo_8k``
    (:func:`_cells_expert_layer`), forward + backward, as both
    backends run it: under the stacked backend's ``vmap`` (megablox's grouped
    products) and inside the collective backend's checked ``shard_map``
    (``lax.ragged_dot``). The row kernels' scalar operands are 49,152 int32 in
    SMEM, their DMAs slice whole (8, 128) tiles: Mosaic refused a row by
    itself here, at no chip time. Every kernel's device op has to carry a name
    of its own: one left under the block's ``h_<i>`` alone would be counted as
    flash attention."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from consensusml_tpu.models import moe

    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    monkeypatch.setattr(moe, "_TRACED", {})
    cfg = _cells_expert_layer(cell)
    layer = moe.HeldExpertsMLP(cfg)
    x = jax.ShapeDtypeStruct((1, 1, 8192, cfg.hidden), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: jax.vmap(lambda k: layer.init(k, jnp.zeros(x.shape[1:], x.dtype))["params"])(
            jax.random.split(jax.random.key(0), 1)))

    def grads(p, x):
        def loss(p, x):
            with jax.named_scope("h_1"):
                y, _ = layer.apply({"params": p}, x)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1))(p, x)

    if backend == "vmap":
        step, sharding = jax.vmap(grads), one_chip
    else:
        mesh = Mesh(np.asarray(list(one_chip.device_set)), ("w",))
        one = lambda t: jax.tree.map(lambda a: a[0], t)
        step = jax.shard_map(
            lambda p, x: jax.tree.map(lambda a: a[None], grads(one(p), one(x))),
            mesh=mesh, in_specs=P("w"), out_specs=P("w"))
        sharding = NamedSharding(mesh, P("w"))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), t)
    text = jax.jit(step).lower(place(params), place(x)).compile().as_text()
    kernels = _kernel_names(text)
    rows = [k for k in kernels if k.startswith("moe_rows_")]
    # forward: gather + combine; backward: the scaled gather with its dots, and the combine
    assert sorted(rows) == ["moe_rows_combine"] * 2 + ["moe_rows_gather"] * 2
    others = set(kernels) - set(rows)
    if backend == "vmap":
        assert others == {"moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs"}
    else:  # XLA's own ragged product, under its own names
        assert others and not [k for k in others if k.startswith("moe_gmm")]
    assert not [k for k in kernels if re.match(r"h_\d+", k)]


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_the_hybrid_cells_mamba_block_compiles_for_v5e(one_chip, monkeypatch, backend):
    """An ``M`` block of the hybrid cell (8,192 tokens, 64 heads of 64 in 8
    groups, state 128, chunk 128), forward, rematted forward and backward, as
    both backends run it: the scan is the fused pair, three kernels in all
    (the forward one twice: the rematted one saves the state that enters each
    chunk), named for their own scopes and neither for the block (``h_<i>`` is
    how flash attention's are found) nor ``moe_gmm*``; none asks for more than
    the default scoped VMEM (a compile that needed more would be refused
    here, as flash attention's dk/dv was at 17.00M of 16.00M)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from consensusml_tpu.models import ssm

    asked = []
    real = ssm.pl.pallas_call

    def spy(*args, **kwargs):
        asked.append(kwargs.get("compiler_params"))
        return real(*args, **kwargs)

    monkeypatch.setattr(ssm.pl, "pallas_call", spy)
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    cfg = ssm.Mamba2Config()
    mixer = ssm.Mamba2Mixer(cfg)
    u = jax.ShapeDtypeStruct((1, 1, 8192, cfg.hidden), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: jax.vmap(lambda k: mixer.init(k, jnp.zeros(u.shape[1:], u.dtype))["params"])(
            jax.random.split(jax.random.key(0), 1)))
    monkeypatch.setattr(ssm, "_TRACED", {})

    def grads(p, u):
        @jax.checkpoint
        def block(p, u):
            with jax.named_scope("h_0"):
                return mixer.apply({"params": p}, u)[0]

        return jax.grad(lambda p, u: jnp.sum(block(p, u).astype(jnp.float32) ** 2), argnums=(0, 1))(p, u)

    if backend == "vmap":
        step, sharding = jax.vmap(grads), one_chip
    else:
        mesh = Mesh(np.asarray(list(one_chip.device_set)), ("w",))
        one = lambda t: jax.tree.map(lambda a: a[0], t)
        step = jax.shard_map(
            lambda p, u: jax.tree.map(lambda a: a[None], grads(one(p), one(u))),
            mesh=mesh, in_specs=P("w"), out_specs=P("w"))
        sharding = NamedSharding(mesh, P("w"))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), t)
    text = jax.jit(step).lower(place(params), place(u)).compile().as_text()
    kernels = _kernel_names(text)
    assert sorted(kernels) == ["ssd_bwd", "ssd_fwd", "ssd_fwd"]
    assert asked and all(a is None for a in asked)  # the default limit
    assert sorted(k[0] for k in ssm._TRACED) == ["ssd_bwd", "ssd_fwd", "ssd_fwd"]  # one trace each
