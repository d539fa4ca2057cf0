"""Ring flash attention: the Pallas-kernel ring path vs dense attention
on the gathered sequence (interpreter mode on the 8-device CPU mesh)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from consensusml_tpu.models import flash_attention as fa_mod
from consensusml_tpu.models.attention import dot_product_attention
from consensusml_tpu.parallel import ring_flash_attention


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(fa_mod, "_BQ", 16)
    monkeypatch.setattr(fa_mod, "_BK", 16)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


def _run_ring(q, k, v, n, causal):
    mesh = _mesh(n)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(None, "sp"),
        out_specs=P(None, "sp"),
    )
    def f(q, k, v):
        return ring_flash_attention(q, k, v, "sp", causal=causal, interpret=True)

    shard = NamedSharding(mesh, P(None, "sp"))
    return f(*(jax.device_put(x, shard) for x in (q, k, v)))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(causal):
    n, b, s, h, d = 4, 1, 64, 2, 64  # 16 tokens per device
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3)
    )
    want = dot_product_attention(q, k, v, causal=causal, dtype=jnp.float32, impl="dense")
    got = _run_ring(q, k, v, n, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_grads_match_dense(causal):
    n, b, s, h, d = 4, 1, 64, 1, 64
    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3)
    )
    mesh = _mesh(n)
    shard = NamedSharding(mesh, P(None, "sp"))

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(None, "sp"),
        out_specs=P(None, "sp"),
    )
    def ring_loss_grad(q, k, v):
        # LOCAL loss per device: the global loss is the sum of local
        # losses, and the ring backward already aggregates each kv
        # block's gradient across all devices' cotangents
        def loss(q, k, v):
            o = ring_flash_attention(q, k, v, "sp", causal=causal, interpret=True)
            return jnp.sum(o**2)

        # grads come back sequence-sharded, like the inputs
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_ring = ring_loss_grad(
        *(jax.device_put(x, shard) for x in (q, k, v))
    )

    def dense_loss(q, k, v):
        o = dot_product_attention(q, k, v, causal=causal, dtype=jnp.float32, impl="dense")
        return jnp.sum(o**2)

    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name}",
        )


def test_ring_flash_padded_blocks():
    # per-device block (12) not a multiple of the kernel blocks (16)
    n, b, s, h, d = 4, 1, 48, 1, 64
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3)
    )
    want = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense")
    got = _run_ring(q, k, v, n, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_ring_flash_rejects_mismatched_blocks():
    q = jnp.zeros((1, 16, 1, 64))
    k = jnp.zeros((1, 32, 1, 64))
    with pytest.raises(ValueError, match="equal block shapes"):
        ring_flash_attention(q, k, k, "sp")


def test_ring_flash_padded_blocks_grads():
    """Backward through padded per-device blocks (s_blk=12 < block=16):
    the zero-do padded rows must contribute nothing to dq/dk/dv."""
    n, b, s, h, d = 4, 1, 48, 1, 64
    rng = np.random.default_rng(6)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3)
    )
    mesh = _mesh(n)
    shard = NamedSharding(mesh, P(None, "sp"))

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(None, "sp"),
        out_specs=P(None, "sp"),
    )
    def ring_grads(q, k, v):
        def loss(q, k, v):
            o = ring_flash_attention(q, k, v, "sp", causal=True, interpret=True)
            return jnp.sum(o**2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_ring = ring_grads(*(jax.device_put(x, shard) for x in (q, k, v)))

    def dense_loss(q, k, v):
        o = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense")
        return jnp.sum(o**2)

    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name}",
        )


def test_ring_flash_picks_the_plain_body_at_run_time():
    """32 tokens a device = 2 x 2 tiles of 16 per visiting block: a block
    from a lower rank lies wholly under the diagonal (four plain tiles,
    chosen from the SMEM offsets with ``aligned=False``), the device's own
    block has two diagonal tiles, one below and one above. Forward and all
    three gradients against dense, at the tolerances of the cases above."""
    from consensusml_tpu.obs import get_registry

    n, b, s, h, d = 4, 1, 128, 1, 64
    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3)
    )
    mesh = _mesh(n)
    shard = NamedSharding(mesh, P(None, "sp"))
    runtime = {
        kernel: get_registry().counter(
            "consensusml_flash_tiles_total",
            labels={"kernel": kernel, "kind": "runtime"},
        )
        for kernel in ("fwd", "bwd")
    }
    before = {kernel: c.value for kernel, c in runtime.items()}

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(None, "sp"),
        out_specs=P(None, "sp"),
    )
    def ring(q, k, v):
        def loss(q, k, v):
            o = ring_flash_attention(q, k, v, "sp", causal=True, interpret=True)
            return jnp.sum(o**2), o

        grads, o = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return o, grads

    got, g_ring = ring(*(jax.device_put(x, shard) for x in (q, k, v)))
    # the ring loop's body is traced once: 4 tiles a kernel a head
    assert {k_: c.value - before[k_] for k_, c in runtime.items()} == {
        "fwd": 4, "bwd": 4,
    }

    def dense_loss(q, k, v):
        o = dot_product_attention(q, k, v, causal=True, dtype=jnp.float32, impl="dense")
        return jnp.sum(o**2), o

    g_dense, want = jax.grad(dense_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)
    for name, a, b_ in zip("qkv", g_ring, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name}",
        )
