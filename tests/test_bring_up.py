"""What the chip bring-up changed, checked where no chip is needed: the
compile cache's one rule, in-process device refusals, placement that
refuses instead of idling chips, kernels under the production shard_map,
and the selectors that now say what they picked."""

import dataclasses
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from consensusml_tpu.comm import WorkerMesh
from consensusml_tpu.compress import (
    ChunkedTopKCompressor,
    describe_codec,
    topk_int8_compressor,
)
from consensusml_tpu.consensus import GossipConfig
from consensusml_tpu.topology import RingTopology
from consensusml_tpu.train import (
    LocalSGDConfig,
    init_stacked_state,
    make_collective_train_step,
    make_simulated_train_step,
)


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env_and_sets_nothing(
    monkeypatch, tmp_path, restore_cache_dir
):
    from consensusml_tpu import compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper set no directory in code
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_fixed_checkout_path(
    monkeypatch, restore_cache_dir
):
    from consensusml_tpu import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the path is part of the cache key: it must not depend on the call
    assert compile_cache.enable_compile_cache() == want


# ---------------------------------------------------------------------------
# train.py: device and placement
# ---------------------------------------------------------------------------


def test_device_tpu_without_tpu_exits_2_in_process(monkeypatch, capsys):
    """No liveness child: a chip belongs to one process, so the check is
    this process's own default backend."""
    import train

    def no_children(*a, **k):
        raise AssertionError("--device tpu spawned a child process")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(subprocess, "run", no_children)
    assert train.main(["--config", "mnist_mlp", "--device", "tpu"]) == 2
    assert "no TPU reachable" in capsys.readouterr().err


def test_backend_auto_refuses_to_idle_chips():
    from train import resolve_backend

    # four chips, the config's default world of eight: refuse, name the fix
    backend, refusal = resolve_backend("auto", "tpu", 4, 8)
    assert refusal and "--workers 4" in refusal and "8 workers" in refusal
    # with a tp=2 submesh per worker, two workers fill the host
    _, refusal = resolve_backend("auto", "tpu", 4, 8, per_worker=2)
    assert "--workers 2" in refusal
    # enough devices: collective; one device (or the CPU): stacking is the
    # only layout there is, so auto may pick it
    assert resolve_backend("auto", "tpu", 8, 8) == ("collective", None)
    assert resolve_backend("auto", "tpu", 1, 8) == ("simulated", None)
    assert resolve_backend("auto", "cpu", 4, 8) == ("simulated", None)
    # an explicit choice is never second-guessed
    assert resolve_backend("simulated", "tpu", 4, 8) == ("simulated", None)


# ---------------------------------------------------------------------------
# kernels under the production shard_map
# ---------------------------------------------------------------------------


def _mlp_problem(comp, world=4):
    from consensusml_tpu.models import MLP, mlp_loss_fn

    topo = RingTopology(world)
    model = MLP(hidden=64)
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo, compressor=comp, gamma=0.5),
        optimizer=optax.sgd(0.05),
        h=2,
    )
    init = lambda r: model.init(r, jnp.zeros((1, 8, 8, 1)))["params"]
    rng = np.random.default_rng(0)
    batches = [
        {
            "image": jnp.asarray(
                rng.normal(size=(world, 2, 4, 8, 8, 1)), jnp.float32
            ),
            "label": jnp.asarray(
                rng.integers(0, 10, size=(world, 2, 4)), jnp.int32
            ),
        }
        for _ in range(2)
    ]
    return topo, cfg, mlp_loss_fn(model), init, batches


def test_collective_step_with_interpreted_codec_matches_simulated():
    """The codec kernels (top-k select, chunk scatter, int8 quantize) run
    INSIDE the train step's shard_map exactly as production builds it —
    check_vma on, nothing hand-set — and agree with the stacked backend.
    At the parent commit this died at trace time (out_shape without vma,
    then the loop-carry type check inside the top-k kernel)."""
    comp = topk_int8_compressor(ratio=0.1, chunk=128, impl="interpret")
    assert "select=interpret scatter=interpret" in describe_codec(comp)
    topo, cfg, loss_fn, init, batches = _mlp_problem(comp)
    wmesh = WorkerMesh.create(topo, devices=jax.devices()[:4])
    step_c = make_collective_train_step(cfg, loss_fn, wmesh)
    step_s = make_simulated_train_step(cfg, loss_fn)
    state_c = wmesh.shard_stacked(init_stacked_state(cfg, init, jax.random.key(0), 4))
    state_s = init_stacked_state(cfg, init, jax.random.key(0), 4)
    for b in batches:
        state_c, mc = step_c(state_c, wmesh.shard_stacked(b))
        state_s, ms = step_s(state_s, b)
        np.testing.assert_allclose(float(mc["loss"]), float(ms["loss"]), rtol=1e-5)
        np.testing.assert_allclose(
            float(mc["consensus_error"]), float(ms["consensus_error"]), rtol=1e-4
        )
    assert float(mc["consensus_error"]) > 0
    for pc, ps in zip(jax.tree.leaves(state_c.params), jax.tree.leaves(state_s.params)):
        np.testing.assert_allclose(np.asarray(pc), np.asarray(ps), rtol=2e-5, atol=1e-6)


def test_pp_refuses_the_bucketed_compressed_wire():
    """CHOCO's per-bucket state is laid out for the whole tree and cannot
    shard over the stage axis: refused when the step is built, with the
    option that works in the message (it used to die mid-trace on a
    bucket-layout mismatch)."""
    from consensusml_tpu.parallel import pipeline_pp_rules

    topo = RingTopology(2)
    wmesh = WorkerMesh.create(
        topo, devices=jax.devices()[:4],
        model_axes=(("pp", 2),), manual_model_axes=("pp",),
    )
    cfg = LocalSGDConfig(
        gossip=GossipConfig(
            topology=topo,
            compressor=ChunkedTopKCompressor(chunk=128, k_per_chunk=8),
            gamma=0.5,
        ),
        optimizer=optax.sgd(0.1),
        h=1,
    )
    with pytest.raises(NotImplementedError, match="bucket_bytes=0"):
        make_collective_train_step(
            cfg, lambda *a: None, wmesh, rules=pipeline_pp_rules()
        )
    # the per-leaf wire builds
    make_collective_train_step(
        dataclasses.replace(cfg, bucket_bytes=0), lambda *a: None, wmesh,
        rules=pipeline_pp_rules(),
    )


# ---------------------------------------------------------------------------
# selectors that say what they picked, tables that refuse what they lack
# ---------------------------------------------------------------------------


def test_describe_codec_names_what_runs(monkeypatch):
    comp = topk_int8_compressor(chunk=512, k=8, impl="auto")
    off_chip = describe_codec(comp)
    assert "select=jnp scatter=jnp" in off_chip and "PallasInt8Compressor/512 jnp" in off_chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = describe_codec(comp)
    assert "select=pallas scatter=pallas" in on_chip
    assert "PallasInt8Compressor/512 pallas" in on_chip
    # past the kernel's pass budget the winners come from one sort per
    # chunk — still chosen from k, but now it shows
    big = ChunkedTopKCompressor(chunk=256, k_per_chunk=128, impl="auto")
    assert "select=jnp scatter=pallas" in describe_codec(big)


def test_unknown_device_kind_has_no_roofline():
    from consensusml_tpu.obs import MetricsRegistry
    from consensusml_tpu.obs.costs import CostLedger, device_peaks

    assert device_peaks("TPU v5 lite")[0] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        CostLedger(registry=MetricsRegistry(), device_kind="TPU v9")
    # all three peaks given: the table is not consulted
    led = CostLedger(
        registry=MetricsRegistry(), device_kind="TPU v9",
        peak_flops_per_s=1e12, peak_bytes_per_s=1e11,
        peak_transfer_bytes_per_s=1e10,
    )
    assert led.peak_flops_per_s == 1e12


def test_replica_set_refuses_subprocess_replicas_on_a_tpu_host():
    """Every child inherits every chip and a chip belongs to one process:
    the first child's reported platform decides, the parent stays off jax."""
    from consensusml_tpu.fleet import ReplicaSet, SubprocessReplica

    class Fake(SubprocessReplica):
        spawned = killed = 0

        def spawn(self, block=True, timeout=300.0):
            type(self).spawned += 1
            self.platform = "tpu"

        def kill(self):
            type(self).killed += 1

    fleet = ReplicaSet([Fake("/nonexistent", name=f"r{i}") for i in range(3)])
    with pytest.raises(RuntimeError, match="one process"):
        fleet.spawn_all(block=False)
    assert (Fake.spawned, Fake.killed) == (1, 1)  # siblings never started
