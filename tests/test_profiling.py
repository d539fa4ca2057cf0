"""Tracing/profiling subsystem: timer stats, fences, xprof trace dump,
and the --profile-dir CLI path."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensusml_tpu.utils import RoundTimer, fence, trace

pytestmark = pytest.mark.profiling


def test_round_timer_separates_warmup_and_steady_state():
    timer = RoundTimer(warmup=1)
    for i in range(4):
        with timer.lap():
            time.sleep(0.05 if i == 0 else 0.01)
    stats = timer.stats()
    assert stats.count == 3  # warmup lap excluded
    assert 0.005 < stats.p50_s < 0.05
    assert stats.max_s < 0.05  # the slow compile lap is not in steady state
    assert "p95" in stats.format()


def test_round_timer_fences_on_metrics():
    @jax.jit
    def slow(x):
        return jnp.sum(x * x)

    timer = RoundTimer(warmup=0)
    metrics = {}
    x = jnp.ones((256, 256))
    with timer.lap(metrics_fn=lambda: metrics):
        metrics = {"loss": slow(x)}
    assert timer.stats().count == 1
    assert np.isfinite(timer.stats().mean_s)


def test_fence_handles_trees_and_empty():
    fence({})
    fence({"a": jnp.ones((3,)), "b": [jnp.zeros(())]})


def test_round_timer_fence_is_the_round_fence_span(global_ring):
    """The lap's fence is a span where the wait happens, not a duration
    back-dated by the loop: it sits inside its round and inherits it."""
    timer = RoundTimer(warmup=0)
    metrics = {}
    with global_ring.span("train.round", round=2):
        with timer.lap(metrics_fn=lambda: metrics):
            metrics = {"loss": jnp.sum(jnp.ones((8, 8)))}
    # a first use compiles the sum and the fence's slice: where an earlier
    # test installed the compile log those are jax.* spans in the ring too,
    # and the collector's hook beside it may add a host.gc pause
    fence_ev, round_ev = (
        e for e in global_ring.events()
        if not e["name"].startswith("jax.") and e["name"] != "host.gc"
    )
    assert fence_ev["name"] == "round.fence" and fence_ev["args"] == {"round": 2}
    assert fence_ev["parent"] == round_ev["id"]
    assert fence_ev["dur_ns"] <= timer.last_fence_s * 1e9 + 1e6


def test_trace_writes_xprof_dump(tmp_path):
    d = str(tmp_path / "prof")
    with trace(d):
        jnp.sum(jnp.ones((64, 64))).block_until_ready()
    dumped = glob.glob(os.path.join(d, "**", "*"), recursive=True)
    assert dumped, "trace produced no files"


def test_cli_profile_dir(tmp_path):
    from train import main

    d = str(tmp_path / "prof")
    rc = main([
        "--config", "mnist_mlp", "--device", "cpu", "--backend", "simulated",
        "--rounds", "6", "--profile-dir", d, "--log-every", "100",
    ])
    assert rc == 0
    assert glob.glob(os.path.join(d, "**", "*"), recursive=True)
