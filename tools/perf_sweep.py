"""Perf sweep for the headline ResNet-50 bench (one variant per subprocess).

Drives the same measurement as bench.py (scan-of-steps inside one jit,
host value fetch as the timing fence — an execution barrier on every
backend)
across configuration variants, to locate the throughput sinks
profile-style without hand-reading traces first:

  path  : sim  — the bench's make_simulated_train_step (vmap over 1 worker)
          raw  — plain jitted fwd+bwd+SGD step, no vmap/gossip wrapper
  batch : images per step
  bn    : f32 | bf16 — flax BatchNorm at that elementwise dtype
          fused      — the Pallas fused BN(+ReLU) kernels (norm_impl auto)
          fusedw     — fused kernels only where C>=128 (XLA-preferred
                       layouts; C<128 layers stay on the XLA path)

Usage:  python tools/perf_sweep.py sim:128:f32 raw:256:bf16 ...
Each spec runs in a fresh subprocess (clean XLA client, honest compile).

Fed-input mode (`--fed-input`, ISSUE 3): sweeps the overlapped
host→device feed — native ring ``depth x nthreads x wire [x prefetch]``
— around the training step, one fresh subprocess per variant, and emits
a JSON table (`FED_TABLE [...]`) of imgs/sec + feed-stall/overlap so
the input-pipeline knobs are located by measurement, not folklore:

  python tools/perf_sweep.py --fed-input              # default grid
  python tools/perf_sweep.py --fed-input 4:4:u8 6:8:u8:3 4:4:f32:0

Spec: depth:nthreads:wire[:prefetch] (prefetch default 2; 0 = overlap
off, the A/B baseline). Env knobs: SWEEP_FED_BATCH / SWEEP_FED_IMAGE /
SWEEP_FED_STEPS / SWEEP_FED_MODEL (resnet50 | tiny — tiny is the CPU
CI smoke, exercised by tests/test_prefetch.py).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)  # script lives in tools/, package at repo root


def run_variant(path: str, batch: int, bn: str, steps: int, image: int) -> dict:
    import functools

    import jax

    if os.environ.get("BENCH_DEVICE"):
        jax.config.update("jax_platforms", os.environ["BENCH_DEVICE"])
    import jax.numpy as jnp
    import numpy as np
    import optax

    from consensusml_tpu.models import resnet50, resnet_loss_fn

    model = resnet50(
        num_classes=1000,
        stem="imagenet",
        dtype=jnp.bfloat16,
        norm_dtype=jnp.float32 if bn == "f32" else None,
        norm_impl="auto" if bn in ("fused", "fusedw") else "flax",
        norm_pack_small=bn != "fusedw",
    )
    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.normal(size=(batch, image, image, 3)), jnp.bfloat16
    )
    labels = jnp.asarray(rng.integers(0, 1000, size=(batch,)), jnp.int32)
    loss_fn = resnet_loss_fn(model)
    tx = optax.sgd(0.1, momentum=0.9)

    if path == "raw":
        variables = model.init(jax.random.key(0), images[:1], train=True)
        params = variables["params"]
        mstate = {k: v for k, v in variables.items() if k != "params"}
        opt_state = tx.init(params)
        carry0 = (params, mstate, opt_state, jax.random.key(1))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def multi_step(carry):
            def body(c, _):
                params, mstate, opt_state, key = c
                key, sub = jax.random.split(key)
                (loss, mstate), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, mstate, {"image": images, "label": labels}, sub)
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, mstate, opt_state, key), loss

            return jax.lax.scan(body, carry, None, length=steps)

        t0 = time.time()
        carry, losses = multi_step(carry0)
        warm = float(losses[-1])
        compile_s = time.time() - t0
        t0 = time.time()
        carry, losses = multi_step(carry)
        final = float(losses[-1])
        dt = time.time() - t0
    else:  # sim — the exact bench path
        from consensusml_tpu.consensus import GossipConfig
        from consensusml_tpu.models import resnet_init
        from consensusml_tpu.topology import RingTopology
        from consensusml_tpu.train import (
            LocalSGDConfig,
            init_stacked_state,
            make_simulated_train_step,
        )

        cfg = LocalSGDConfig(
            gossip=GossipConfig(topology=RingTopology(1)), optimizer=tx, h=1
        )
        step = make_simulated_train_step(cfg, loss_fn)
        state = init_stacked_state(
            cfg, resnet_init(model, (1, image, image, 3)), jax.random.key(0), 1
        )
        batch_data = {
            "image": images[None, None],
            "label": labels[None, None],
        }

        @functools.partial(jax.jit, donate_argnums=(0,))
        def multi_step(state):
            def body(s, _):
                s, m = step(s, batch_data)
                return s, m["loss"]

            return jax.lax.scan(body, state, None, length=steps)

        t0 = time.time()
        state, losses = multi_step(state)
        warm = float(losses[-1])
        compile_s = time.time() - t0
        t0 = time.time()
        state, losses = multi_step(state)
        final = float(losses[-1])
        dt = time.time() - t0

    return {
        "variant": f"{path}:{batch}:{bn}",
        "imgs_sec": round(batch * steps / dt, 1),
        "step_ms": round(1000 * dt / steps, 2),
        "compile_s": round(compile_s, 1),
        "platform": jax.default_backend(),
        "loss": round(final, 4),
        "warm_loss": round(warm, 4),
    }


def run_fed_variant(
    depth: int, nthreads: int, wire: str, prefetch: int,
    batch: int, image: int, steps: int, model_kind: str,
) -> dict:
    """One fed-input variant: the bench's fed protocol (per-round feed +
    jitted step, one completion fetch as the fence) through
    ``native_cls_feed`` with explicit ring/prefetch knobs."""
    import functools

    import jax

    if os.environ.get("BENCH_DEVICE"):
        jax.config.update("jax_platforms", os.environ["BENCH_DEVICE"])
    import jax.numpy as jnp
    import numpy as np
    import optax

    from consensusml_tpu.consensus import GossipConfig
    from consensusml_tpu.data import SyntheticClassification, native_cls_feed
    from consensusml_tpu.models import resnet50, resnet_init, resnet_loss_fn
    from consensusml_tpu.models.resnet import BottleneckBlock, ResNet
    from consensusml_tpu.topology import RingTopology
    from consensusml_tpu.train import (
        LocalSGDConfig,
        init_stacked_state,
        make_simulated_train_step,
    )

    classes = 1000 if model_kind == "resnet50" else 10
    if model_kind == "resnet50":
        model = resnet50(
            num_classes=classes, stem="imagenet", dtype=jnp.bfloat16
        )
    else:  # tiny: the smoke-scale ResNet (fast CPU CI)
        model = ResNet(
            stage_sizes=[1, 1], block=BottleneckBlock, num_classes=classes,
            width=8, stem="cifar", dtype=jnp.float32,
        )
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=RingTopology(1)),
        optimizer=optax.sgd(0.1, momentum=0.9),
        h=1,
    )
    base_step = make_simulated_train_step(cfg, resnet_loss_fn(model))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def f32_step(state, batch_data):
        new_state, metrics = base_step(state, batch_data)
        return new_state, metrics["loss"]

    qscale = SyntheticClassification.U8_QSCALE
    qoff = SyntheticClassification.U8_QOFF

    @functools.partial(jax.jit, donate_argnums=(0,))
    def u8_step(state, batch_data):
        # device-side dequant of the u8 wire, fused into the round
        img = jnp.asarray(batch_data["image"], model.dtype) / qscale - qoff
        new_state, metrics = base_step(state, dict(batch_data, image=img))
        return new_state, metrics["loss"]

    step = u8_step if wire == "u8" else f32_step
    data = SyntheticClassification(
        n=64, image_shape=(image, image, 3), classes=classes
    )

    def feed(n):
        return native_cls_feed(
            data, 1, 1, batch, n, wire=wire, qscale=qscale, qoff=qoff,
            prefetch=prefetch, depth=depth, nthreads=nthreads,
        )

    state = init_stacked_state(
        cfg, resnet_init(model, (1, image, image, 3)), jax.random.key(0), 1
    )
    loss = None
    warm = feed(2)  # warm: compile + one steady-state round
    try:
        for b in warm:
            state, loss = step(state, b)
        float(loss)
        pf = feed(steps)
        try:
            t0 = time.time()
            for b in pf:
                state, loss = step(state, b)
            final = float(loss)  # single completion fence: pipelined feed
            dt = time.time() - t0
        finally:
            getattr(pf, "close", lambda: None)()
    finally:
        # a step() exception must not orphan the prefetch thread + ring
        getattr(warm, "close", lambda: None)()
    # overlap stats exist only when a prefetcher ran; the prefetch=0
    # baseline reports null rather than a fake 100% overlap
    stall = getattr(pf, "stall_seconds_total", None)
    return {
        "variant": f"{depth}:{nthreads}:{wire}:{prefetch}",
        "depth": depth,
        "nthreads": nthreads,
        "wire": wire,
        "prefetch": prefetch,
        "imgs_sec": round(batch * steps / dt, 1),
        "feed_stall_s_total": None if stall is None else round(stall, 4),
        "prefetch_overlap_pct": (
            None
            if stall is None
            else round(100.0 * (1.0 - min(1.0, stall / dt)), 1)
        ),
        "platform": jax.default_backend(),
        "loss": round(final, 4),
    }


_FED_DEFAULT_GRID = [
    # depth:nthreads:wire:prefetch — the plan_ring neighborhood plus the
    # overlap-off and f32-wire baselines
    "4:2:f32:0", "4:2:u8:0", "4:2:u8:2", "4:4:u8:2", "4:8:u8:2", "6:8:u8:4",
]


def _fed_main(argv: list[str]) -> None:
    if "--_fed_one" in argv:
        spec = argv[argv.index("--_fed_one") + 1]
        parts = spec.split(":")
        depth, nthreads, wire = int(parts[0]), int(parts[1]), parts[2]
        prefetch = int(parts[3]) if len(parts) > 3 else 2
        out = run_fed_variant(
            depth, nthreads, wire, prefetch,
            batch=int(os.environ.get("SWEEP_FED_BATCH", "128")),
            image=int(os.environ.get("SWEEP_FED_IMAGE", "224")),
            steps=int(os.environ.get("SWEEP_FED_STEPS", "12")),
            model_kind=os.environ.get("SWEEP_FED_MODEL", "resnet50"),
        )
        print("FED_RESULT " + json.dumps(out), flush=True)
        return

    specs = [a for a in argv if ":" in a] or _FED_DEFAULT_GRID
    table = []
    for spec in specs:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--_fed_one", spec],
            capture_output=True,
            text=True,
            timeout=float(os.environ.get("SWEEP_TIMEOUT", "1200")),
            cwd=REPO,
        )
        out = [
            l for l in proc.stdout.splitlines() if l.startswith("FED_RESULT ")
        ]
        if out:
            row = json.loads(out[-1][len("FED_RESULT "):])
        else:
            row = {"variant": spec, "error": proc.stderr[-400:]}
        table.append(row)
        print("FED_RESULT " + json.dumps(row), flush=True)
    print("FED_TABLE " + json.dumps(table), flush=True)


def main() -> None:
    if "--fed-input" in sys.argv or "--_fed_one" in sys.argv:
        _fed_main([a for a in sys.argv[1:] if a != "--fed-input"])
        return
    if "--_one" in sys.argv:
        spec = sys.argv[sys.argv.index("--_one") + 1]
        path, batch, bn = spec.split(":")
        steps = int(os.environ.get("SWEEP_STEPS", "20"))
        image = int(os.environ.get("SWEEP_IMAGE", "224"))
        print(
            "VARIANT_RESULT "
            + json.dumps(run_variant(path, int(batch), bn, steps, image)),
            flush=True,
        )
        return

    specs = [a for a in sys.argv[1:] if ":" in a]
    for spec in specs:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--_one", spec],
            capture_output=True,
            text=True,
            timeout=float(os.environ.get("SWEEP_TIMEOUT", "1200")),
            cwd=REPO,
        )
        out = [
            l for l in proc.stdout.splitlines() if l.startswith("VARIANT_RESULT ")
        ]
        if out:
            print(out[-1][len("VARIANT_RESULT "):], flush=True)
        else:
            print(
                json.dumps(
                    {"variant": spec, "error": proc.stderr[-400:]}
                ),
                flush=True,
            )


if __name__ == "__main__":
    main()
