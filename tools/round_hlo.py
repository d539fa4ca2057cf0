#!/usr/bin/env python3
"""A recipe's full-size round, lowered (and optionally compiled) for a DESCRIBED
TPU v5e: no chip.

    python3 tools/round_hlo.py <recipe> [--compile] [--dump FILE]

from the root of a checkout (the parent's copy runs the same file: ``cd <parent>
&& python3 <this file> ...``). Prints the sha256 of the lowered StableHLO text of
``make_simulated_train_step`` on ``configs.build(recipe, "full", world=1)`` as a
TPU would get it (``on_tpu`` patched true, so the Pallas kernels are in), with
the source-line debug info that JAX embeds in every Mosaic kernel's serialized
body stripped: two trees whose kernels and programs compute the same print the
same hash, whatever lines their frames moved to (with it, an edit ABOVE a kernel
in its file changes a few bytes of every kernel traced below). ``--compile``
also compiles and prints the compiler's memory analysis (arguments, worst-case
workspace): what the round needs on the chip, minutes here, no chip time.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("recipe")
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax._src import tpu_custom_call
    from jax._src.lib.mlir import passmanager
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)  # a compile for a described chip cannot be read back
    device = SingleDeviceSharding(topo.devices[0])
    serialize = tpu_custom_call._lower_to_custom_call_config

    def stripped(module, *a, **k):
        passmanager.PassManager.parse(
            "builtin.module(strip-debuginfo)", context=module.context).run(module.operation)
        return serialize(module, *a, **k)

    tpu_custom_call._lower_to_custom_call_config = stripped
    import importlib

    for name in ("pallas_util", "models.attention", "models.moe", "models.ssm", "models.gated_delta", "models.hyper_connections",
                 "compress.kernels"):
        try:
            module = importlib.import_module(f"consensusml_tpu.{name}")
        except ImportError:
            continue
        if hasattr(module, "on_tpu"):
            module.on_tpu = lambda: True
    from consensusml_tpu import configs
    from consensusml_tpu.train import make_simulated_train_step
    from consensusml_tpu.train.local_sgd import TrainState

    bundle = configs.build(args.recipe, "full", world=1)
    cfg = bundle.cfg

    def state(rng):
        params = jax.vmap(bundle.init_params)(jax.random.split(rng, 1))
        return TrainState(
            step=jnp.zeros((1,), jnp.int32), params=params, model_state={},
            opt_state=jax.vmap(cfg.optimizer.init)(params),
            gossip=cfg.engine().init_state({"params": params, "model_state": {}}, world_size=1),
            rng=jax.random.split(jax.random.key(0), 1))

    place = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=device), tree)
    shapes = jax.eval_shape(state, jax.random.key(0))
    print("parameters", sum(x.size for x in jax.tree.leaves(shapes.params)), flush=True)
    t0 = time.time()
    lowered = make_simulated_train_step(cfg, bundle.loss_fn).lower(
        place(shapes), place(next(iter(bundle.batches(1, 0)))))
    text = lowered.as_text()
    print(f"lowered in {time.time() - t0:.1f} s: {len(text)} bytes, sha256 "
          f"{hashlib.sha256(text.encode()).hexdigest()[:16]}", flush=True)
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)
    if args.compile:
        t0 = time.time()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        gb = lambda n: round(n / 1e9, 3)
        print(f"compiled in {time.time() - t0:.1f} s: arguments {gb(m.argument_size_in_bytes)} GB, "
              f"outputs {gb(m.output_size_in_bytes)} (aliased {gb(m.alias_size_in_bytes)}), workspace "
              f"{gb(m.temp_size_in_bytes)}, kernel calls "
              f"{compiled.as_text().count('tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
