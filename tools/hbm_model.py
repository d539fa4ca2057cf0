"""Analytic per-device HBM accounting for every config x scale (docs/memory.md).

The north star puts full-scale workloads on pods this sandbox does not
have ("ResNet-50 ... on v4-32"; llama_lora tp=4 wants 64 chips;
bert_mlm wants 32). Tracing on a virtual mesh proves SHAPES, not memory
— this tool closes that gap (VERDICT r2 item 6): it predicts per-device
bytes from first principles and is validated on the one real chip.

Components, per device (= one gossip worker, or one tp shard of one):

- state (EXACT, via ``jax.eval_shape`` — no device, no formulas): params,
  model_state (BN stats), optimizer state, gossip state (CHOCO xhat/s,
  overlap correction, push-sum mass), SlowMo outer. Tensor-parallel
  leaves are divided by the product of mesh axes their sharding rule
  names (``parallel.sharding.spec_for_path`` — the same rules the real
  run shards with).
- round batch (exact): one worker's ``(h, B, ...)`` slice.
- codec transients: CHOCO's delta / decompressed-innovation temporaries
  (2x the gossiped subtree in f32) plus payload send+recv buffers
  (``engine.wire_bytes_per_round`` x (1 + number of neighbor shifts)).
- activations (MODELED — the one estimated term): per-family formulas
  below, written against how XLA actually schedules these models (bf16
  saved tensors, f32 softmax/statistics, blockwise/flash attention so no
  S^2 score residuals). Coefficients were fit ONCE against compiled
  per-op accounting on the real chip and are fixed here; the on-TPU test
  (tests/test_hbm_model.py) pins total prediction vs measured peak.

Peak model: the inner loop's activations and the gossip round's codec
transients are live at DIFFERENT times inside one XLA program, so

    peak ~= state + batch + max(activations, codec_transients) + payloads

Usage:
  python tools/hbm_model.py --all --md            # the docs table
  python tools/hbm_model.py --config gpt2_topk --scale full
  python tools/hbm_model.py --config cifar_resnet50 --scale full --measure
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

GIB = 1024**3

# activation-model coefficients (see _transformer_act / _resnet_act).
# Fit once against the real chip's compiled accounting; change only with
# a new measurement in docs/memory.md.
_SAVED_PER_LAYER_HIDDEN = 8  # hidden-sized bf16 residuals saved per layer
_SAVED_PER_LAYER_MLP = 2  # mlp-sized bf16 residuals saved per layer
_HEAD_LOGITS_F32 = 2.0  # logits + softmax/CE residuals, in B*S*V f32 units
# conv output + BN/ReLU residuals, bf16 units; 2.0 a priori, calibrated
# to 1.6 against XLA's compiled buffer assignment for cifar_resnet50
# full on the v5e (docs/memory.md "Validation") — XLA recomputes part of
# the BN/ReLU chain instead of saving it
_RESNET_SAVED_PER_CONV = 1.6


def _tree_bytes(tree, divide=None) -> int:
    import jax

    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        n = math.prod(leaf.shape) if leaf.shape else 1
        if divide is not None:
            n //= divide(path, leaf)
        total += n * leaf.dtype.itemsize
    return total


def _tp_divider(bundle, model_axes):
    """leaf -> tensor-parallel shard count, from the bundle's own rules."""
    if not model_axes or bundle.tp_rules is None:
        return None
    import jax

    from consensusml_tpu.parallel.sharding import spec_for_path

    sizes = dict(model_axes)
    rules = bundle.tp_rules()  # default axis names, as WorkerMesh uses

    def divide(path, leaf) -> int:
        pathstr = jax.tree_util.keystr(path, simple=True, separator="/")
        spec = spec_for_path(pathstr, len(leaf.shape), rules)
        return math.prod(sizes.get(ax, 1) for ax in spec if ax is not None)

    return divide


# ---------------------------------------------------------------------------
# activation models (the estimated term)
# ---------------------------------------------------------------------------


def _transformer_act(
    B, S, hidden, mlp, layers, vocab, heads, mlp_tensors=_SAVED_PER_LAYER_MLP
) -> int:
    """Decoder/encoder activation residuals, bf16 compute.

    Per layer: ~8 hidden-sized tensors (ln outs, qkv, attention out,
    projection, residual adds) + ``mlp_tensors`` mlp-sized ones (2 for a
    GELU stack: mlp_in out + act out; 3 for SwiGLU, whose gate branch
    saves an extra tensor), saved in bf16, plus the attention logsumexp
    (f32 per head-row; the blockwise/flash paths save no S^2 scores).
    Head: logits and the cross-entropy/softmax residuals in f32 — at LM
    vocab sizes this is the dominant single term.
    """
    per_layer = B * S * (
        _SAVED_PER_LAYER_HIDDEN * hidden + mlp_tensors * mlp
    ) * 2 + B * heads * S * 4
    embed = 3 * B * S * hidden * 2
    head = int(_HEAD_LOGITS_F32 * B * S * vocab * 4)
    return layers * per_layer + embed + head


def _resnet_act(model, image: int, B: int) -> int:
    """Walk the architecture: every conv's output map, bf16, times the
    saved-residual coefficient (conv out + BN/ReLU saved tensors)."""
    from consensusml_tpu.models.resnet import BottleneckBlock

    w = model.width
    hw = image
    total = 0  # elements
    if model.stem == "imagenet":
        hw //= 2
        total += hw * hw * w  # 7x7/2 stem conv
        hw //= 2  # maxpool
    else:
        total += hw * hw * w  # 3x3 cifar stem
    bottleneck = model.block is BottleneckBlock
    for i, n_blocks in enumerate(model.stage_sizes):
        feats = w * (2**i)
        if i > 0:
            hw //= 2  # stride-2 entry block
        out_f = 4 * feats if bottleneck else feats
        for b in range(n_blocks):
            if bottleneck:  # 1x1 feats, 3x3 feats, 1x1 4*feats
                total += hw * hw * (feats + feats + out_f)
            else:  # 3x3 feats, 3x3 feats
                total += hw * hw * 2 * feats
            if b == 0:  # projection shortcut
                total += hw * hw * out_f
    return int(_RESNET_SAVED_PER_CONV * B * total * 2)


def _mlp_act(model, B, in_pixels) -> int:
    return B * (in_pixels + model.hidden + 10) * 4 * 2


def _activation_bytes(bundle, shapes) -> int:
    """Dispatch on the bundle's model family."""
    model = bundle.model
    name = type(model).__name__
    B = shapes["batch"]
    if name == "ResNet":
        return _resnet_act(model, shapes["image"], B)
    if name == "MLP":
        return _mlp_act(model, B, shapes["pixels"])
    c = model.config
    mlp = getattr(c, "mlp_dim", None) or 4 * c.hidden
    # SwiGLU (llama) runs three mlp matmuls: the gate branch saves one
    # extra mlp-sized residual over a GELU stack
    mlp_tensors = 3 if name == "LlamaLM" else _SAVED_PER_LAYER_MLP
    return _transformer_act(
        B, shapes["seq"], c.hidden, mlp, c.layers, c.vocab_size, c.heads,
        mlp_tensors=mlp_tensors,
    )


# ---------------------------------------------------------------------------
# the prediction
# ---------------------------------------------------------------------------


def _sample_shapes(bundle) -> dict:
    """Microbatch geometry from one real round batch (worker slice)."""
    batch = next(iter(bundle.batches(1, 0)))
    leaf = batch["image"] if "image" in batch else batch["input_ids"]
    # (W, H, B, ...) stacked layout
    out = {
        "h": leaf.shape[1],
        "batch": leaf.shape[2],
        "batch_bytes": sum(
            math.prod(x.shape[1:]) * x.dtype.itemsize for x in batch.values()
        ),
    }
    if "image" in batch:
        out["image"] = leaf.shape[3]
        out["pixels"] = math.prod(leaf.shape[3:])
    else:
        out["seq"] = leaf.shape[3]
    return out


def predict(
    name: str,
    scale: str = "full",
    world: int | None = None,
    model_axes: tuple[tuple[str, int], ...] | None = None,
) -> dict:
    """Per-device HBM prediction for one config. Pure host computation —
    builds no arrays, touches no accelerator."""
    import jax

    from consensusml_tpu.configs import build

    bundle = build(name, scale, world=world)
    axes = bundle.model_axes if model_axes is None else model_axes
    tp = math.prod(s for _, s in axes) if axes else 1
    divide = _tp_divider(bundle, axes)
    cfg = bundle.cfg
    engine = cfg.engine()

    probe = jax.eval_shape(bundle.init_params, jax.random.key(0))
    params, model_state = (
        probe if isinstance(probe, tuple) and len(probe) == 2 else (probe, {})
    )
    opt_state = jax.eval_shape(cfg.optimizer.init, params)
    gossip = jax.eval_shape(
        lambda p: engine.init_state(
            {"params": p, "model_state": model_state},
            # the probe shapes are PER-WORKER: world_size only matters
            # for the push-sum mass scalar — passing it otherwise would
            # make the fused/bucketed CHOCO state misread the per-worker
            # tree as stacked
            world_size=(
                cfg.gossip.topology.world_size
                if cfg.gossip.push_sum_enabled
                else None
            ),
        ),
        params,
    )
    outer = (
        jax.eval_shape(
            __import__(
                "consensusml_tpu.train.outer", fromlist=["slowmo_init"]
            ).slowmo_init,
            params,
        )
        if cfg.outer is not None
        else None
    )

    state = {
        "params": _tree_bytes(params, divide),
        "model_state": _tree_bytes(model_state, divide),
        "opt": _tree_bytes(opt_state, divide),
        "gossip": _tree_bytes(gossip, divide) if gossip is not None else 0,
        "outer": _tree_bytes(outer, divide) if outer is not None else 0,
    }

    shapes = _sample_shapes(bundle)
    comp = cfg.gossip.compressor
    if comp is not None:
        # the engine gossips {params, model_state} (local_sgd._gossiped)
        gossiped = {"params": params, "model_state": model_state}
        if cfg.gossip.path_filter is not None:
            gossiped, _ = engine._select(gossiped)
        n_gossiped = sum(
            math.prod(x.shape) for x in jax.tree.leaves(gossiped)
        )
        wire = engine.wire_bytes_per_round(
            {"params": params, "model_state": model_state}
        )
        shifts = (
            1
            if cfg.gossip.topology.uses_psum
            else len(cfg.gossip.topology.shifts)
        )
        codec = {
            "codec_temp": 2 * n_gossiped * 4,  # delta + dec(q), f32
            "payloads": wire * (1 + shifts),  # local q + per-neighbor recv
        }
    else:
        codec = {"codec_temp": 0, "payloads": 0}

    act = _activation_bytes(bundle, shapes) // tp
    total = (
        sum(state.values())
        + shapes["batch_bytes"]
        + max(act, codec["codec_temp"])
        + codec["payloads"]
    )
    return {
        "config": name,
        "scale": scale,
        "world": bundle.world_size,
        "model_axes": list(map(list, axes)) if axes else [],
        "per_device": {
            **state,
            "batch": shapes["batch_bytes"],
            "activations": act,
            **codec,
        },
        "predicted_peak_bytes": int(total),
        "predicted_peak_gib": round(total / GIB, 3),
    }


# ---------------------------------------------------------------------------
# on-chip validation
# ---------------------------------------------------------------------------


def measure(name: str, scale: str, rounds: int = 2) -> dict:
    """Device-truth memory for one single-worker round (the per-worker
    layout predict() models): XLA's compile-time buffer assignment
    (``Compiled.memory_analysis`` — arguments + temps is the device
    footprint XLA reserves) plus, where the runtime exposes it,
    ``memory_stats`` peak (libtpu reports one; the CPU backend does
    not, and there the compile-time number is the check)."""
    import jax

    from consensusml_tpu.configs import build
    from consensusml_tpu.train import init_stacked_state, make_simulated_train_step

    bundle = build(name, scale, world=1)
    cfg = bundle.cfg
    step = make_simulated_train_step(cfg, bundle.loss_fn)
    state = init_stacked_state(
        cfg, bundle.init_params, jax.random.key(0), 1
    )
    batch = next(iter(bundle.batches(1, 0)))
    ma = step.lower(state, batch).compile().memory_analysis()
    # donated state aliases its outputs, so arguments+temps IS the live
    # footprint — the ONE definition shared with the cost ledger and
    # the three-way reconciliation (obs/memviz.compiled_footprint)
    from consensusml_tpu.obs.memviz import compiled_footprint

    compiled_peak = compiled_footprint(ma)
    out = {
        "platform": jax.default_backend(),
        "argument_bytes": ma.argument_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "compiled_peak_bytes": int(compiled_peak),
        "compiled_peak_gib": round(compiled_peak / GIB, 3),
    }
    metrics = None
    for b in bundle.batches(rounds, 0):
        state, metrics = step(state, b)
    out["loss"] = round(float(metrics["loss"]), 4)  # executes for real
    stats = jax.local_devices()[0].memory_stats() or {}
    if stats.get("peak_bytes_in_use"):
        out["measured_peak_bytes"] = stats["peak_bytes_in_use"]
        out["measured_peak_gib"] = round(
            stats["peak_bytes_in_use"] / GIB, 3
        )
    return out


_ALL = [
    ("mnist_mlp", "full", None, None),
    ("cifar_resnet50", "full", None, None),
    ("bert_mlm", "full", None, None),
    ("gpt2_topk", "full", None, None),
    ("llama_lora", "full", None, None),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None)
    # default resolved after parse: "full" for the analytic paths, but
    # "smoke" under --reconcile, which actually COMPILES AND RUNS the
    # config on this box — full-scale llama/gpt2 would OOM a dev host
    ap.add_argument("--scale", default=None, choices=("smoke", "full"))
    ap.add_argument("--world", type=int, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--measure", action="store_true",
                    help="also run world=1 on this device and report peak")
    ap.add_argument("--reconcile", action="store_true",
                    help="run the three-way reconciliation (analytic vs "
                         "compiled memory_analysis vs live peak) through "
                         "obs/memviz.reconcile_config and print its doc — "
                         "the drift gauges a live run exports under "
                         "consensusml_hbm_* (docs/memory.md "
                         "'Reconciliation')")
    ap.add_argument("--md", action="store_true")
    args = ap.parse_args()
    if args.scale is None:
        args.scale = "smoke" if args.reconcile else "full"

    if args.reconcile:
        if not args.config:
            ap.error("--reconcile needs --config NAME")
        from consensusml_tpu.obs.memviz import reconcile_config

        doc = reconcile_config(args.config, args.scale)
        print(json.dumps(doc, indent=2))
        return

    runs = (
        _ALL
        if args.all
        else [(args.config, args.scale, args.world, None)]
    )
    if runs[0][0] is None:
        ap.error("pass --config NAME or --all")

    rows = []
    for name, scale, world, axes in runs:
        r = predict(name, scale, world=world, model_axes=axes)
        if args.measure:
            r["measured"] = measure(name, scale)
        rows.append(r)
        print(f"# {json.dumps(r)}", file=sys.stderr, flush=True)

    if args.md:
        print(
            "| config | world | model axes | params | opt | gossip | "
            "activations | codec | predicted peak/device |"
        )
        print("|---|---|---|---|---|---|---|---|---|")
        g = lambda b: f"{b / GIB:.2f}"
        for r in rows:
            d = r["per_device"]
            axes = (
                "x".join(f"{a}={s}" for a, s in r["model_axes"]) or "—"
            )
            print(
                f"| {r['config']} ({r['scale']}) | {r['world']} | {axes} "
                f"| {g(d['params'])} | {g(d['opt'])} | {g(d['gossip'])} "
                f"| {g(d['activations'])} "
                f"| {g(d['codec_temp'] + d['payloads'])} "
                f"| **{r['predicted_peak_gib']} GiB** |"
            )
    else:
        print(json.dumps(rows, indent=2))


if __name__ == "__main__":
    main()
