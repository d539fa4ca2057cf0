"""Convergence comparison across the gossip modes (docs/convergence.md).

Same workload, same seeds, same data order for every variant; simulated
backend so every mode shares one device's arithmetic. Two workloads:

- ``--workload mlp`` — 8-worker MLP (the mnist_mlp shape), h=2, CPU. The
  quick smoke matrix; its task is easy enough that top-1 saturates, so
  only loss/consensus-error discriminate.
- ``--workload resnet`` — ResNet-50 with the CIFAR stem on 32x32x3
  synthetic data whose noise floor is tuned so held-out top-1 lands in
  the 0.7-0.9 band: hard enough that the accuracy column *could*
  separate the gossip modes. This is the apparatus behind the north
  star's "at matching top-1 accuracy" clause (BASELINE.json): if a codec
  or topology hurt convergence, it would show here as a top-1 gap.

Sweep axes (either workload): ``--h-sweep`` runs exact + CHOCO at
H ∈ {1, 2, 8} (config 3's recipe is H=8 periodic averaging), and
``--gamma-sweep`` runs CHOCO int8 across gamma to show the consensus
floor is controllable (VERDICT r2 items 1 and 4).

Usage:
  python tools/convergence_study.py --workload resnet --rounds 300 \
      --h-sweep --gamma-sweep --md --out /tmp/study.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

GAMMAS = (0.1, 0.3, 0.5, 0.8, 1.0)
H_SWEEP = (1, 2, 8)


def build_workload(name: str, noise: float | None, batch: int | None):
    """Model/loss/eval/data factory shared by every variant of a run."""
    import jax.numpy as jnp

    from consensusml_tpu.data import SyntheticClassification
    from consensusml_tpu.train import classification_eval_fn

    if name == "mlp":
        from consensusml_tpu.models import MLP, mlp_loss_fn

        model = MLP(hidden=32)
        # noise high enough that the Bayes rate is < 1: an all-1.0 table
        # would say nothing about the modes' relative convergence
        data = SyntheticClassification(
            n=2048, image_shape=(28, 28, 1), noise=3.0 if noise is None else noise
        )
        return {
            "world": 8,
            "h": 2,
            "batch": batch or 16,
            "loss_fn": mlp_loss_fn(model),
            "init": lambda r: model.init(r, jnp.zeros((1, 28, 28, 1)))["params"],
            "eval_fn": classification_eval_fn(model),
            "data": data,
            "opt": lambda: __import__("optax").sgd(0.05),
            "opt_factory": lambda lr: __import__("optax").sgd(lr),
            "scale": 1.0,
            "holdout": 512,
            "eval_batch": 64,
        }
    if name == "lm":
        # config-5's own model family (decoder LM + Adam): the pairing
        # BASELINE.json actually puts behind the top-k codec
        import optax

        from consensusml_tpu.data import SyntheticLM
        from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM, gpt2_loss_fn
        from consensusml_tpu.train import causal_lm_eval_fn

        model = GPT2LM(
            config=GPT2Config(
                vocab_size=128, hidden=128, layers=4, heads=4, max_len=64,
                dropout=0.0,
            )
        )
        data = SyntheticLM(vocab_size=128, seq_len=32)
        return {
            "world": 8,
            "h": 2,
            "batch": batch or 16,
            "loss_fn": gpt2_loss_fn(model),
            "init": lambda r: model.init(r, jnp.zeros((1, 32), jnp.int32))[
                "params"
            ],
            "eval_fn": causal_lm_eval_fn(model),
            "data": data,
            "opt": lambda: optax.adam(1e-3),
            "opt_factory": lambda lr: optax.adam(lr),
            "scale": 1.0,
            "holdout": None,  # LM eval batches come from the keyed stream
            "eval_batch": 64,
        }
    if name == "lm_full":
        # VERDICT r3 item 2: the shipped FULL-scale codec (k=8 of 512,
        # ratio 1/64, gamma 0.5 — configs gpt2_topk "full") proven on a
        # >=10M-param decoder rather than extrapolated from the 1M-param
        # smoke proxy. ~30M params (vocab 8192, hidden 512, 8 layers,
        # seq 256): big enough that the sparsity frontier is exercised
        # at real depth/width ratios, small enough that 8 simulated
        # workers fit one v5e chip for a few hundred rounds.
        import optax

        from consensusml_tpu.data import SyntheticLM
        from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM, gpt2_loss_fn
        from consensusml_tpu.train import causal_lm_eval_fn

        model = GPT2LM(
            config=GPT2Config(
                vocab_size=8192, hidden=512, layers=8, heads=8, max_len=256,
                dropout=0.0,
            )
        )
        data = SyntheticLM(vocab_size=8192, seq_len=256)
        return {
            "world": 8,
            "h": 2,  # config 5's own H
            "batch": batch or 8,
            "loss_fn": gpt2_loss_fn(model),
            "init": lambda r: model.init(r, jnp.zeros((1, 256), jnp.int32))[
                "params"
            ],
            "eval_fn": causal_lm_eval_fn(model),
            "data": data,
            "opt": lambda: optax.adam(6e-4),
            "opt_factory": lambda lr: optax.adam(lr),
            "scale": 1.0,
            "holdout": None,
            "eval_batch": 16,
            # the SHIPPED full-scale codec parameters (ratio 1/64)
            "codec": {"chunk": 512, "k": 8},
        }
    if name == "bert32":
        # VERDICT r3 item 3: config 3's advertised scale is 32-WORKER
        # local-SGD (H=8) and the headline metric names 32-worker gossip,
        # but every recorded trajectory so far ran 8 workers. This
        # workload records the world=32 story on the simulated backend:
        # a mid-size BERT (~8M params — world size, not model size, is
        # the axis under test; 32 full BERT-base replicas would blow one
        # chip's HBM), H=8 periodic averaging, masked-LM eval. Run with
        # --torus for the 4x8 torus row next to the ring.
        import optax

        from consensusml_tpu.data import SyntheticLM
        from consensusml_tpu.models.bert import (
            BertConfig,
            BertMLM,
            bert_mlm_loss_fn,
        )
        from consensusml_tpu.train import mlm_eval_fn

        # vocab 2048: the Markov successor table must be MEMORIZED
        # (random structure), and MLM supervises only 15% of positions —
        # at vocab 8192 the table never fits this round budget and every
        # mode plateaus at the marginal (measured r4), telling us nothing
        # about the 32-worker dynamics under test
        model = BertMLM(
            config=BertConfig(
                vocab_size=2048, hidden=256, layers=4, heads=8,
                mlp_dim=1024, max_len=128, dropout=0.0,
            )
        )
        data = SyntheticLM(vocab_size=2048, seq_len=128)
        return {
            "world": 32,
            "h": 8,  # config 3's recipe: H=8 + periodic averaging
            "batch": batch or 8,
            "loss_fn": bert_mlm_loss_fn(model),
            "init": lambda r: model.init(r, jnp.zeros((1, 128), jnp.int32))[
                "params"
            ],
            "eval_fn": mlm_eval_fn(model),
            "data": data,
            "opt": lambda: optax.adam(3e-4),
            "opt_factory": lambda lr: optax.adam(lr),
            "scale": 1.0,
            "holdout": None,
            "eval_batch": 16,
            "mlm_rate": 0.15,
        }
    if name == "resnet":
        from consensusml_tpu.models import resnet50, resnet_init, resnet_loss_fn

        model = resnet50(num_classes=10, stem="cifar")
        noise = 12.0 if noise is None else noise
        data = SyntheticClassification(
            n=8192, image_shape=(32, 32, 3), noise=noise
        )
        return {
            "world": 8,
            "h": 2,
            "batch": batch or 16,
            "loss_fn": resnet_loss_fn(model),
            "init": resnet_init(model, (1, 32, 32, 3)),
            "eval_fn": classification_eval_fn(model, train_kwarg=True),
            "data": data,
            "opt": lambda: __import__("optax").sgd(0.05, momentum=0.9),
            "opt_factory": lambda lr: __import__("optax").sgd(lr, momentum=0.9),
            # raw inputs have std ~= noise; a uniform rescale keeps the
            # task identical but the conv stem numerically comfortable
            "scale": 1.0 / (1.0 + noise),
            "holdout": 1024,
            "eval_batch": 128,
        }
    raise ValueError(f"unknown workload {name!r}")


def variants(wl, args):
    import optax  # noqa: F401  (opt factories resolve it lazily)

    from consensusml_tpu.compress import (
        PallasInt8Compressor,
        QSGD4Compressor,
        topk_int4_compressor,
        topk_int8_compressor,
    )
    from consensusml_tpu.consensus import GossipConfig
    from consensusml_tpu.topology import (
        OnePeerExponentialTopology,
        RingTopology,
    )
    from consensusml_tpu.train import LocalSGDConfig, SlowMoConfig

    world, h, tx = wl["world"], wl["h"], wl["opt"]
    ring = RingTopology(world)
    # workload-specific codec parameters (lm_full pins the SHIPPED
    # full-scale k=8/512); default = the smoke-scale ratio-0.1 codec
    ca = wl.get("codec", {"ratio": 0.1, "chunk": 128})
    gs = getattr(args, "gossip_steps", 1)
    cw = getattr(args, "codec_warmup", 0)
    cr = getattr(args, "codec_refresh", 0)
    _g = getattr(args, "gamma", None)
    base_gamma = 0.5 if _g is None else _g  # explicit --gamma 0 is a value
    choco = lambda comp, gamma=base_gamma, hh=h, topo=ring: LocalSGDConfig(  # noqa: E731
        gossip=GossipConfig(
            topology=topo, compressor=comp, gamma=gamma, gossip_steps=gs,
            codec_warmup_rounds=cw, codec_refresh_every=cr,
        ),
        optimizer=tx(),
        h=hh,
    )
    out = {
        "exact ring": LocalSGDConfig(
            gossip=GossipConfig(topology=ring), optimizer=tx(), h=h
        ),
        "overlap ring": LocalSGDConfig(
            gossip=GossipConfig(topology=ring, overlap=True), optimizer=tx(), h=h
        ),
        "choco topk+int8": choco(topk_int8_compressor(**ca)),
        "choco topk+int4": choco(topk_int4_compressor(**ca)),
        "choco qsgd4": choco(QSGD4Compressor(chunk=ca["chunk"])),
        "choco int8 (quant only)": choco(
            PallasInt8Compressor(chunk=ca["chunk"])
        ),
        "push-sum one-peer (directed)": LocalSGDConfig(
            gossip=GossipConfig(
                topology=OnePeerExponentialTopology(world), push_sum=True
            ),
            optimizer=tx(),
            h=h,
        ),
        "exact ring + SlowMo": LocalSGDConfig(
            gossip=GossipConfig(topology=ring),
            optimizer=tx(),
            h=h,
            outer=SlowMoConfig(beta=0.5),
        ),
    }
    if args.torus:
        from consensusml_tpu.topology import topology_from_name

        tor = topology_from_name("torus", world)
        out["exact torus"] = LocalSGDConfig(
            gossip=GossipConfig(topology=tor), optimizer=tx(), h=h
        )
        # the codec rows above ride the ring; these re-run codecs on the
        # torus — the exact-vs-compressed comparison at the topology a
        # 32-worker run actually wants (bert32: ring mixing is ~6x
        # slower at world 32 and delays consensus learning past any
        # affordable round budget). The dense-codec torus rows ask the
        # world-32 accuracy question top-k failed (docs/convergence.md):
        # does a codec without never-shipped coordinates cross the cliff?
        out["choco topk+int8 torus"] = choco(
            topk_int8_compressor(**ca), topo=tor
        )
        out["choco int8 (quant only) torus"] = choco(
            PallasInt8Compressor(chunk=ca["chunk"]), topo=tor
        )
        out["choco qsgd4 torus"] = choco(QSGD4Compressor(chunk=ca["chunk"]), topo=tor)
    if args.h_sweep:
        for hh in H_SWEEP:
            if hh == h:
                continue  # the base rows already cover the default H
            out[f"exact ring h={hh}"] = LocalSGDConfig(
                gossip=GossipConfig(topology=ring), optimizer=tx(), h=hh
            )
            out[f"choco topk+int8 h={hh}"] = choco(
                topk_int8_compressor(**ca), hh=hh
            )
    if args.gamma_sweep:
        for g in GAMMAS:
            if g == 0.5:
                continue  # == the base "choco topk+int8" row
            out[f"choco topk+int8 gamma={g}"] = choco(
                topk_int8_compressor(**ca), gamma=g
            )
    if args.modes:
        keep = [m.strip() for m in args.modes.split(",")]
        exact = {k: v for k, v in out.items() if k in keep}
        # exact names win ("exact ring" should not drag in "+ SlowMo");
        # substrings only for filters that name no row exactly
        out = exact or {
            k: v for k, v in out.items() if any(s in k for s in keep)
        }
    return out


def run_variant(cfg, wl, rounds: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from consensusml_tpu.data import round_batches
    from consensusml_tpu.train import (
        evaluate,
        init_stacked_state,
        make_simulated_train_step,
    )

    from consensusml_tpu.data import lm_round_batches

    world, scale = wl["world"], wl["scale"]
    is_lm = not hasattr(wl["data"], "images")  # SyntheticLM vs image data
    step = make_simulated_train_step(cfg, wl["loss_fn"])
    state = init_stacked_state(cfg, wl["init"], jax.random.key(0), world)
    # equal tokens-seen across the h-sweep: fewer rounds at larger H so
    # every row consumes the same number of microbatches
    n_rounds = max(1, (rounds * wl["h"]) // cfg.h)
    mlm_rate = wl.get("mlm_rate", 0.0)
    batches = (
        lm_round_batches(
            wl["data"], world, cfg.h, wl["batch"], n_rounds, mlm_rate=mlm_rate
        )
        if is_lm
        else round_batches(wl["data"], world, cfg.h, wl["batch"], n_rounds)
    )
    losses, errs = [], []
    for i, batch in enumerate(batches):
        if scale != 1.0:
            batch = dict(batch, image=batch["image"] * scale)
        state, m = step(state, batch)
        # keep metrics ON DEVICE: a float() here is a host sync every
        # round, which stalls dispatch behind each round's completion.
        # Bound the dispatch queue with one sync every 25 rounds, fetch
        # the rest at the end.
        losses.append(m["loss"])
        errs.append(m["consensus_error"])
        if i % 25 == 24:
            float(m["loss"])
    losses = [float(v) for v in np.asarray(jnp.stack(losses))]
    errs = [float(v) for v in np.asarray(jnp.stack(errs))]

    eb = wl["eval_batch"]
    if is_lm:
        # held-out LM windows: same keyed sample stream, disjoint seeds
        # (MLM workloads corrupt them with the shared keyed masker)
        def eval_batches():
            from consensusml_tpu.data.synthetic import mlm_corrupt

            for r in range(8):
                rng = np.random.default_rng((999_983, r))
                ids = wl["data"].sample(rng, (eb,))
                if mlm_rate > 0:
                    yield mlm_corrupt(ids, wl["data"], 999_983, r, mlm_rate)
                else:
                    yield {"input_ids": jnp.asarray(ids)}

    else:
        held = wl["data"].holdout(wl["holdout"])

        def eval_batches():
            for r in range(wl["holdout"] // eb):
                yield {
                    "image": jnp.asarray(held.images[r * eb : (r + 1) * eb])
                    * scale,
                    "label": jnp.asarray(held.labels[r * eb : (r + 1) * eb]),
                }

    ev = evaluate(wl["eval_fn"], state, eval_batches())
    # classifiers report held-out top-1; LMs report held-out nll
    metric = "top1" if "top1" in ev["mean_model"] else "nll"
    # 8-point trajectories: divergence SHAPE matters for the frontier
    # study (growing vs plateaued consensus error are different verdicts)
    stride = max(1, n_rounds // 8)
    return {
        "rounds": n_rounds,
        "metric": metric,
        "final_loss": round(float(np.mean(losses[-5:])), 4),
        "loss_trajectory": [round(v, 3) for v in losses[::stride]],
        "consensus_error_trajectory": [round(v, 3) for v in errs[::stride]],
        "consensus_error": round(errs[-1], 4),
        f"{metric}_consensus_model": round(
            float(ev["mean_model"][metric]), 4
        ),
        f"{metric}_worker_mean": round(
            float(ev["worker_mean"][metric]), 4
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("mlp", "resnet", "lm", "lm_full", "bert32"), default="mlp")
    ap.add_argument("--rounds", type=int, default=80)
    ap.add_argument("--noise", type=float, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--h-sweep", action="store_true")
    ap.add_argument("--gamma-sweep", action="store_true")
    ap.add_argument("--modes", default=None, help="comma substrings to keep")
    ap.add_argument("--torus", action="store_true",
                    help="add an 'exact torus' row (e.g. the 4x8 torus at "
                         "world=32 next to the ring)")
    ap.add_argument("--lr", type=float, default=None,
                    help="override the workload's optimizer learning rate")
    ap.add_argument("--codec-refresh", type=int, default=0,
                    help="dense refresh round every K rounds (bounds top-k "
                         "error-feedback drift)")
    ap.add_argument("--gamma", type=float, default=None,
                    help="override the BASE choco gamma (0.5) for every "
                         "codec row incl. the torus one — the gamma-sweep "
                         "rows keep their own values")
    ap.add_argument("--codec-warmup", type=int, default=0,
                    help="exact-gossip warmup rounds before the codec "
                         "engages (CHOCO tracking warms during them)")
    ap.add_argument("--gossip-steps", type=int, default=1,
                    help="consensus iterations per round for the CHOCO rows "
                         "(T small-gamma iterations; wire x T)")
    ap.add_argument("--codec-k", type=int, default=None,
                    help="override the workload codec's k (top-k per chunk) — "
                         "the lm_full frontier sweep's sparsity axis")
    ap.add_argument(
        "--device",
        choices=("cpu", "tpu"),
        default=None,
        help="default: cpu for mlp, accelerator (if present) otherwise",
    )
    ap.add_argument("--md", action="store_true", help="print a markdown table")
    ap.add_argument("--out", default=None, help="also write results JSON here")
    args = ap.parse_args()

    import jax

    device = args.device or ("cpu" if args.workload == "mlp" else "tpu")
    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")

    wl = build_workload(args.workload, args.noise, args.batch)
    if args.codec_k is not None:
        if "codec" not in wl:
            raise SystemExit("--codec-k only applies to workloads with a pinned codec (lm_full)")
        wl["codec"] = dict(wl["codec"], k=args.codec_k)
    if args.lr is not None:
        # SAME optimizer family, new lr — replacing the family would make
        # every row incomparable to the pinned recipe
        factory = wl["opt_factory"]
        wl["opt"] = lambda: factory(args.lr)
    rows = {}
    for name, cfg in variants(wl, args).items():
        rows[name] = run_variant(cfg, wl, args.rounds)
        print(f"# {name}: {json.dumps(rows[name])}", file=sys.stderr, flush=True)

    if args.out:
        meta = {
            "workload": args.workload,
            "rounds": args.rounds,
            "noise": args.noise,
            "backend": jax.default_backend(),
        }
        with open(args.out, "w") as f:
            json.dump({"meta": meta, "rows": rows}, f, indent=2)

    if args.md and not rows:
        print("no variants matched --modes", file=sys.stderr)
        return
    if args.md:
        metric = next(iter(rows.values()))["metric"]
        label = "top-1" if metric == "top1" else "nll"
        print(
            f"| mode | rounds | final loss | consensus error |"
            f" {label} (consensus model) | {label} (worker mean) |"
        )
        print("|---|---|---|---|---|---|")
        for name, r in rows.items():
            print(
                f"| {name} | {r['rounds']} | {r['final_loss']} "
                f"| {r['consensus_error']} | {r[f'{metric}_consensus_model']} "
                f"| {r[f'{metric}_worker_mean']} |"
            )
    else:
        print(json.dumps(rows, indent=2))


if __name__ == "__main__":
    main()
