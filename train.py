#!/usr/bin/env python
"""Single-host training entry point.

Reference parity: the reference's ``train.py`` launcher with a
``--device`` backend flag (BASELINE.json north_star: "existing train.py /
worker.py entrypoints select the TPU backend via --device=tpu"; SURVEY.md
L6 — mount empty). Differences born of the TPU design: there is no worker
process spawn — "N workers" is either N devices in a mesh (``--backend
collective``) or a stacked axis on one device (``--backend simulated``);
multi-host pods launch this same script once per host via ``worker.py``.

Examples:
    python train.py --config mnist_mlp --device cpu --rounds 50
    python train.py --config gpt2_topk --device cpu --backend simulated
    python train.py --config cifar_resnet50 --device tpu --scale full
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--config", default=None, help="workload name (see --list)")
    p.add_argument("--device", default="auto", choices=["auto", "cpu", "tpu"],
                   help="backend platform; cpu simulates workers on host devices")
    p.add_argument("--model-axes", default=None,
                   help='hybrid model parallelism for the collective backend: '
                        '"tp=N" gives every worker an N-device submesh with '
                        'params sharded per the config\'s TP rules (one axis '
                        'only from the CLI); "none" disables a config\'s '
                        'default (full-scale llama_lora defaults to tp=4)')
    p.add_argument("--backend", default="auto", choices=["auto", "collective", "simulated"],
                   help="collective = shard_map over a device mesh; simulated = "
                        "stacked workers on one device (CPU reference mode)")
    p.add_argument("--scale", default=None, choices=["smoke", "full"],
                   help="workload size (default: smoke on cpu, full on tpu)")
    p.add_argument("--rounds", type=int, default=50)
    p.add_argument("--drop-prob", type=float, default=0.0,
                   help="per-round worker dropout probability (fault injection; "
                        "non-finite failure detection is enabled alongside it)")
    p.add_argument("--slowmo-beta", type=float, default=None,
                   help="enable the SlowMo outer optimizer with this slow-momentum "
                        "decay (e.g. 0.8); default off")
    p.add_argument("--workers", type=int, default=None,
                   help="override the config's worker count (topology is "
                        "rebuilt at this size). Two resume paths exist: "
                        "with --resume this is the CHECKPOINT-BOUNDARY "
                        "elastic path — a checkpoint from any world size "
                        "is resized, joiners start from the consensus mean "
                        "of the checkpointed replicas, leavers' replicas "
                        "are dropped (utils.elastic); LIVE joins mid-run "
                        "ride --churn-schedule instead — joiners "
                        "gossip-bootstrap from their neighbors with no "
                        "checkpoint read (consensusml_tpu.swarm)")
    p.add_argument("--topology", default=None,
                   help='override the config\'s gossip graph: "ring", "torus", '
                        '"dense", "exp", "onepeer-exp", or with args e.g. '
                        '"hierarchical:slices=2,outer_every=4" (multi-slice '
                        'ring-of-rings — inner ring on ICI every round, '
                        'inter-slice ring on DCN 1-in-K rounds)')
    p.add_argument("--codec", default=None,
                   choices=["topk_int8", "topk_int4", "int8", "int4", "fp8"],
                   help="swap the compressed-gossip codec on a compressed "
                        "config. topk_int8/topk_int4: sparsify then "
                        "quantize the surviving values (topk_int4 = half "
                        "the wire of the config-5 default). int8/int4/fp8: "
                        "the pure per-chunk quantizers — denser wire, but "
                        "they ride the FUSED one-pass bucketed wire (one "
                        "pack+quantize kernel per bucket per round; see "
                        "docs/gossip_bucketing.md). These resolve to the "
                        "compiled Pallas kernels on TPU and the Pallas "
                        "interpreter elsewhere — the chosen path is logged "
                        "loudly at startup")
    p.add_argument("--gossip-steps", type=int, default=None,
                   help="consensus iterations per round (wire x N): N "
                        "small-gamma CHOCO iterations contract like N "
                        "rounds while each stays inside the stability "
                        "region — the recalibration lever for aggressive "
                        "codecs at scale (docs/convergence.md frontier)")
    p.add_argument("--gamma", type=float, default=None,
                   help="override the CHOCO consensus step size")
    p.add_argument("--codec-refresh", type=int, default=None,
                   help="dense refresh round every K rounds on a compressed "
                        "config (bounds top-k error-feedback drift; "
                        "amortized wire +dense/K)")
    p.add_argument("--codec-warmup", type=int, default=None,
                   help="exact-gossip warmup rounds before the compressed "
                        "codec engages (innovation tracking warms during "
                        "them; the frontier study's early-instability fix)")
    p.add_argument("--overlap-gossip", action="store_true",
                   help="combine-then-adapt gossip: the mixing correction is "
                        "computed from pre-inner-loop params and applied next "
                        "round, letting XLA overlap the communication with "
                        "the H local steps (exact gossip, or compressed "
                        "gossip on the bucketed wire)")
    p.add_argument("--gossip-pipeline", type=int, default=None, metavar="D",
                   help="pipelined overlap gossip: keep D mixing "
                        "corrections in flight (requires --overlap-gossip "
                        "or an overlap config) — the correction computed "
                        "at round r lands at round r+D, so each round's "
                        "collective has D rounds of local compute to hide "
                        "under (cross-round slack for slow links/DCN). "
                        "D=1 is plain overlap gossip, bit-identical to "
                        "--overlap-gossip alone")
    p.add_argument("--bucket-bytes", type=int, default=None,
                   help="gossip wire bucket cap in bytes — leaves coalesce "
                        "into fused wire buffers of roughly this much "
                        "estimated traffic each (default 4 MiB; see "
                        "GossipConfig.bucket_bytes). 0 = per-leaf wire "
                        "(one collective per tree leaf)")
    p.add_argument("--push-sum", action="store_true",
                   help="ratio-consensus averaging (exact mean on directed "
                        "topologies and under faults; see consensus.pushsum)")
    p.add_argument("--churn-schedule", default=None, metavar="SPEC",
                   help="train under LIVE membership churn on the simulated "
                        "backend (consensusml_tpu.swarm): SPEC is either a "
                        'seeded generator ("seed=0,rounds=12,joins=3,'
                        'drops=2,stragglers=1") or explicit events '
                        '("join@5:1;drop@4:2;rejoin@6:2;straggle@7:3x2"). '
                        "Drops freeze the member's replica until rejoin and "
                        "mask it out of gossip mid-round (push-sum-weighted "
                        "recovery engages automatically when the mixing "
                        "matrix goes asymmetric); joiners gossip-bootstrap "
                        "their replica from neighbors — no checkpoint read "
                        "— and participate from the next round. See "
                        "docs/elasticity.md")
    p.add_argument("--native-loader", action="store_true",
                   help="assemble round batches with the C++ prefetch ring "
                        "(producer threads run ahead of the device; see "
                        "data.native_pipeline). Sample draws differ from the "
                        "Python loaders' numpy streams by design")
    p.add_argument("--native-wire", choices=("f32", "u8"), default=None,
                   help="host->device wire format for --native-loader image "
                        "batches: u8 ships quantized bytes (1/4 the "
                        "transfer; file images re-ship their original "
                        "bytes) and the jitted step dequants on device. "
                        "Default: "
                        "u8 for image/classification configs, f32 otherwise "
                        "(pass --native-wire f32 to force the float wire)")
    p.add_argument("--prefetch-depth", type=int, default=2, metavar="N",
                   help="overlapped host->device feed: stage up to N round "
                        "batches on device ahead of the consumer "
                        "(DevicePrefetcher; 2 = double buffering, the "
                        "transfer for round r+1 overlaps round r's "
                        "compute). 0 disables the overlap (batches "
                        "transfer synchronously at dispatch, the pre-PR-3 "
                        "behavior); feed-stall time lands on the "
                        "consensusml_feed_stall_seconds gauge either way "
                        "the prefetcher runs (docs/observability.md)")
    p.add_argument("--data-dir", default=None,
                   help="train on real files from this directory (MNIST idx / "
                        "CIFAR-10 binaries / tokens.bin — see data.files); "
                        "falls back to procedural data when absent")
    p.add_argument("--lr", type=float, default=None,
                   help="override the config's peak learning rate")
    p.add_argument("--lr-schedule", default=None,
                   choices=["constant", "cosine", "linear"],
                   help="LR schedule over --rounds (steps = rounds x h)")
    p.add_argument("--warmup-rounds", type=int, default=0,
                   help="linear LR warmup, in gossip rounds")
    p.add_argument("--grad-clip", type=float, default=0.0,
                   help="global-norm gradient clipping (0 = off)")
    p.add_argument("--round-timeout", type=float, default=0.0,
                   help="seconds without round progress before the process "
                        "hard-exits with a diagnostic (failure detection for "
                        "multi-process runs: a dead peer wedges survivors "
                        "inside a collective forever otherwise); arms after "
                        "the first completed round so XLA compile never "
                        "counts; 0 = disabled")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--metrics-out", default=None, help="JSONL metrics path")
    p.add_argument("--profile-dir", default=None,
                   help="dump an xprof trace of rounds 2-3 to this directory")
    p.add_argument("--trace-events", default=None, metavar="PATH",
                   help="write the host span ring as Chrome trace-event "
                        "JSON here at exit (Perfetto / chrome://tracing "
                        "loadable; spans also enter jax.named_scope so an "
                        "xprof dump lines up — docs/observability.md)")
    p.add_argument("--metrics-prom", default=None, metavar="PATH",
                   help="write the telemetry registry as a Prometheus "
                        "textfile here (atomically, every --telemetry-every "
                        "rounds and at exit; point a node-exporter textfile "
                        "collector at its directory)")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="serve the live observability endpoints over HTTP "
                        "on this port (0 = pick a free one): /metrics is "
                        "the Prometheus text exposition rendered fresh per "
                        "scrape (same locked expose() path as "
                        "--metrics-prom), /traces the merged Chrome trace, "
                        "/requests the request-trace registry snapshot "
                        "(docs/observability.md 'Request tracing'), "
                        "/alerts + /query + /healthz the SLO/alert plane "
                        "over the in-process metric history "
                        "(docs/observability.md 'Alerting & history'), and "
                        "/profile?ms=N an on-demand jax.profiler capture of "
                        "the LIVE loop (single-flight; docs/observability.md "
                        "'Live profiling')")
    p.add_argument("--cost-ledger", action="store_true",
                   help="register the run's executables (train step, gossip "
                        "round under its bucket plan) in the compiled cost "
                        "ledger: lower().compile() cost/memory analysis + "
                        "compile wall time per executable into the "
                        "consensusml_cost_*/consensusml_compile_* families, "
                        "live HBM gauges at --telemetry-every cadence, and "
                        "the three-way analytic/compiled/live HBM drift "
                        "(docs/observability.md 'Cost attribution'; costs "
                        "ONE duplicate XLA compile per executable at round "
                        "0 — analysis only, jit caches untouched)")
    p.add_argument("--telemetry-every", type=int, default=10, metavar="N",
                   help="cadence (rounds) for the heavier telemetry: metric "
                        "snapshots, Prometheus rewrite, the history-ring "
                        "sample + SLO/alert rule evaluation, and the CHOCO "
                        "||s - xhat|| residual fetch (default 10)")
    p.add_argument("--flight-recorder", default=None, metavar="DIR",
                   help="enable the crash flight recorder: on watchdog "
                        "timeout, unhandled exception, or SIGTERM, dump the "
                        "last rounds' spans + metric snapshots to a "
                        "timestamped JSON file in DIR")
    p.add_argument("--obs-cluster-dir", default=None, metavar="DIR",
                   help="cluster observability sideband: atomically rewrite "
                        "this rank's obs-rank-N.json snapshot (registry "
                        "values, round progress, heartbeat) in DIR at "
                        "--telemetry-every cadence; point every rank of a "
                        "swarm at one shared DIR and render the merged view "
                        "with tools/obs_report.py (docs/observability.md "
                        "'Cluster view')")
    p.add_argument("--link-probes", action="store_true",
                   help="probe per-link latency/bandwidth: at "
                        "--telemetry-every cadence, time one small transfer "
                        "across every directed gossip edge and export the "
                        "consensusml_link_* families per (src, dst) — the "
                        "slowest-link ranking the cluster report and the "
                        "topology auto-tuner consume (host-side sideband, "
                        "never inside the jitted round)")
    p.add_argument("--eval-every", type=int, default=0,
                   help="also run the held-out eval every K rounds during "
                        "training (requires --eval-batches)")
    p.add_argument("--eval-batches", type=int, default=0,
                   help="after training, score this many held-out batches "
                        "(per-worker AND consensus-mean-model top-1/ppl)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0, help="rounds; 0 = end only")
    p.add_argument("--export-serving", default=None, metavar="DIR",
                   help="write the consensus-mean SERVING artifact here at "
                        "end of run (and at every --checkpoint-every "
                        "boundary when set): worker replicas collapse via "
                        "the shared consensus mean into a deployable "
                        "params tree + serve_meta.json that "
                        "serve.load_engine() / tools/loadgen.py start "
                        "from directly. Each export bumps the artifact's "
                        "generation counter, so an engine watching DIR "
                        "(Engine.watch) hot-swaps to every new mean "
                        "mid-traffic — no drain, no dropped streams "
                        "(docs/serving.md)")
    p.add_argument("--resume", default=None, help="checkpoint path to resume from")
    p.add_argument("--list", action="store_true", help="list configs and exit")
    return p.parse_args(argv)


def _try_restore(path: str, template, lr_flags: bool):
    """restore_state with a clean CLI diagnostic instead of a raw orbax
    traceback. Returns the restored state, or None (caller exits 2)."""
    from consensusml_tpu.utils import restore_state

    try:
        return restore_state(path, template)
    except Exception as e:
        hint = (
            " (hint: --lr-schedule/--grad-clip change the optimizer state "
            "structure; resume with the SAME LR flags the checkpoint was "
            "trained with)"
            if lr_flags
            else ""
        )
        print(
            f"error: cannot restore {path}: "
            f"{type(e).__name__}: {str(e)[:400]}{hint}",
            file=sys.stderr,
        )
        return None


def resolve_backend(
    requested: str, platform: str, n_devices: int, world: int,
    per_worker: int = 1,
) -> tuple[str, str | None]:
    """``(backend, refusal)`` for ``--backend``: ``auto`` is collective
    when every worker gets its own device(s) and simulated on a single
    device. In between, on a TPU host, it refuses: stacking all workers
    on chip 0 would idle the other chips without a word."""
    if requested != "auto":
        return requested, None
    need = world * per_worker
    if n_devices >= need:
        return "collective", None
    if platform == "tpu" and n_devices > 1:
        return "simulated", (
            f"{world} workers need {need} devices but this TPU host has "
            f"{n_devices}; --backend auto will not stack them on one chip "
            f"and idle the rest — pass --workers {n_devices // per_worker} "
            "(one per chip), or --backend simulated to stack them on "
            "purpose"
        )
    return "simulated", None


def main(argv=None) -> int:
    args = parse_args(argv)

    # device selection must happen before heavy jax use
    if args.device == "cpu":
        os.environ.setdefault("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
            os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=32"
    import jax

    if args.device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif args.device == "tpu" and jax.default_backend() != "tpu":
        # checked in THIS process: a chip belongs to one process, so a
        # probing child would either take it from us or (under worker.py,
        # which has already initialised jax) be refused it
        print(
            f"error: --device tpu requested but jax backend is "
            f"{jax.default_backend()!r} (no TPU reachable)",
            file=sys.stderr,
        )
        return 2
    from consensusml_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    from consensusml_tpu import configs
    from consensusml_tpu.comm import WorkerMesh
    from consensusml_tpu.train import (
        init_stacked_state,
        make_collective_train_step,
        make_simulated_train_step,
    )
    from consensusml_tpu.utils import MetricsLogger

    if args.list:
        for name in configs.names():
            b = configs.build(name, "smoke")
            print(f"{name:16s} {b.description}")
        return 0
    if args.config is None:
        print("error: --config is required (or --list)", file=sys.stderr)
        return 2

    platform = jax.default_backend()
    scale = args.scale or ("full" if platform == "tpu" else "smoke")
    if args.device == "auto" and args.scale is None:
        # say which way auto went: a TPU that failed to come up must not
        # read as a green full-scale run
        print(
            f"note: --device auto resolved to {platform!r}; "
            f"defaulting to --scale {scale}",
            flush=True,
        )
    ckpt_world = None
    if args.resume:
        from consensusml_tpu.utils import checkpoint_world_size

        ckpt_world = checkpoint_world_size(args.resume)
        if ckpt_world is None and args.workers is not None:
            print(
                "warning: checkpoint has no world-size record (pre-meta "
                "checkpoint); --workers must match its original world or "
                "the restore will fail with a shape mismatch",
                file=sys.stderr,
            )
    # without an explicit --workers, a resumed run adopts the checkpoint's
    # world size — forgetting the flag must never silently drop replicas
    world = args.workers if args.workers is not None else ckpt_world
    try:
        bundle = configs.build(
            args.config, scale, data_dir=args.data_dir, world=world
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    # fail fast on eval-flag mistakes: the expensive state build /
    # checkpoint restore below must never run first
    if args.eval_every > 0 and args.eval_batches <= 0:
        print("error: --eval-every requires --eval-batches", file=sys.stderr)
        return 2
    if (args.eval_every > 0 or args.eval_batches > 0) and (
        bundle.eval_fn is None or bundle.eval_batches is None
    ):
        print("error: this config has no held-out eval", file=sys.stderr)
        return 2

    lr_flags = (
        args.lr is not None
        or args.lr_schedule is not None
        or args.warmup_rounds > 0
        or args.grad_clip > 0
    )
    if lr_flags:
        import dataclasses

        from consensusml_tpu.train.schedules import build_optimizer

        if bundle.optimizer_factory is None:
            print(
                f"error: config {args.config} has no optimizer factory; "
                "LR/clip flags are unavailable",
                file=sys.stderr,
            )
            return 2
        # schedules are in absolute optimizer steps and the checkpointed
        # step count is absolute too, so a resumed run must size the
        # schedule over (already-trained + requested) rounds or it would
        # spend the whole second leg at the schedule's end value
        sched_start = 0
        if args.resume:
            from consensusml_tpu.utils import checkpoint_round

            ckpt_round = checkpoint_round(args.resume)
            sched_start = ckpt_round or 0
            if ckpt_round is None and args.lr_schedule:
                print(
                    "warning: checkpoint has no round record (pre-round "
                    "meta); the LR schedule is sized over this run's "
                    "--rounds only",
                    file=sys.stderr,
                )
        try:
            tx = build_optimizer(
                bundle.optimizer_factory,
                peak_lr=args.lr if args.lr is not None else bundle.base_lr,
                kind=args.lr_schedule or "constant",
                total_steps=(sched_start + args.rounds) * bundle.cfg.h,
                warmup_steps=args.warmup_rounds * bundle.cfg.h or bundle.base_warmup_steps,
                grad_clip=args.grad_clip,
            )
        except ValueError as e:  # e.g. --warmup-rounds >= --rounds
            print(f"error: {e}", file=sys.stderr)
            return 2
        bundle.cfg = dataclasses.replace(bundle.cfg, optimizer=tx)

    if args.topology is not None:
        import dataclasses

        from consensusml_tpu.topology import topology_from_name

        name, _, argstr = args.topology.partition(":")
        try:
            topo_kwargs = dict(
                (kv.split("=")[0].strip(), int(kv.split("=")[1]))
                for kv in argstr.split(",") if kv
            )
            topo = topology_from_name(name, bundle.world_size, **topo_kwargs)
        except (IndexError, ValueError) as e:
            print(f"error: bad --topology {args.topology!r}: {e}", file=sys.stderr)
            return 2
        bundle.cfg = dataclasses.replace(
            bundle.cfg, gossip=dataclasses.replace(bundle.cfg.gossip, topology=topo)
        )

    if args.drop_prob > 0 or args.push_sum:
        import dataclasses

        from consensusml_tpu.consensus import FaultConfig

        gossip = bundle.cfg.gossip
        if args.push_sum and gossip.compressor is not None:
            print(
                "error: --push-sum is incompatible with a compressed-gossip "
                "config (CHOCO tracking assumes row-stochastic mixing)",
                file=sys.stderr,
            )
            return 2
        # push_sum first: it is what makes faults legal on directed graphs,
        # and GossipConfig validates on every replace
        if args.push_sum:
            gossip = dataclasses.replace(gossip, push_sum=True)
        if args.drop_prob > 0:
            gossip = dataclasses.replace(
                gossip, faults=FaultConfig(drop_prob=args.drop_prob)
            )
        bundle.cfg = dataclasses.replace(bundle.cfg, gossip=gossip)
    if args.codec is not None:
        import dataclasses

        if bundle.cfg.gossip.compressor is None:
            print(
                f"error: --codec only applies to compressed-gossip configs "
                f"({args.config} uses exact mixing)",
                file=sys.stderr,
            )
            return 2
        from consensusml_tpu.compress import (
            PallasFp8Compressor,
            PallasInt4Compressor,
            PallasInt8Compressor,
            resolve_codec_impl,
            topk_int4_compressor,
            topk_int8_compressor,
        )

        # preserve the config's sparsity/chunking and change ONLY the
        # quantizer width: read chunk and k (or ratio) off the current
        # compressor rather than hardcoding, so a config whose codec
        # parameters drift keeps them under --codec
        cur = bundle.cfg.gossip.compressor
        inner = getattr(cur, "inner", cur)
        # for impl="reference" composed codecs the chunk lives on the
        # OUTER quantizer, not the inner TopKCompressor — fall back to it
        # before the hardcoded default so --codec preserves the config's
        # chunking either way
        chunk = (
            getattr(inner, "chunk", None)
            or getattr(cur, "chunk", None)
            or (512 if scale == "full" else 128)
        )
        if args.codec in ("int8", "int4", "fp8"):
            # pure per-chunk quantizers: resolve "pallas auto" for real —
            # compiled kernels on TPU, interpreter fallback elsewhere (the
            # codec-level "auto" would silently run the jnp reference off
            # TPU and the reported codec would not be the executed one)
            impl = resolve_codec_impl()
            chunk = -(-chunk // 128) * 128  # kernel tiling: lane multiple
            comp = {
                "int8": PallasInt8Compressor,
                "int4": PallasInt4Compressor,
                "fp8": PallasFp8Compressor,
            }[args.codec](chunk=chunk, impl=impl)
        else:
            make = {
                "topk_int8": topk_int8_compressor,
                "topk_int4": topk_int4_compressor,
            }[args.codec]
            k = getattr(inner, "k_per_chunk", None) or getattr(inner, "k", None)
            if k is not None:
                comp = make(chunk=chunk, k=k, impl="auto")
            else:
                comp = make(
                    ratio=getattr(inner, "ratio", 0.1), chunk=chunk, impl="auto"
                )
        bundle.cfg = dataclasses.replace(
            bundle.cfg,
            gossip=dataclasses.replace(bundle.cfg.gossip, compressor=comp),
        )
    if (
        args.gossip_steps is not None
        or args.gamma is not None
        or args.codec_warmup is not None
        or args.codec_refresh is not None
    ):
        import dataclasses

        overrides = {}
        if args.gossip_steps is not None:
            overrides["gossip_steps"] = args.gossip_steps
        if args.codec_warmup is not None:
            overrides["codec_warmup_rounds"] = args.codec_warmup
        if args.codec_refresh is not None:
            overrides["codec_refresh_every"] = args.codec_refresh
        if args.gamma is not None:
            if bundle.cfg.gossip.compressor is None:
                print(
                    "error: --gamma only applies to compressed-gossip "
                    f"configs ({args.config} uses exact mixing)",
                    file=sys.stderr,
                )
                return 2
            overrides["gamma"] = args.gamma
        try:
            bundle.cfg = dataclasses.replace(
                bundle.cfg,
                gossip=dataclasses.replace(bundle.cfg.gossip, **overrides),
            )
        except (NotImplementedError, ValueError) as e:
            print(
                f"error: --gossip-steps/--gamma/--codec-warmup: {e}",
                file=sys.stderr,
            )
            return 2
    if args.bucket_bytes is not None:
        import dataclasses

        try:
            # override the LocalSGDConfig-level knob, not gossip directly:
            # a later replace() re-runs __post_init__, which re-applies
            # the retained bucket_bytes field over the gossip sub-config
            # (0 = the per-leaf wire)
            bundle.cfg = dataclasses.replace(
                bundle.cfg, bucket_bytes=args.bucket_bytes
            )
        except (NotImplementedError, ValueError) as e:
            print(f"error: --bucket-bytes: {e}", file=sys.stderr)
            return 2
    if args.overlap_gossip:
        import dataclasses

        try:
            bundle.cfg = dataclasses.replace(
                bundle.cfg,
                gossip=dataclasses.replace(bundle.cfg.gossip, overlap=True),
            )
        except NotImplementedError as e:
            print(f"error: --overlap-gossip: {e}", file=sys.stderr)
            return 2
    if args.gossip_pipeline is not None:
        import dataclasses

        try:
            bundle.cfg = dataclasses.replace(
                bundle.cfg,
                gossip=dataclasses.replace(
                    bundle.cfg.gossip, pipeline_depth=args.gossip_pipeline
                ),
            )
        except (NotImplementedError, ValueError) as e:
            print(f"error: --gossip-pipeline: {e}", file=sys.stderr)
            return 2
    if args.slowmo_beta is not None:
        import dataclasses

        from consensusml_tpu.train import SlowMoConfig

        # measured hazard, not a style warning: on the hard CNN study the
        # textbook beta 0.5 collapsed top-1 0.796 -> 0.121 because the
        # outer momentum compounds the inner optimizer's (momentum-SGD /
        # Adam) effective step (docs/convergence.md, VERDICT r3)
        if args.slowmo_beta >= 0.4:
            print(
                f"warning: --slowmo-beta {args.slowmo_beta}: the "
                "convergence study destabilized at beta 0.5 on a "
                "momentum-SGD workload (top-1 0.796 -> 0.121, "
                "docs/convergence.md); start at 0.2 and raise only while "
                "held-out accuracy holds",
                file=sys.stderr,
            )
        try:
            bundle.cfg = dataclasses.replace(
                bundle.cfg, outer=SlowMoConfig(beta=args.slowmo_beta)
            )
        except NotImplementedError as e:
            print(f"error: --slowmo-beta: {e}", file=sys.stderr)
            return 2

    if args.churn_schedule is not None:
        # the live-membership path: a dedicated loop (swarm.run_churn)
        # replaces the fixed-world round loop below
        bad = [
            flag
            for flag, on in [
                ("--backend collective", args.backend == "collective"),
                ("--model-axes", args.model_axes is not None),
                ("--native-loader", args.native_loader),
                ("--resume", args.resume is not None),
                ("--drop-prob", args.drop_prob > 0),
                ("--overlap-gossip", args.overlap_gossip),
                ("--checkpoint-every", args.checkpoint_every > 0),
                ("--eval-every", args.eval_every > 0),
                ("--profile-dir", args.profile_dir is not None),
                ("--link-probes", args.link_probes),
                ("--flight-recorder", args.flight_recorder is not None),
                ("--round-timeout", args.round_timeout > 0),
            ]
            if on
        ]
        if bad:
            print(
                f"error: --churn-schedule runs the simulated swarm loop "
                f"and does not compose with {', '.join(bad)} "
                "(scheduled churn IS the fault model; end-of-run "
                "--checkpoint-dir / --eval-batches still work)",
                file=sys.stderr,
            )
            return 2
        return _churn_loop(args, bundle, scale)

    model_axes = bundle.model_axes
    user_set_axes = args.model_axes is not None
    if user_set_axes:
        if args.model_axes.strip().lower() in ("none", ""):
            model_axes = ()
        else:
            try:
                model_axes = tuple(
                    (kv.split("=")[0].strip(), int(kv.split("=")[1]))
                    for kv in args.model_axes.split(",")
                )
            except (IndexError, ValueError):
                print(
                    f'error: bad --model-axes {args.model_axes!r} '
                    '(expected e.g. "tp=2" or "none")',
                    file=sys.stderr,
                )
                return 2
            if any(s < 1 for _, s in model_axes):
                print(
                    f'error: bad --model-axes {args.model_axes!r} '
                    "(axis sizes must be >= 1)",
                    file=sys.stderr,
                )
                return 2
            if len(model_axes) > 1:
                # a config's tp_rules shard over ONE axis; silently
                # replicating over the extra axes would burn devices
                print(
                    "error: --model-axes supports a single axis from the "
                    'CLI (got "' + args.model_axes + '"); multi-axis '
                    "hybrid runs need a config with explicit rules "
                    "(see WorkerMesh.create + parallel.sharding)",
                    file=sys.stderr,
                )
                return 2
    if model_axes and bundle.tp_rules is None:
        print(
            f"error: config {bundle.name} has no model-sharding rules; "
            "--model-axes is not supported for it",
            file=sys.stderr,
        )
        return 2
    per_worker = 1
    for _, s in model_axes:
        per_worker *= s
    if (
        model_axes
        and not user_set_axes
        and len(jax.devices()) < bundle.world_size * per_worker
    ):
        # the config's DEFAULT submesh doesn't fit this host — drop it and
        # continue rather than failing on a flag the user never passed
        axes_str = ",".join(f"{n}={s}" for n, s in model_axes)
        print(
            f"note: dropping config default model_axes={axes_str} "
            f"(needs {bundle.world_size}x{per_worker} devices, have "
            f"{len(jax.devices())}); pass --model-axes to force",
            flush=True,
        )
        model_axes = ()
        per_worker = 1

    n_devices = len(jax.devices())
    backend, refusal = resolve_backend(
        args.backend, platform, n_devices, bundle.world_size, per_worker
    )
    if refusal:
        print(f"error: {refusal}", file=sys.stderr)
        return 2
    if backend == "simulated" and model_axes:
        print(
            "error: --model-axes needs the collective backend "
            f"({bundle.world_size}x{per_worker} devices)",
            file=sys.stderr,
        )
        return 2
    axes_str = ",".join(f"{n}={s}" for n, s in model_axes) or "-"
    print(
        f"config={bundle.name} scale={scale} platform={platform} "
        f"backend={backend} workers={bundle.world_size} h={bundle.cfg.h} "
        f"model_axes={axes_str}: {bundle.description}",
        flush=True,
    )
    used = bundle.world_size * per_worker if backend == "collective" else 1
    print(
        f"devices: {n_devices} x {jax.devices()[0].device_kind} found, "
        f"{used} used"
        + (
            f" ({bundle.world_size} workers stacked on one)"
            if backend == "simulated" and bundle.world_size > 1
            else ""
        ),
        flush=True,
    )
    # bandwidth accounting: what one worker puts on the wire per round
    param_shapes = jax.eval_shape(bundle.init_params, jax.random.key(0))
    if isinstance(param_shapes, tuple) and len(param_shapes) == 2:
        param_shapes = param_shapes[0]  # (params, model_state) initializers
    engine = bundle.cfg.engine()
    wire = engine.wire_bytes_per_round(param_shapes)
    plan = engine.bucket_plan(param_shapes)
    wire_layout = (
        "per-leaf wire"
        if plan is None
        else f"{plan.num_buckets} wire bucket(s)"
    )
    print(
        f"gossip wire: {wire / 1e6:.3f} MB/worker/round ({wire_layout})",
        flush=True,
    )
    if engine.compressed:
        from consensusml_tpu.compress import describe_codec

        # what will actually execute, stage by stage — impl="auto" and
        # fused_wire="auto" both resolve from the backend and the codec,
        # so the log is the only place the choice is visible
        print(
            f"codec: {describe_codec(bundle.cfg.gossip.compressor)}; "
            + (
                "fused one-pass wire"
                if engine.fused_wire_active
                else "two-step wire (compress, then decompress-accumulate)"
            ),
            flush=True,
        )

    # ---- telemetry (consensusml_tpu.obs; docs/observability.md) ---------
    from consensusml_tpu.obs import get_registry, get_tracer

    tracer = get_tracer()
    registry = get_registry()
    telemetry_on = bool(
        args.trace_events
        or args.metrics_prom
        or args.flight_recorder
        or args.obs_cluster_dir
        or args.link_probes
        or args.cost_ledger
        or args.metrics_port is not None
    )
    if telemetry_on:
        # host span recording on; without any sink the tracer stays
        # disabled and spans are bare jax.named_scopes (dict-cheap path)
        tracer.enabled = True
    metrics_http = None
    if args.metrics_port is not None:
        from consensusml_tpu.obs import (
            MetricsServer,
            get_alert_engine,
            get_history,
        )

        # the round loop drives record()/evaluate() from its telemetry
        # tick (no ticker thread here) — the server only surfaces
        # /alerts, /query and /healthz over the same engines
        metrics_http = MetricsServer(
            port=args.metrics_port,
            history=get_history(),
            alerts=get_alert_engine(),
        )
        print(
            f"metrics endpoint: {metrics_http.url()} "
            "(/metrics /traces /requests /alerts /query /healthz)",
            flush=True,
        )
    for k, v in engine.telemetry(param_shapes).items():
        registry.gauge(f"consensusml_{k}").set(v)
    recorder = None
    if args.flight_recorder:
        from consensusml_tpu.obs import FlightRecorder

        recorder = FlightRecorder(args.flight_recorder).install()
        print(f"flight recorder armed: {args.flight_recorder}", flush=True)

    # --native-wire u8: batches arrive as quantized uint8; the dequant
    # runs INSIDE the jitted step (on device) so the host->device wire
    # stays 1/4 size. The WHOLE feature lives in this block: it wraps
    # the loss (hence before step construction) AND rebinds
    # bundle.native_batches to the u8-bound source, so the later
    # batch-source selection needs no knowledge of wire modes.
    # Explicit --native-wire validates loudly; the None default resolves
    # to u8 whenever the config's native path supports it (a quarter of
    # the transfer) and f32 otherwise.
    loss_fn = bundle.loss_fn
    wire_supported = bundle.native_batches is not None and getattr(
        bundle.native_batches, "supports_wire", False
    )
    if args.native_wire == "u8":
        if not args.native_loader:
            print(
                "error: --native-wire u8 requires --native-loader",
                file=sys.stderr,
            )
            return 2
        if bundle.native_batches is None:
            # the accurate diagnosis comes first: without ANY native path
            # the wire format is moot, and the u8-specific message below
            # ("image workloads only") would misdirect the fix
            print(
                f"error: config {bundle.name} has no native loader path",
                file=sys.stderr,
            )
            return 2
        if not wire_supported:
            print(
                f"error: config {bundle.name} has no u8-wire native path "
                "(image workloads only)",
                file=sys.stderr,
            )
            return 2
    native_wire = args.native_wire
    if native_wire is None:
        native_wire = "u8" if args.native_loader and wire_supported else "f32"
    if args.native_loader:
        why = "explicit" if args.native_wire else (
            "auto: image config, --native-wire f32 overrides"
            if native_wire == "u8"
            else "auto: config has no u8 path"
        )
        print(f"native wire: {native_wire} ({why})", flush=True)
    if native_wire == "u8" and args.native_loader and wire_supported:
        import jax.numpy as jnp

        qscale = bundle.native_batches.qscale
        qoff = bundle.native_batches.qoff
        base_loss = bundle.loss_fn
        base_source = bundle.native_batches

        def loss_fn(params, model_state, batch, rng):
            img = batch.get("image")
            if img is not None and img.dtype == jnp.uint8:
                batch = dict(
                    batch, image=jnp.asarray(img, jnp.float32) / qscale - qoff
                )
            return base_loss(params, model_state, batch, rng)

        def _u8_batches(rounds, seed, start=0, **kw):
            return base_source(rounds, seed, start, wire="u8", **kw)

        # the rebound source keeps the capability attributes (configs
        # RunBundle contract) so the train loop's views/prefetch
        # selection still sees them
        for attr in ("supports_wire", "supports_views", "qscale", "qoff"):
            if hasattr(base_source, attr):
                setattr(_u8_batches, attr, getattr(base_source, attr))
        bundle.native_batches = _u8_batches

    if backend == "collective":
        from consensusml_tpu.comm import slice_major_devices

        # slice-major order puts a hierarchical topology's outer axis
        # across slice boundaries (DCN) and keeps inner rings on ICI; on
        # single-slice/CPU hosts the stable sort leaves order unchanged
        devices = slice_major_devices()[: bundle.world_size * per_worker]
        wmesh = WorkerMesh.create(
            bundle.cfg.gossip.topology, devices=devices, model_axes=model_axes
        )
        step = make_collective_train_step(bundle.cfg, loss_fn, wmesh)
        rules = (
            bundle.tp_rules(model_axes[0][0]) if model_axes else None
        )
        shard = lambda s: wmesh.shard_stacked(s, rules=rules)
    else:
        step = make_simulated_train_step(bundle.cfg, loss_fn)
        shard = lambda s: s

    start = 0
    # Elastic resume fires only on an EXPLICIT --workers override that
    # differs from the checkpoint's recorded world; it builds the old-world
    # template instead of (not in addition to) the new-world one.
    elastic_from = (
        ckpt_world
        if args.resume
        and args.workers is not None
        and ckpt_world is not None
        and ckpt_world != bundle.world_size
        else None
    )
    if elastic_from is not None:
        from consensusml_tpu.utils import resize_state

        # template leaves stay jax arrays: orbax takes each leaf's
        # sharding from the template. Build + restore + resize on the CPU
        # backend — host RAM holds the full old-world replica set where a
        # single accelerator's HBM could not (full-scale elastic resume) —
        # then `shard` moves the result onto the worker mesh.
        with jax.default_device(jax.devices("cpu")[0]):
            old_template = init_stacked_state(
                bundle.cfg, bundle.init_params, jax.random.key(args.seed),
                elastic_from,
            )
            restored = _try_restore(args.resume, old_template, lr_flags)
            if restored is None:
                return 2
            resized = resize_state(
                bundle.cfg, restored, bundle.world_size,
                rng=jax.random.key(args.seed + 1),
            )
        state = shard(resized)
        print(
            f"elastic resume: {elastic_from} -> {bundle.world_size} workers "
            "(joiners from consensus mean; gossip state reset)",
            flush=True,
        )
    else:
        init = lambda: init_stacked_state(
            bundle.cfg, bundle.init_params, jax.random.key(args.seed),
            bundle.world_size,
        )
        # built under jit straight into its shards: created eagerly, every
        # worker's replica would first land on device 0, and four
        # GPT-2-medium replicas with optimizer and CHOCO state are 28 GB
        # against a chip's 16
        state = jax.jit(
            init,
            out_shardings=(
                wmesh.stacked_shardings(jax.eval_shape(init), rules)
                if backend == "collective"
                else None
            ),
        )()
        if args.resume:
            restored = _try_restore(args.resume, state, lr_flags)
            if restored is None:
                return 2
            state = restored
    if args.resume:
        from consensusml_tpu.utils import replicated_scalar

        start = replicated_scalar(state.step)
        print(f"resumed from {args.resume} at round {start}", flush=True)
    # where the state actually landed (not where it was asked to): every
    # device that holds a shard of the params
    holders = set().union(
        *(x.sharding.device_set for x in jax.tree.leaves(state.params))
    )
    registry.gauge(
        "consensusml_state_devices",
        "distinct devices holding a shard of the train state",
    ).set(len(holders))
    print(
        f"state: sharded over {len(holders)} device(s) "
        f"{sorted(d.id for d in holders)}",
        flush=True,
    )

    # ExitStack so the exits fire on exception paths too: the JSONL handle
    # (MetricsLogger is a context manager now) and the telemetry sink
    # writes must land even when a round raises mid-run.
    stack = contextlib.ExitStack()
    with stack:
        logger = stack.enter_context(
            MetricsLogger(args.metrics_out, every=args.log_every)
        )
        if args.trace_events:
            stack.callback(
                lambda: print(
                    "trace events: "
                    f"{tracer.write_chrome_trace(args.trace_events)}",
                    flush=True,
                )
            )
        if args.metrics_prom:
            stack.callback(
                lambda: registry.write_prometheus(args.metrics_prom)
            )
        if metrics_http is not None:
            stack.callback(metrics_http.close)
        return _train_loop(
            args, bundle, engine, wire, step, state, start, backend,
            wmesh if backend == "collective" else None,
            logger, tracer, registry, recorder, telemetry_on, scale,
            param_shapes,
        )


def _churn_loop(args, bundle, scale) -> int:
    """The --churn-schedule path: live membership churn on the simulated
    backend (consensusml_tpu.swarm; docs/elasticity.md). Joiners
    gossip-bootstrap from neighbors — no checkpoint read — drops freeze
    the member's replica until rejoin, and training never stops."""
    import jax

    from consensusml_tpu import configs
    from consensusml_tpu.obs import ClusterWriter, get_registry, get_tracer
    from consensusml_tpu.swarm import (
        ChurnSchedule,
        churn_config,
        run_churn,
        validate_schedule,
    )
    from consensusml_tpu.utils import MetricsLogger

    registry = get_registry()
    initial = bundle.world_size
    try:
        schedule = ChurnSchedule.parse(
            args.churn_schedule, initial_world=initial
        )
        cfg = churn_config(bundle.cfg)
        # dry-replay the whole schedule up front: a semantically invalid
        # sequence (e.g. rejoin of a never-dropped member) must be a
        # clean rc=2 here, not a traceback after training started
        validate_schedule(schedule, cfg.gossip.topology, args.rounds)
    except (ValueError, NotImplementedError) as e:
        print(f"error: --churn-schedule: {e}", file=sys.stderr)
        return 2
    capacity = initial + schedule.total_joins
    counts = schedule.counts()
    print(
        f"churn schedule: {schedule.spec()}",
        flush=True,
    )
    print(
        f"swarm: initial={initial} capacity={capacity} "
        f"joins={counts['join']} drops={counts['drop']} "
        f"rejoins={counts['rejoin']} stragglers={counts['straggle']} "
        f"push_sum={cfg.gossip.push_sum!r}",
        flush=True,
    )
    # batches come stacked at CAPACITY; the harness slices to the live
    # world each round, so slot i's stream is churn-independent
    cap_bundle = (
        bundle
        if capacity == initial
        else configs.build(
            bundle.name, scale, data_dir=args.data_dir, world=capacity
        )
    )

    if args.trace_events or args.metrics_prom or args.obs_cluster_dir:
        get_tracer().enabled = True
    history = alerts = None
    # same arming condition as main's telemetry_on: --metrics-port alone
    # must still drive record()/evaluate() or its /alerts endpoint would
    # advertise a plane no tick ever feeds
    if (
        args.trace_events or args.metrics_prom or args.obs_cluster_dir
        or args.flight_recorder or args.link_probes or args.cost_ledger
        or args.metrics_port is not None
    ):
        from consensusml_tpu.obs import get_alert_engine, get_history

        history = get_history()
        alerts = get_alert_engine()
    cluster = None
    if args.obs_cluster_dir:
        cluster = ClusterWriter(
            args.obs_cluster_dir,
            rank=jax.process_index(),
            registry=registry,
            world_size=capacity,
            history=history,
            alerts=alerts,
        )
        print(f"cluster snapshots: {cluster.path}", flush=True)

    # the logger handles JSONL + per-round registry gauges; its console
    # print goes to devnull so the churn-format line below (epoch/active
    # as ints) is the ONE round line, not a near-duplicate pair
    with open(os.devnull, "w") as devnull, MetricsLogger(
        args.metrics_out, every=args.log_every, stream=devnull
    ) as logger:

        def on_round(rnd, row):
            logger.log(rnd, row)
            registry.counter(
                "consensusml_rounds_total", "completed training rounds"
            ).inc()
            registry.gauge("consensusml_round_progress").set(rnd)
            registry.gauge("consensusml_heartbeat_time_seconds").set(
                time.time()
            )
            if rnd % max(1, args.log_every) == 0:
                print(
                    f"[round {rnd}] loss={row['loss']:.4f} "
                    f"consensus_error={row['consensus_error']:.4f} "
                    f"epoch={row['epoch']} active={row['active']}/"
                    f"{row['world']}",
                    flush=True,
                )
            if (rnd + 1) % max(1, args.telemetry_every) == 0:
                registry.snapshot({"round": rnd})
                if history is not None:
                    history.record()
                    alerts.evaluate()
                if args.metrics_prom:
                    registry.write_prometheus(args.metrics_prom)
                if cluster is not None:
                    cluster.write(round=rnd)

        def on_event(row):
            workers = ",".join(str(u) for u in row["workers"])
            detail = row.get("detail") or {}
            extra = (
                f" (bootstrap {detail['bootstrap_rounds']} rounds, "
                f"eps {detail['eps_measured']:.2e})"
                if "bootstrap_rounds" in detail
                else (
                    f" ({detail['duration']} rounds)"
                    if "duration" in detail
                    else ""
                )
            )
            print(
                f"[round {row['round']}] membership {row['kind']}: "
                f"w{workers}{extra}",
                flush=True,
            )
            if cluster is not None:
                cluster.record_event(row)

        report = run_churn(
            cfg,
            bundle.loss_fn,
            bundle.init_params,
            schedule,
            rounds=args.rounds,
            batches=lambda rounds, seed: cap_bundle.batches(rounds, seed),
            seed=args.seed,
            registry=registry,
            on_round=on_round,
            on_event=on_event,
        )
        if args.metrics_prom:
            registry.write_prometheus(args.metrics_prom)
        if cluster is not None:
            cluster.write(round=args.rounds - 1)
    if args.trace_events:
        print(
            f"trace events: {get_tracer().write_chrome_trace(args.trace_events)}",
            flush=True,
        )

    view = report.final_view
    print(
        f"swarm final: epoch={view.epoch} members={view.n_active} active / "
        f"{view.world_size} slots, {len(report.bootstraps)} gossip "
        f"bootstraps (no checkpoint reads), {report.recompiles} step "
        f"rebuilds",
        flush=True,
    )
    print(
        f"final: loss={report.losses[-1]:.4f} "
        f"consensus_error={report.consensus_errors[-1]:.4f}",
        flush=True,
    )
    if args.checkpoint_dir:
        from consensusml_tpu.utils import save_state

        path = save_state(
            os.path.join(args.checkpoint_dir, f"step_{args.rounds}"),
            report.final_state,
        )
        print(f"checkpoint: {path}", flush=True)
    if args.eval_batches > 0:
        from consensusml_tpu.swarm import alive_consensus_state
        from consensusml_tpu.train import evaluate

        # members still DOWN at end of run hold frozen stale replicas;
        # the mean model must aggregate the LIVE swarm only
        result = evaluate(
            cap_bundle.eval_fn,
            alive_consensus_state(report.final_state, view),
            cap_bundle.eval_batches(args.eval_batches, args.seed),
        )
        fmt = lambda d: " ".join(
            f"{k}={float(v):.4f}" for k, v in sorted(d.items())
        )
        print(f"eval[mean-model]: {fmt(result['mean_model'])}", flush=True)
        print(f"eval[worker-avg]: {fmt(result['worker_mean'])}", flush=True)
    return 0


def _train_loop(
    args, bundle, engine, wire, step, state, start, backend, wmesh,
    logger, tracer, registry, recorder, telemetry_on, scale,
    param_shapes,
) -> int:
    """The round loop, split out of :func:`main` so its sinks can be
    ExitStack-managed without indenting half the CLI."""
    import contextlib

    import jax

    from consensusml_tpu.utils import RoundTimer, trace as profile_trace

    timer = RoundTimer(warmup=1)  # round 0 carries XLA compilation
    metrics = {}
    last_saved = None
    profiling = contextlib.nullcontext()
    # multi-controller: host batches are global values (keyed loaders are
    # process-independent), but jit can only auto-place addressable arrays —
    # assemble each round's global jax.Array from per-process shards.
    multiproc = backend == "collective" and jax.process_count() > 1
    from consensusml_tpu.utils import AsyncSaver

    # disk writes overlap the next rounds' compute (sync in multiproc —
    # orbax coordinates the processes inside save)
    saver = AsyncSaver()

    m_rounds = registry.counter(
        "consensusml_rounds_total", "completed training rounds"
    )
    m_wire_total = registry.counter(
        "consensusml_wire_bytes_total",
        "bytes one worker has put on the gossip wire",
    )
    m_latency = registry.histogram(
        "consensusml_round_latency_seconds",
        "wall time of one full training round (inner loop + gossip)",
    )
    m_heartbeat = registry.gauge(
        "consensusml_heartbeat_time_seconds",
        "unix time of this rank's latest completed round (cluster-view "
        "liveness; staleness flags a straggler)",
    )
    m_progress = registry.gauge(
        "consensusml_round_progress",
        "this rank's latest completed round index (cluster-view skew)",
    )

    # ---- cluster observability plane (obs.health/links/cluster) ---------
    from consensusml_tpu.obs import (
        ClusterWriter,
        ConsensusHealthMonitor,
        LinkProber,
    )

    # SLO/alert plane (obs.history/obs.alerts): history rings + the
    # default ruleset, driven from telemetry_tick below; only armed when
    # some telemetry sink exists (the singletons then also feed cluster
    # snapshots, /alerts and flight-recorder dumps)
    history = alerts = None
    if telemetry_on:
        from consensusml_tpu.obs import get_alert_engine, get_history

        history = get_history()
        alerts = get_alert_engine()
    # always on: a few float stores per round, and sustained divergence
    # should be loud even when no sink is configured; with the alert
    # plane armed, episode logs route through its event stream
    health = ConsensusHealthMonitor(
        engine.topology, registry=registry, alerts=alerts
    )
    prober = None
    if args.link_probes:
        prober = LinkProber(
            engine.topology,
            registry=registry,
            devices=wmesh.worker_devices() if wmesh is not None else None,
        )
        # per-edge steady-state wire gauges from the engine accounting
        # (param_shapes: main's eval_shape output, computed once)
        prober.record_wire_rates(engine, param_shapes)
        print(
            f"link probes armed: {len(prober.edges)} edges "
            f"({prober.payload_bytes} B payload)",
            flush=True,
        )
    cluster = None
    if args.obs_cluster_dir:
        cluster = ClusterWriter(
            args.obs_cluster_dir,
            rank=jax.process_index(),
            registry=registry,
            world_size=bundle.world_size,
            history=history,
            alerts=alerts,
        )
        print(f"cluster snapshots: {cluster.path}", flush=True)

    # ---- compiled cost ledger + live HBM accounting (obs.costs/memviz) --
    ledger = accountant = None
    if args.cost_ledger:
        from consensusml_tpu.obs import HbmAccountant, get_cost_ledger

        ledger = get_cost_ledger()
        accountant = HbmAccountant(registry=registry)

    def register_run_costs(state, batch):
        """Round-0 ledger registration (state/batch templates exist,
        nothing has compiled yet): the full train-step executable, and
        — on the simulated backend, whose transport program is the one
        round_simulated lowers — the gossip round under its bucket
        plan. AOT analysis only; the step's own first-call compile is
        untouched (the duplicate compile is this flag's documented
        cost)."""
        row = ledger.register("train.step", step, state, batch)
        print(
            f"cost ledger: train.step {row.flops:.3g} flops "
            f"{row.bytes_accessed:.3g} B accessed, compile "
            f"{row.compile_s * 1e3:.0f} ms",
            flush=True,
        )
        if backend == "simulated":
            gossiped = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                {"params": state.params, "model_state": state.model_state},
            )
            grow = engine.register_costs(ledger, gossiped)
            print(
                f"cost ledger: gossip.round {grow.flops:.3g} flops, "
                f"{grow.meta['buckets']} bucket(s), compile "
                f"{grow.compile_s * 1e3:.0f} ms",
                flush=True,
            )

    def telemetry_tick(rnd, state):
        """The heavier sampled telemetry (--telemetry-every cadence):
        link probes, CHOCO residual fetch, metric snapshot, Prometheus
        rewrite, cluster snapshot."""
        if prober is not None:
            prober.probe_round()
        if accountant is not None:
            accountant.tick()  # live HBM gauges (host bookkeeping only)
        if ledger is not None and ledger.row("train.step") is not None:
            # pair the steady-state measured round with the compiled
            # cost row -> expected-vs-measured attribution gauges
            ledger.observe_measured("train.step", timer.last_lap_s)
        resid = engine.choco_residual(state.gossip)
        if resid is not None:
            registry.gauge(
                "consensusml_choco_residual",
                "CHOCO tracking residual ||s - xhat|| (sampled)",
            ).set(resid)
        registry.snapshot({"round": rnd})
        if history is not None:
            # sample every family into the history rings, then evaluate
            # the SLO/alert rules over the retained windows — fire and
            # clear transitions land on /alerts, in tracer instants and
            # in the cluster snapshot written below
            history.record()
            alerts.evaluate()
        if args.metrics_prom:
            registry.write_prometheus(args.metrics_prom)
        if cluster is not None:
            cluster.write(round=rnd)

    def run_eval(state, rnd):
        # evaluate() caches its jitted step per eval_fn, so periodic
        # calls don't recompile
        from consensusml_tpu.train import evaluate

        result = evaluate(
            bundle.eval_fn, state,
            bundle.eval_batches(args.eval_batches, args.seed),
        )
        fmt = lambda d: " ".join(
            f"{k}={float(v):.4f}" for k, v in sorted(d.items())
        )
        tag = f"[round {rnd}] " if rnd is not None else ""
        print(
            f"{tag}eval[mean-model]: {fmt(result['mean_model'])}\n"
            f"{tag}eval[worker-avg]: {fmt(result['worker_mean'])}",
            flush=True,
        )
        return result

    last_exported = None

    def export_art(state, rnd):
        # synchronous on purpose: the artifact is the consensus mean —
        # 1/W of the checkpoint — and the train->serve handoff must be
        # complete when the log line lands
        nonlocal last_exported
        from consensusml_tpu.serve.export import export_serving, serving_meta

        path = export_serving(
            args.export_serving, state,
            config_name=bundle.name, scale=scale, round=rnd,
        )
        last_exported = rnd
        gen = serving_meta(path).get("generation", "?")
        print(
            f"serving artifact: {path} (round {rnd}, generation {gen})",
            flush=True,
        )

    batch_source = bundle.batches
    if args.native_loader:
        from consensusml_tpu import native

        if bundle.native_batches is None:
            print(
                f"error: config {bundle.name} has no native loader path",
                file=sys.stderr,
            )
            return 2
        if not native.available():
            print(
                "error: --native-loader requested but the native library "
                "is unavailable (see consensusml_tpu.native)",
                file=sys.stderr,
            )
            return 2
        batch_source = bundle.native_batches
    watchdog = None
    if args.round_timeout > 0:
        from consensusml_tpu.utils import ProgressWatchdog

        on_timeout = None
        if recorder is not None:
            def on_timeout(reason):
                registry.counter(
                    "consensusml_watchdog_timeouts_total",
                    "watchdog round-progress timeouts",
                ).inc()
                registry.snapshot({"watchdog_timeout": True})
                recorder.dump(reason)

        watchdog = ProgressWatchdog(
            args.round_timeout, label="train round", on_timeout=on_timeout
        ).start()
    # ---- overlapped host->device feed (data.prefetch) -------------------
    # The prefetcher stages round r+1's batch on device (non-blocking
    # device_put, placed where the step consumes it) while round r runs;
    # the native image path additionally goes zero-copy: ring slots pin
    # as staging buffers (views=True) and release on transfer completion.
    # Multi-controller runs keep host batches (global arrays are
    # assembled below) but still overlap the host-side batch assembly.
    from consensusml_tpu.data.prefetch import DevicePrefetcher, prefetch_to_device
    from consensusml_tpu.train import batch_placement

    use_views = (
        args.prefetch_depth > 0
        and not multiproc
        and getattr(batch_source, "supports_views", False)
    )
    if use_views:
        # prefetch sizes the native ring too (each in-flight transfer
        # pins a slot), so the window is forwarded to the source
        source = batch_source(
            args.rounds, args.seed, start,
            views=True, prefetch=args.prefetch_depth,
        )
    else:
        source = batch_source(args.rounds, args.seed, start)
    feed = prefetch_to_device(
        source,
        args.prefetch_depth,
        placement=batch_placement(backend, wmesh),
        place=not multiproc,
    )
    batch_shardings = None
    prev_alive_mask = None
    try:
        for i, batch in enumerate(feed):
            rnd = start + i
            if multiproc:
                # shardings depend only on the (fixed) batch structure —
                # compute once, reuse every round
                if batch_shardings is None:
                    batch_shardings = wmesh.stacked_shardings(batch)
                batch = wmesh.shard_stacked(batch, shardings=batch_shardings)
            if ledger is not None and i == 0:
                register_run_costs(state, batch)
            if args.profile_dir and i == 2:
                profiling = profile_trace(args.profile_dir)
                profiling.__enter__()
            with tracer.span("train.round", round=rnd):
                with timer.lap(metrics_fn=lambda: metrics):
                    with tracer.span("round.dispatch"):
                        state, metrics = step(state, batch)
            if args.profile_dir and i == 4:
                profiling.__exit__(None, None, None)
                profiling = contextlib.nullcontext()
                print(f"profile trace: {args.profile_dir}", flush=True)
            # the (world,) participation vector feeds the per-rank fault
            # counters below, not the scalar log line
            alive_mask = metrics.pop("alive_mask", None)
            if "moe_rows" in metrics:  # an expert layer's counters: arrays, onto the registry
                from consensusml_tpu.models.moe import record_expert_counts
                from consensusml_tpu.models.nemotron_h import FIRST_STEP_KEYS

                mc = bundle.model.config
                rows, absent = jax.device_get(
                    (metrics.pop("moe_rows"), metrics.pop("moe_absent_pairs"))
                )  # one small fetch a round, with the loss's
                for shown in FIRST_STEP_KEYS:  # the first step's: left on the device
                    metrics.pop(shown, None)
                if "mtp_loss" in metrics:  # summed over inner steps and workers, like the counters
                    metrics["mtp_loss"] = metrics["mtp_loss"] / (bundle.cfg.h * bundle.world_size)
                    registry.gauge(
                        "consensusml_mtp_loss",
                        "the multi-token-prediction module's loss (tokens two ahead), mean of the round",
                    ).set(float(metrics["mtp_loss"]))
                record_expert_counts(
                    rows, absent, mc.expert_layers, mc.held_start,
                    calls=bundle.cfg.h * bundle.world_size,
                )
            logger.log(rnd, metrics)  # float() fetches => a real execution fence
            # per-round registry feed: a few float stores — cheap enough to
            # stay on unconditionally (docs/observability.md schema)
            m_rounds.inc()
            m_wire_total.inc(wire)
            m_latency.observe(timer.last_lap_s)
            m_heartbeat.set(time.time())
            m_progress.set(rnd)
            if "consensus_error" in metrics:
                cdist = float(metrics["consensus_error"])
                registry.gauge(
                    "consensusml_consensus_distance",
                    "post-gossip consensus distance sqrt(mean_i ||x_i - xbar||^2)",
                ).set(cdist)
                # measured-decay-vs-spectral-bound check; loud on
                # sustained divergence (obs.health)
                health.observe(rnd, cdist)
            registry.gauge(
                "consensusml_round_stall_seconds",
                "host wait at the round's execution fence (overlap headroom)",
            ).set(timer.last_fence_s)
            if timer.last_lap_s > 0:
                registry.gauge(
                    "consensusml_inner_steps_per_sec",
                    "local optimizer steps per second per worker",
                ).set(bundle.cfg.h / timer.last_lap_s)
            if "alive_frac" in metrics:
                from consensusml_tpu.consensus import record_fault_metrics

                # the mask feeds the per-rank labeled drop/recovery
                # counters (one small fetch; only on fault-model runs)
                mask = (
                    None if alive_mask is None else jax.device_get(alive_mask)
                )
                record_fault_metrics(
                    float(metrics["alive_frac"]),
                    alive=mask,
                    prev_alive=prev_alive_mask,
                )
                prev_alive_mask = mask
            if telemetry_on and (rnd + 1) % max(1, args.telemetry_every) == 0:
                telemetry_tick(rnd, state)
            if watchdog is not None:
                watchdog.beat(f"round {rnd}")
            if (
                args.eval_every > 0
                and (rnd + 1) % args.eval_every == 0
                # keep the xprof window (rounds 2-3) pure training compute
                and isinstance(profiling, contextlib.nullcontext)
                # the end-of-run eval below covers a final-round boundary
                and rnd + 1 != start + args.rounds
            ):
                if watchdog is not None:
                    # eval (incl. its first-call XLA compile) has no per-round
                    # budget: suspend enforcement entirely rather than grant
                    # it one round's allowance, and re-arm when it completes
                    watchdog.pause()
                run_eval(state, rnd)
                if watchdog is not None:
                    watchdog.beat(f"eval done @ round {rnd}")
            if (
                args.checkpoint_dir
                and args.checkpoint_every
                and (rnd + 1) % args.checkpoint_every == 0
            ):
                saver.submit(args.checkpoint_dir, state, step=rnd + 1)
                last_saved = rnd + 1
            if (
                args.export_serving
                and args.checkpoint_every
                and (rnd + 1) % args.checkpoint_every == 0
            ):
                # serving handoff rides the checkpoint cadence (latest
                # wins at DIR) — a serving fleet can roll mid-run
                export_art(state, rnd + 1)
    finally:
        # stop the prefetch thread (and close the underlying loader/
        # generator) on every exit path, including mid-run exceptions
        close = getattr(feed, "close", None)
        if close is not None:
            close()
    if isinstance(feed, DevicePrefetcher) and feed.batches_out:
        # the acceptance signal for the overlapped feed: total host wait
        # for data across the run (~0 when H2D fully hides under compute)
        print(
            f"feed: {feed.batches_out} rounds prefetched, stall "
            f"{feed.stall_seconds_total:.3f}s total "
            f"({1e3 * feed.last_stall_s:.1f} ms last round)",
            flush=True,
        )
    if not isinstance(profiling, contextlib.nullcontext):
        # run ended before round 4: close the trace so the dump is valid
        profiling.__exit__(None, None, None)
        print(f"profile trace: {args.profile_dir}", flush=True)
    if args.checkpoint_dir and last_saved != start + args.rounds:
        saver.submit(args.checkpoint_dir, state, step=start + args.rounds)
    if watchdog is not None:
        watchdog.stop()
    if args.checkpoint_dir:
        saver.wait()
        print(f"checkpoint: {saver.last_path}", flush=True)
    if args.export_serving and last_exported != start + args.rounds:
        export_art(state, start + args.rounds)
    if ledger is not None and accountant is not None and metrics:
        # end-of-run expected-vs-measured attribution + the three-way
        # HBM reconciliation (docs/memory.md "Reconciliation") — BEFORE
        # the final telemetry tick so the last cluster snapshot carries
        # the reconciled gauges
        if ledger.row("train.step") is not None:
            attr = ledger.observe_measured(
                "train.step", timer.stats().p50_s
            )
            print(
                "cost attribution: train.step measured "
                f"{1e3 * attr['measured_s']:.1f} ms vs {attr['bound']}-"
                f"bound floor {1e3 * attr['expected_s']:.2f} ms "
                f"({attr['ratio_to_floor']:.1f}x)",
                flush=True,
            )
        analytic = None
        try:
            from consensusml_tpu.obs.memviz import _load_hbm_model

            hm = _load_hbm_model()
            if hm is not None:
                pred = hm.predict(
                    bundle.name, scale, world=bundle.world_size
                )
                analytic = float(pred["predicted_peak_bytes"])
                if backend == "simulated":
                    # predict() models ONE worker's device; the simulated
                    # backend stacks every worker on this one device
                    analytic *= bundle.world_size
        except Exception as e:
            print(f"hbm reconciliation: no analytic side ({e})", flush=True)
        row = ledger.row("train.step")
        # a run shorter than --telemetry-every has no in-loop sample
        # yet; without this tick the live side would be a fake zero
        accountant.tick()
        rec = accountant.reconcile(
            analytic_bytes=analytic,
            compiled_bytes=float(row.peak_bytes) if row else None,
        )
        drift = ", ".join(
            f"{k} {v:+.1f}%" for k, v in sorted(rec["drift_pct"].items())
        )
        print(
            "hbm reconciliation: analytic "
            f"{(rec['analytic_bytes'] or 0) / 1e6:.1f} MB vs compiled "
            f"{(rec['compiled_bytes'] or 0) / 1e6:.1f} MB vs live "
            f"{(rec['live_peak_bytes'] or 0) / 1e6:.1f} MB"
            + (f" ({drift})" if drift else ""),
            flush=True,
        )
    if (
        telemetry_on
        and metrics
        # skip when the loop's own cadence just ticked this round —
        # a duplicate tick would re-fetch the full CHOCO state at exit
        and (start + args.rounds) % max(1, args.telemetry_every) != 0
    ):
        # final sample so short runs (< --telemetry-every rounds) still
        # land a snapshot; the ExitStack writes the prom/trace files
        telemetry_tick(start + args.rounds - 1, state)
    elif cluster is not None:
        # cadence just ticked: the snapshot is current, but refresh the
        # heartbeat so the cluster view sees a clean exit
        cluster.write(round=start + args.rounds - 1)
    if metrics:
        print(f"timing: {timer.stats().format()}", flush=True)
        print(
            f"final: loss={float(metrics['loss']):.4f} "
            f"consensus_error={float(metrics['consensus_error']):.4f}",
            flush=True,
        )
    if args.eval_batches > 0:  # config's eval support validated up front
        run_eval(state, None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
