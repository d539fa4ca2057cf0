"""Jaxpr contract checks: invariants of the traced train program.

The gossip stack's perf story rests on properties of the COMPILED round
program that no unit test of the math can see: the round must not call
back into the host (a callback serializes the device pipeline every
round), must not silently promote to f64 (4x wire + HBM on a path sized
in f32), must issue exactly the collectives the schedule verifier
proved, and must hit the jit cache on every round after the first (a
signature that drifts between consecutive rounds recompiles every
round — minutes per round at pod scale, the classic "why is round 2 as
slow as round 1" regression).

For each config in :mod:`consensusml_tpu.configs` (smoke scale, CPU):

- ``host-callback`` — no callback/debug primitives anywhere in the
  train-step jaxpr (checked recursively through scan/cond/pjit bodies);
- ``f64-promotion`` — no float64/complex128 intermediate anywhere;
- ``collective-count`` — the gossip round, traced per-worker under
  ``shard_map`` on the config's topology, contains exactly as many
  ``ppermute`` equations as the schedule materializer predicts from the
  topology + bucket plan (and none at all for psum topologies). This
  ties the PROVEN schedule to the TRACED program: if the engine ever
  issues a collective the verifier did not model, this contract fails
  rather than the verifier silently passing;
- ``recompile`` — tracing the train step with the output shapes of
  round r as the input of round r+1 yields a byte-identical canonical
  jaxpr: two consecutive rounds share one compilation. Dtype drift
  (e.g. a weak-type f32 scalar hardening), shape drift, or a
  config-dependent branch on the round counter all fail this.

Everything traces abstractly (``jax.make_jaxpr`` / ``jax.eval_shape``):
no parameters are materialized, no program executes, no TPU is needed.
The train-step contracts run on the simulated backend (identical round
semantics, cross-validated by tests); the collective-count contract
traces the collective engine itself under ``shard_map`` on the CPU
mesh.
"""

from __future__ import annotations

import hashlib
from typing import Any

from consensusml_tpu.analysis.findings import Finding

__all__ = [
    "check_config",
    "check_all_configs",
    "check_fused_wire",
    "count_primitives",
]

PASS = "jaxpr"

_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call", "debug_print",
}
_BAD_DTYPES = {"float64", "complex128"}


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub)
                elif hasattr(sub, "jaxpr"):
                    yield from _iter_eqns(sub.jaxpr)


def count_primitives(jaxpr) -> dict[str, int]:
    """Recursive primitive histogram of a (closed) jaxpr."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    counts: dict[str, int] = {}
    for eqn in _iter_eqns(jaxpr):
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
    return counts


def _canonical_hash(closed_jaxpr) -> str:
    """Hash of the jaxpr's canonical printed form. Var names in jax's
    printer are assigned in traversal order, so two traces of the same
    program print identically — and any structural difference (extra
    op, dtype change, different constant) changes the text."""
    text = closed_jaxpr.pretty_print() if hasattr(
        closed_jaxpr, "pretty_print"
    ) else str(closed_jaxpr)
    return hashlib.sha256(text.encode()).hexdigest()


def _shape_only(tree: Any):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def _stacked_state_and_batch(bundle):
    """Abstract stacked TrainState + one concrete round batch (smoke
    data is procedural and tiny; the state is never materialized)."""
    import jax

    from consensusml_tpu.train import init_stacked_state

    state = jax.eval_shape(
        lambda rng: init_stacked_state(
            bundle.cfg, bundle.init_params, rng, bundle.world_size
        ),
        jax.random.key(0),
    )
    batch = next(iter(bundle.batches(1, 0)))
    return state, _shape_only(batch)


def _callback_f64_findings(closed, mk, what: str) -> list[Finding]:
    """The two program-purity contracts shared by the train step and the
    serving decode step: no host callbacks, no f64/complex128."""
    findings: list[Finding] = []
    counts = count_primitives(closed)
    for prim in sorted(set(counts) & _CALLBACK_PRIMS):
        findings.append(
            mk(
                "host-callback", prim,
                f"{what} traces a host callback ({prim} x"
                f"{counts[prim]}): every round would fence the device "
                "pipeline on the host",
            )
        )
    bad = set()
    for eqn in _iter_eqns(closed.jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in _BAD_DTYPES:
                bad.add((eqn.primitive.name, dt))
    for prim, dt in sorted(bad):
        findings.append(
            mk(
                "f64-promotion", f"{prim}:{dt}",
                f"{what} computes in {dt} (via {prim}): doubles "
                "wire and HBM on a path budgeted in f32 — find the "
                "promoting op (python float op on a traced value, "
                "np.float64 constant, ...)",
            )
        )
    return findings


def _check_step_jaxpr(name: str, bundle) -> list[Finding]:
    import jax

    from consensusml_tpu.train import make_simulated_train_step

    mk = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "train_step", detail, msg
    )
    step = make_simulated_train_step(bundle.cfg, bundle.loss_fn)
    state, batch = _stacked_state_and_batch(bundle)
    closed = jax.make_jaxpr(step)(state, batch)
    findings = _callback_f64_findings(closed, mk, "train step")

    # recompile contract: round r's OUTPUT shapes, fed back as round
    # r+1's input, must retrace to the identical program
    out_state_shapes, _metrics = jax.eval_shape(step, state, batch)
    h1 = _canonical_hash(closed)
    h2 = _canonical_hash(jax.make_jaxpr(step)(out_state_shapes, batch))
    if h1 != h2:
        findings.append(
            mk(
                "recompile", "signature-hash",
                "round r+1 (fed round r's output state) traces to a "
                "DIFFERENT program than round r — the jit cache misses "
                "every round; diff the two jaxprs for the drifting "
                "dtype/shape/weak-type",
            )
        )
    # ... and the state must be shape-stable outright, or the donated
    # buffers cannot be reused
    in_flat = jax.tree.leaves(_shape_only(state))
    out_flat = jax.tree.leaves(out_state_shapes)
    drift = [
        (a.shape, a.dtype, b.shape, b.dtype)
        for a, b in zip(in_flat, out_flat)
        if a.shape != b.shape or a.dtype != b.dtype
    ]
    if len(in_flat) != len(out_flat) or drift:
        findings.append(
            mk(
                "recompile", "state-drift",
                f"TrainState changes structure across a round "
                f"({len(in_flat)} -> {len(out_flat)} leaves, "
                f"{len(drift)} leaf shape/dtype changes): donation and "
                "the jit cache both break",
            )
        )
    return findings


def _check_collective_count(name: str, bundle) -> list[Finding]:
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from consensusml_tpu.analysis import schedule as sched
    from consensusml_tpu.train.local_sgd import _gossiped

    findings: list[Finding] = []
    engine = bundle.cfg.engine()
    cfg = engine.config
    topo = engine.topology
    mk = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "gossip_round", detail, msg
    )
    if (
        cfg.push_sum_enabled
        or cfg.overlap
        or cfg.faults is not None
        or cfg.codec_warmup_rounds > 0
        or cfg.codec_refresh_every > 0
        or topo.is_time_varying
    ):
        # cond/switch trace BOTH wire layouts into one jaxpr; a static
        # per-round count is not defined there
        return findings
    if len(jax.devices()) < topo.world_size:
        return [
            mk(
                "collective-count", "no-mesh",
                f"cannot trace: {topo.world_size} workers but only "
                f"{len(jax.devices())} devices "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count)",
            )
        ]

    from consensusml_tpu.comm import WorkerMesh

    # per-worker gossiped-tree shapes (params + model_state)
    probe = jax.eval_shape(bundle.init_params, jax.random.key(0))
    if isinstance(probe, tuple) and len(probe) == 2:
        params, model_state = probe
    else:
        params, model_state = probe, {}
    tree = _gossiped(params, model_state)
    world = topo.world_size
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((world,) + tuple(x.shape), x.dtype),
        tree,
    )
    wmesh = WorkerMesh.create(topo, platform="cpu")

    def round_fn(t):
        st = engine.init_state(t)
        out, _ = engine.round_collective(t, st, step=np.int32(0))
        return out

    f = jax.shard_map(
        round_fn,
        mesh=wmesh.mesh,
        in_specs=P(*topo.axis_names),
        out_specs=P(*topo.axis_names),
    )
    counts = count_primitives(jax.make_jaxpr(f)(stacked))
    traced = counts.get("ppermute", 0)
    predicted = sum(
        1
        for op in sched.materialize_schedules(engine, tree)[0]
        if op.kind == "ppermute"
    )
    if traced != predicted:
        findings.append(
            mk(
                "collective-count", "ppermute",
                f"gossip round traces {traced} ppermutes but the "
                f"verified schedule models {predicted} — the engine "
                "issues collectives the schedule verifier never "
                "checked (or the wire layout regressed); update "
                "analysis/schedule.py alongside the engine",
            )
        )
    if topo.uses_psum and traced != 0:
        findings.append(
            mk(
                "collective-count", "psum-topology-ppermute",
                f"dense (psum) topology traces {traced} ppermutes; the "
                "dense wire must stay a single reduction",
            )
        )
    return findings


def check_fused_wire(world: int = 8) -> list[Finding]:
    """Contracts of the FUSED one-pass gossip wire (ROADMAP item 5 /
    docs/gossip_bucketing.md "Fused wire"): trace ``round_collective``
    for a representative fused engine per topology class and assert, on
    the traced program itself:

    - ``fused-active`` — the engine engages the fused wire at all
      (bucketed transport + a codec advertising fused kernels under
      ``fused_wire="auto"``); a silent fallback to the two-step path
      would pass every other contract while fusing nothing;
    - ``kernel-count`` — exactly ONE ``pallas_call`` per bucket per
      kernel stage per innovation exchange: encode + decode per bucket
      on ppermute topologies, encode only on psum topologies (the dense
      receive decodes in plain ops under the reduction). More means a
      stage un-fused (extra HBM round-trips — the regression this wire
      exists to prevent); fewer means a bucket fell off the kernel path;
    - ``collective-count`` — the fused program's traced ppermute count
      still equals the schedule verifier's model (fusion changes HBM
      traffic, never the wire: same payload leaves, same collectives);
    - the shared purity contracts (no host callbacks, no f64).

    Traced with the codec's ``interpret`` impl so the kernels appear as
    ``pallas_call`` equations on any host — the compiled TPU program has
    the same jaxpr modulo lowering.
    """
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from consensusml_tpu.analysis import schedule as sched
    from consensusml_tpu.comm import WorkerMesh
    from consensusml_tpu.compress import PallasInt8Compressor
    from consensusml_tpu.consensus import ConsensusEngine, GossipConfig
    from consensusml_tpu.topology import DenseTopology, RingTopology

    findings: list[Finding] = []
    if len(jax.devices()) < world:
        return [
            Finding(
                PASS, "kernel-count", "fused-wire", "gossip_round",
                "no-mesh",
                f"cannot trace the fused wire: {world} workers but only "
                f"{len(jax.devices())} devices "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count)",
            )
        ]
    comp = PallasInt8Compressor(chunk=128, impl="interpret")
    # two f32 leaves sized to split into multiple buckets at a small cap,
    # exercising the per-bucket (not per-round) kernel accounting
    tree = {
        "w": jax.ShapeDtypeStruct((4096, 16), jax.numpy.float32),
        "b": jax.ShapeDtypeStruct((513,), jax.numpy.float32),
    }
    for topo in (RingTopology(world), DenseTopology(world)):
        tag = type(topo).__name__.removesuffix("Topology").lower()
        mk = lambda rule, detail, msg, tag=tag: Finding(
            PASS, rule, f"fused-wire:{tag}", "gossip_round", detail, msg
        )
        engine = ConsensusEngine(
            GossipConfig(
                topology=topo, compressor=comp, gamma=0.5,
                bucket_bytes=64 * 1024,
            )
        )
        if not engine.fused_wire_active:
            findings.append(
                mk(
                    "fused-active", "two-step-fallback",
                    "a bucketed engine with a fused-capable codec "
                    "(PallasInt8) does not engage the fused wire under "
                    "fused_wire='auto' — the one-pass kernels silently "
                    "fell back to the two-step path",
                )
            )
            continue
        plan = engine.bucket_plan(tree)
        stages = 1 if topo.uses_psum else 2  # psum decodes in plain ops
        expected = stages * plan.num_buckets * engine.config.gossip_steps
        stacked = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (world,) + tuple(x.shape), x.dtype
            ),
            tree,
        )
        wmesh = WorkerMesh.create(topo, platform="cpu")

        def round_fn(t, engine=engine):
            st = engine.init_state(t)
            out, _ = engine.round_collective(t, st, step=np.int32(0))
            return out

        f = jax.shard_map(
            round_fn,
            mesh=wmesh.mesh,
            in_specs=P(*topo.axis_names),
            out_specs=P(*topo.axis_names),
        )
        closed = jax.make_jaxpr(f)(stacked)
        findings += _callback_f64_findings(
            closed, mk, f"fused {tag} gossip round"
        )
        counts = count_primitives(closed)
        traced_kernels = counts.get("pallas_call", 0)
        if traced_kernels != expected:
            findings.append(
                mk(
                    "kernel-count", "pallas_call",
                    f"fused {tag} round traces {traced_kernels} "
                    f"pallas_call(s) but the one-pass wire contract is "
                    f"{expected} ({stages} stage(s) x {plan.num_buckets} "
                    f"buckets x {engine.config.gossip_steps} gossip "
                    "step(s)) — a stage un-fused (extra HBM round-trips) "
                    "or a bucket fell off the kernel path",
                )
            )
        traced = counts.get("ppermute", 0)
        predicted = sum(
            1
            for op in sched.materialize_schedules(engine, tree)[0]
            if op.kind == "ppermute"
        )
        if traced != predicted:
            findings.append(
                mk(
                    "collective-count", "ppermute",
                    f"fused {tag} round traces {traced} ppermutes but the "
                    f"verified schedule models {predicted} — fusion must "
                    "change HBM traffic, never the wire (same payload "
                    "leaves, same collectives); update "
                    "analysis/schedule.py alongside the fused wire",
                )
            )
    return findings


def _check_decode_jaxpr(name: str, bundle) -> list[Finding]:
    """Serving decode-step contracts (causal-LM configs only).

    Steady-state serving lives and dies by the same compiled-program
    invariants as training: a host callback inside the decode step
    fences the device once PER TOKEN, f64 doubles the KV cache, and a
    program whose signature drifts between consecutive decode steps
    recompiles mid-request — the serving engine's zero-recompile
    contract (docs/serving.md). Traced abstractly on the exact jit the
    engine runs (:func:`consensusml_tpu.serve.decode.make_decode_fn`).
    """
    import jax
    import jax.numpy as jnp

    from consensusml_tpu.serve import decode as D

    if bundle.model is None or not D.supports_decode(bundle.model):
        return []
    mk = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "decode_step", detail, msg
    )
    dm = D.DecodeModel.wrap(bundle.model)
    slots, max_len = 4, min(dm.max_len, 32)
    probe = jax.eval_shape(bundle.init_params, jax.random.key(0))
    params = probe[0] if isinstance(probe, tuple) and len(probe) == 2 else probe
    cache = jax.eval_shape(lambda: D.init_cache(dm, slots, max_len))
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32)
    positions = jax.ShapeDtypeStruct((slots,), jnp.int32)
    samp = _sampling_structs(slots)
    decode = D.make_decode_fn(dm)
    closed = jax.make_jaxpr(decode)(params, cache, tokens, positions, *samp)
    findings = _callback_f64_findings(closed, mk, "decode step")

    # recompile contract: step r's OUTPUT cache, fed back as step r+1's
    # input (exactly what the engine loop does every token), must trace
    # to the byte-identical program — zero recompiles across decode
    # steps at ANY slot occupancy / length / greedy-vs-sampled mix
    # (fill level AND sampling parameters are data)
    out_tokens, out_cache = jax.eval_shape(
        decode, params, cache, tokens, positions, *samp
    )
    findings += _hash_stable(
        mk, decode, closed,
        (params, out_cache, out_tokens, positions, *samp),
        "decode", "signature-hash",
    )
    findings += _cache_drift(
        mk, cache, out_cache, "the KV cache", "cache-drift",
        "donation and the jit cache both break",
    )
    return findings


def _sampling_structs(slots: int | None = None):
    """Abstract per-slot sampling triple ``(temperature, top_p, seeds)``
    — scalars when ``slots`` is None (the prefill signature)."""
    import jax
    import jax.numpy as jnp

    shape = () if slots is None else (slots,)
    return (
        jax.ShapeDtypeStruct(shape, jnp.float32),
        jax.ShapeDtypeStruct(shape, jnp.float32),
        jax.ShapeDtypeStruct(shape, jnp.uint32),
    )


def _hash_stable(mk, fn, closed, out_args, what: str, detail: str) -> list[Finding]:
    """Step-over-step recompile contract for one jitted serving stage:
    feeding step r's OUTPUT arrays back as step r+1's input must retrace
    to the byte-identical canonical jaxpr (one compile serves steady
    state). ``closed`` is step r's ALREADY-traced jaxpr — every caller
    holds it from the callback/f64 pass, so only step r+1 traces here."""
    import jax

    h1 = _canonical_hash(closed)
    h2 = _canonical_hash(jax.make_jaxpr(fn)(*out_args))
    if h1 != h2:
        return [
            mk(
                "recompile", detail,
                f"{what} step r+1 (fed step r's outputs) traces to a "
                "DIFFERENT program than step r — the engine recompiles "
                "in steady state; diff the two jaxprs for the drifting "
                "dtype/shape/weak-type",
            )
        ]
    return []


def _cache_drift(
    mk, cache_in, cache_out, what: str, detail: str, tail: str
) -> list[Finding]:
    """Structure/shape/dtype stability of a serving cache pytree across
    one step (the other half of the recompile contract: donation and the
    jit cache both key on it)."""
    import jax

    in_flat = jax.tree.leaves(cache_in)
    out_flat = jax.tree.leaves(cache_out)
    drift = [
        1
        for a, b in zip(in_flat, out_flat)
        if a.shape != b.shape or a.dtype != b.dtype
    ]
    if len(in_flat) != len(out_flat) or drift:
        return [
            mk(
                "recompile", detail,
                f"{what} changes structure across a step "
                f"({len(in_flat)} -> {len(out_flat)} leaves, "
                f"{len(drift)} leaf shape/dtype changes): {tail}",
            )
        ]
    return []


def _check_paged_stage_jaxprs(name: str, bundle) -> list[Finding]:
    """Paged serving-stage contracts (causal-LM configs only).

    The pool engine (``serve/pool/``) runs THREE separately-jitted
    stages — full prefill, prefix-suffix prefill (the prefix cache's
    unshared-suffix admission, including its in-trace copy-on-write),
    and decode; each carries the full contract set INDEPENDENTLY — a
    clean decode jaxpr does not excuse a host callback in the prefill
    scatter:

    - no host callbacks anywhere, in particular not in the block-index
      computation (``physical = table[s, p // bs]`` must stay on device
      — a host round-trip there fences the pipeline once per token) and
      not in the prefix path's COW copy (divergence is resolved
      HOST-side at planning time; the jit only ever sees two block ids);
    - no f64/complex128 (block indices are int32; KV pages are the
      model's compute dtype);
    - step-over-step canonical-jaxpr hash stable PER STAGE: prefill's
      output pages feed the next prefill, decode's output pages feed the
      next decode — both must retrace byte-identically, and the page
      pytree must be structure/shape/dtype-stable (donation depends on
      it). The prefix stage keys on the SUFFIX bucket alone — one
      executable per bucket regardless of how an admission splits into
      matched prefix + computed suffix, which is what keeps the
      zero-recompile contract intact under any hit pattern.
    """
    import jax
    import jax.numpy as jnp

    from consensusml_tpu.serve import decode as D
    from consensusml_tpu.serve import pool as P

    if bundle.model is None or not D.supports_decode(bundle.model):
        return []
    findings: list[Finding] = []
    dm = D.DecodeModel.wrap(bundle.model)
    slots, max_len, bs = 4, min(dm.max_len, 32), 8
    blocks_per_slot = max_len // bs
    num_blocks = slots * blocks_per_slot + 1
    probe = jax.eval_shape(bundle.init_params, jax.random.key(0))
    params = probe[0] if isinstance(probe, tuple) and len(probe) == 2 else probe
    pages = jax.eval_shape(lambda: P.init_pages(dm, num_blocks, bs))

    # -- prefill stage (traced at one representative bucket) ---------------
    mkp = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "paged_prefill", detail, msg
    )
    prefill = P.make_paged_prefill_fn(dm)
    ids = jax.ShapeDtypeStruct((1, max_len), jnp.int32)
    length = jax.ShapeDtypeStruct((), jnp.int32)
    block_row = jax.ShapeDtypeStruct((blocks_per_slot,), jnp.int32)
    samp1 = _sampling_structs(None)
    closed = jax.make_jaxpr(prefill)(
        params, pages, ids, length, block_row, *samp1
    )
    findings += _callback_f64_findings(closed, mkp, "paged prefill stage")
    _tok, _logits, prefill_pages = jax.eval_shape(
        prefill, params, pages, ids, length, block_row, *samp1
    )
    findings += _hash_stable(
        mkp, prefill, closed,
        (params, prefill_pages, ids, length, block_row, *samp1),
        "paged prefill", "signature-hash",
    )

    # -- prefix-suffix prefill stage (traced at the same bucket) -----------
    mkx = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "paged_prefix_prefill", detail, msg
    )
    prefix_prefill = P.make_prefix_prefill_fn(dm)
    pargs = P.prefix_prefill_cost_args(max_len, bs, blocks_per_slot)
    closed = jax.make_jaxpr(prefix_prefill)(params, pages, *pargs)
    findings += _callback_f64_findings(closed, mkx, "paged prefix-prefill stage")
    _tok, _logits, prefix_pages = jax.eval_shape(
        prefix_prefill, params, pages, *pargs
    )
    findings += _hash_stable(
        mkx, prefix_prefill, closed,
        (params, prefix_pages, *pargs),
        "paged prefix prefill", "signature-hash",
    )

    # -- decode stage ------------------------------------------------------
    mkd = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "paged_decode", detail, msg
    )
    decode = P.make_paged_decode_fn(dm)
    table = jax.ShapeDtypeStruct((slots, blocks_per_slot), jnp.int32)
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32)
    positions = jax.ShapeDtypeStruct((slots,), jnp.int32)
    samp = _sampling_structs(slots)
    closed = jax.make_jaxpr(decode)(
        params, pages, table, tokens, positions, *samp
    )
    findings += _callback_f64_findings(closed, mkd, "paged decode stage")
    out_tokens, out_pages = jax.eval_shape(
        decode, params, pages, table, tokens, positions, *samp
    )
    findings += _hash_stable(
        mkd, decode, closed,
        (params, out_pages, table, out_tokens, positions, *samp),
        "paged decode", "signature-hash",
    )
    for stage, mk, out in (
        ("prefill", mkp, prefill_pages),
        ("prefix prefill", mkx, prefix_pages),
        ("decode", mkd, out_pages),
    ):
        findings += _cache_drift(
            mk, pages, out, f"the paged {stage} stage's page pytree",
            "pages-drift",
            "the pool is one fixed allocation for the engine's life — "
            "donation and the jit cache both break",
        )
    return findings


def _check_spec_stage_jaxprs(name: str, bundle) -> list[Finding]:
    """Speculative-decode stage contracts (causal-LM configs only).

    The spec pipeline (``serve/pool/spec.py``) adds TWO executables —
    the draft's k-step propose scan and the target's one fused k-verify
    — and each carries the full contract set independently: no host
    callbacks anywhere (the per-slot PRNG fold, the acceptance uniforms,
    and the residual re-draw all live in-trace), no f64/complex128 (the
    distributions are explicit f32), and the step-over-step canonical
    hash stable — propose's output draft pages feed the next propose,
    verify's output target pages feed the next verify, so the engine's
    zero-recompile contract extends to every sampled speculative tick.
    Traced with the config's own model standing in as its draft (the
    contracts pin program SHAPE; the engine accepts any same-vocab
    draft)."""
    import jax
    import jax.numpy as jnp

    from consensusml_tpu.serve import decode as D
    from consensusml_tpu.serve import pool as P

    if bundle.model is None or not D.supports_decode(bundle.model):
        return []
    findings: list[Finding] = []
    dm = D.DecodeModel.wrap(bundle.model)
    slots, max_len, bs, k = 4, min(dm.max_len, 32), 8, 2
    blocks_per_slot = max_len // bs
    cols = P.spec_table_cols(blocks_per_slot, bs, k)
    num_blocks = slots * blocks_per_slot + 1
    probe = jax.eval_shape(bundle.init_params, jax.random.key(0))
    params = probe[0] if isinstance(probe, tuple) and len(probe) == 2 else probe
    pages = jax.eval_shape(lambda: P.init_pages(dm, num_blocks, bs))
    table = jax.ShapeDtypeStruct((slots, cols), jnp.int32)
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32)
    positions = jax.ShapeDtypeStruct((slots,), jnp.int32)
    samp = _sampling_structs(slots)

    # -- draft propose scan ------------------------------------------------
    mkp = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "spec_propose", detail, msg
    )
    propose = P.make_draft_propose_fn(dm, k)
    closed = jax.make_jaxpr(propose)(
        params, pages, table, tokens, positions, *samp
    )
    findings += _callback_f64_findings(closed, mkp, "spec propose stage")
    props, q_sel, q_probs, out_dpages = jax.eval_shape(
        propose, params, pages, table, tokens, positions, *samp
    )
    findings += _hash_stable(
        mkp, propose, closed,
        (params, out_dpages, table, tokens, positions, *samp),
        "spec propose", "signature-hash",
    )

    # -- fused k-verify ----------------------------------------------------
    mkv = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "spec_verify", detail, msg
    )
    verify = P.make_verify_fn(dm, k)
    closed = jax.make_jaxpr(verify)(
        params, pages, table, tokens, props, q_sel, q_probs, positions,
        *samp,
    )
    findings += _callback_f64_findings(closed, mkv, "spec verify stage")
    _n, _y, out_pages = jax.eval_shape(
        verify, params, pages, table, tokens, props, q_sel, q_probs,
        positions, *samp,
    )
    findings += _hash_stable(
        mkv, verify, closed,
        (params, out_pages, table, tokens, props, q_sel, q_probs,
         positions, *samp),
        "spec verify", "signature-hash",
    )
    for stage, mk, out in (
        ("propose", mkp, out_dpages),
        ("verify", mkv, out_pages),
    ):
        findings += _cache_drift(
            mk, pages, out, f"the spec {stage} stage's page pytree",
            "pages-drift",
            "the pool is one fixed allocation for the engine's life — "
            "donation and the jit cache both break",
        )
    return findings


def _check_fused_attention_jaxprs(name: str, bundle) -> list[Finding]:
    """Fused paged-attention kernel-tier contracts (causal-LM configs).

    The kernel tier (``models/paged_attention.py``) replaces the
    two-step gather + dense attention in the paged decode step and the
    spec k-verify window with ONE pallas pass per layer. The contract
    set, per stage, traced on the exact jit the engine would run under
    ``attn_impl="interpret"`` (same jaxpr as the compiled TPU program
    modulo lowering):

    - ``fused-active`` — the traced program contains ``pallas_call``
      equations at all: an impl that silently composed the gather
      reference would pass every numeric parity pin while fusing
      nothing (the regression the tier exists to prevent). A NEGATIVE
      fixture rides along: the gather impl of the same stage must trace
      to ZERO ``pallas_call``s — if it doesn't, the detector can no
      longer distinguish fused from unfused and its PASSes are vacuous;
    - ``kernel-count`` — exactly ONE ``pallas_call`` per layer per
      stage. More means a layer split its pass (extra HBM round-trips);
      fewer means a layer fell back to the gather path;
    - the shared purity contracts (no host callbacks, no f64) and the
      step-over-step canonical-hash stability — the fused stages
      inherit the zero-recompile contract unchanged.
    """
    import jax
    import jax.numpy as jnp

    from consensusml_tpu.serve import decode as D
    from consensusml_tpu.serve import pool as P

    if bundle.model is None or not D.supports_decode(bundle.model):
        return []
    findings: list[Finding] = []
    dm = D.DecodeModel.wrap(bundle.model)
    layers = dm.model.config.layers
    slots, max_len, bs, k = 4, min(dm.max_len, 32), 8, 2
    blocks_per_slot = max_len // bs
    num_blocks = slots * blocks_per_slot + 1
    cols = P.spec_table_cols(blocks_per_slot, bs, k)
    probe = jax.eval_shape(bundle.init_params, jax.random.key(0))
    params = probe[0] if isinstance(probe, tuple) and len(probe) == 2 else probe
    pages = jax.eval_shape(lambda: P.init_pages(dm, num_blocks, bs))
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32)
    positions = jax.ShapeDtypeStruct((slots,), jnp.int32)
    samp = _sampling_structs(slots)

    def _kernel_findings(mk, closed, what):
        n = count_primitives(closed).get("pallas_call", 0)
        if n == 0:
            return [
                mk(
                    "fused-active", "two-step-fallback",
                    f"{what} under attn_impl='interpret' traces ZERO "
                    "pallas_calls — the kernel tier silently composed "
                    "the gather reference instead of fusing",
                )
            ]
        if n != layers:
            return [
                mk(
                    "kernel-count", "pallas_call",
                    f"{what} traces {n} pallas_call(s) but the fused "
                    f"contract is exactly one per layer ({layers}): "
                    "more = a layer's pass split (extra HBM "
                    "round-trips), fewer = a layer off the kernel path",
                )
            ]
        return []

    # -- fused decode step -------------------------------------------------
    mkd = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "fused_paged_decode", detail, msg
    )
    dec_table = jax.ShapeDtypeStruct((slots, blocks_per_slot), jnp.int32)
    decode = P.make_paged_decode_fn(dm, attn_impl="interpret")
    closed = jax.make_jaxpr(decode)(
        params, pages, dec_table, tokens, positions, *samp
    )
    findings += _kernel_findings(mkd, closed, "the fused paged decode step")
    findings += _callback_f64_findings(closed, mkd, "fused paged decode stage")
    out_tokens, out_pages = jax.eval_shape(
        decode, params, pages, dec_table, tokens, positions, *samp
    )
    findings += _hash_stable(
        mkd, decode, closed,
        (params, out_pages, dec_table, out_tokens, positions, *samp),
        "fused paged decode", "signature-hash",
    )
    # negative fixture: the gather impl of the SAME stage must fuse
    # nothing, or the fused-active detector above proves nothing
    gather_decode = P.make_paged_decode_fn(dm, attn_impl="gather")
    unfused = count_primitives(
        jax.make_jaxpr(gather_decode)(
            params, pages, dec_table, tokens, positions, *samp
        )
    ).get("pallas_call", 0)
    if unfused != 0:
        findings.append(
            mkd(
                "fused-active", "negative-fixture",
                f"the GATHER decode stage traces {unfused} "
                "pallas_call(s); the fused-active detector can no "
                "longer tell fused from unfused apart",
            )
        )

    # -- fused spec k-verify window ----------------------------------------
    mkv = lambda rule, detail, msg: Finding(
        PASS, rule, f"configs:{name}", "fused_spec_verify", detail, msg
    )
    spec_table = jax.ShapeDtypeStruct((slots, cols), jnp.int32)
    props, q_sel, q_probs, _dp = jax.eval_shape(
        P.make_draft_propose_fn(dm, k),
        params, pages, spec_table, tokens, positions, *samp,
    )
    verify = P.make_verify_fn(dm, k, attn_impl="interpret")
    closed = jax.make_jaxpr(verify)(
        params, pages, spec_table, tokens, props, q_sel, q_probs,
        positions, *samp,
    )
    findings += _kernel_findings(mkv, closed, "the fused spec verify window")
    findings += _callback_f64_findings(closed, mkv, "fused spec verify stage")
    _n, _y, v_pages = jax.eval_shape(
        verify, params, pages, spec_table, tokens, props, q_sel, q_probs,
        positions, *samp,
    )
    findings += _hash_stable(
        mkv, verify, closed,
        (params, v_pages, spec_table, tokens, props, q_sel, q_probs,
         positions, *samp),
        "fused spec verify", "signature-hash",
    )
    for stage, mk, out in (
        ("decode", mkd, out_pages),
        ("verify", mkv, v_pages),
    ):
        findings += _cache_drift(
            mk, pages, out, f"the fused {stage} stage's page pytree",
            "pages-drift",
            "the pool is one fixed allocation for the engine's life — "
            "donation and the jit cache both break",
        )
    return findings


def check_config(name: str, *, scale: str = "smoke") -> list[Finding]:
    """All jaxpr contracts for one config (incl. the serving decode
    step, BOTH paged serving stages, the speculative propose/verify
    pair, and the fused paged-attention kernel tier on causal-LM
    configs)."""
    from consensusml_tpu import configs

    bundle = configs.build(name, scale=scale)
    findings = _check_step_jaxpr(name, bundle)
    findings.extend(_check_collective_count(name, bundle))
    findings.extend(_check_decode_jaxpr(name, bundle))
    findings.extend(_check_paged_stage_jaxprs(name, bundle))
    findings.extend(_check_spec_stage_jaxprs(name, bundle))
    findings.extend(_check_fused_attention_jaxprs(name, bundle))
    return findings


def check_all_configs(*, scale: str = "smoke") -> list[Finding]:
    from consensusml_tpu import configs

    findings: list[Finding] = []
    for name in configs.names():
        try:
            findings.extend(check_config(name, scale=scale))
        except Exception as e:  # a config that cannot trace IS a finding
            findings.append(
                Finding(
                    PASS, "trace-error", f"configs:{name}", "", type(e).__name__,
                    f"tracing the {name} train step failed: {e}",
                )
            )
    # the fused one-pass wire is config-independent (engages per codec,
    # not per config); its contracts ride the same pass
    try:
        findings.extend(check_fused_wire())
    except Exception as e:
        findings.append(
            Finding(
                PASS, "trace-error", "fused-wire", "", type(e).__name__,
                f"tracing the fused gossip wire failed: {e}",
            )
        )
    return findings
