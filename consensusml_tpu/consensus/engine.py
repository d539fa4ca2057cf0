"""The gossip round: exact mixing or CHOCO compressed mixing.

Both backends implement the same update; the collective form runs
per-worker inside ``shard_map`` (payloads ride ``ppermute``), the
simulated form runs on stacked arrays via the mixing matrix. The two are
cross-validated in tests/test_consensus.py.

CHOCO-SGD update (gamma = consensus step size, Q = compressor):

    q_i     = Q(x_i - xhat_i)               # compressed innovation
    xhat_i <- xhat_i + q_i                  # everyone can track this
    s_i    <- s_i + sum_j W[i,j] dec(q_j)   # only q travels the wire
    x_i    <- x_i + gamma * (s_i - xhat_i)

With Q = identity and gamma = 1 this reduces exactly to plain gossip
``x <- W x`` (verified in tests), so one engine serves both the exact
configs (dense/ring/torus averaging) and the compressed config
(BASELINE.json configs[4]).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from consensusml_tpu.comm import collectives, simulated
from consensusml_tpu.compress.base import Compressor
from consensusml_tpu.obs import span as _span
from consensusml_tpu.consensus.bucketing import (
    BucketPlan,
    FusedWirePlan,
    build_fused_plan,
    build_plan,
)
from consensusml_tpu.consensus.faults import FaultConfig, masked_mixing_matrix
from consensusml_tpu.consensus.pushsum import (
    PushSumState,
    pushsum_init,
    pushsum_round_collective,
    pushsum_round_simulated,
)
from consensusml_tpu.topology import Topology

__all__ = ["GossipConfig", "ChocoState", "OverlapState", "ConsensusEngine"]


class ChocoState(NamedTuple):
    """Per-worker compressed-gossip state (same structure as params)."""

    xhat: Any  # my public (compression-tracked) copy of my params
    s: Any  # running sum_j W[i,j] xhat_j


class OverlapState(NamedTuple):
    """Overlap-gossip carry: the consensus correction computed from this
    round's PRE-inner-loop params, applied at the start of the next round
    (see ``GossipConfig.overlap``). Exact mode: ``(W - I) z``. Compressed
    (bucketed-path-only) mode: ``gamma * (s - xhat)`` from one CHOCO
    innovation exchange on ``z``, with the tracking state carried in
    ``choco``.

    ``pending`` is the pipelined-gossip queue
    (``GossipConfig.pipeline_depth > 1``): corrections already computed
    but not yet applied, oldest absent (it lives in ``correction``),
    newest last — ``len(pending) == pipeline_depth - 1``, so the
    correction computed at round ``r`` is applied at round ``r +
    pipeline_depth``. Depth 1 keeps ``pending = ()`` and is bit-identical
    to the original overlap carry."""

    correction: Any  # params-shaped
    choco: Any = None  # ChocoState when overlap rides the compressed path
    pending: tuple = ()  # in-flight corrections (pipeline_depth - 1 of them)


@dataclasses.dataclass(frozen=True)
class GossipConfig:
    """How one consensus round is performed.

    ``path_filter(key_path) -> bool`` restricts gossip to selected leaves —
    the LoRA pattern: only adapters ride the wire, frozen base weights are
    passed through untouched (see consensusml_tpu.models.lora).
    """

    topology: Topology
    compressor: Compressor | None = None  # None => exact mixing
    gamma: float = 1.0  # CHOCO consensus step size (ignored when exact)
    path_filter: Any = None  # Callable[[tuple], bool] | None
    # Which gossiped leaves ride the COMPRESSED (CHOCO) path; the rest
    # mix exactly every round. "auto" (default) excludes the
    # ``model_state`` subtree: sparse delta codecs are poison for
    # BatchNorm RUNNING STATISTICS (top-k ships a few large innovations;
    # the tracking error on never-selected slots compounds until the
    # statistics — and with them every normalized activation — diverge;
    # measured on the ResNet-50 convergence study: top-1 0.13 vs 0.80
    # exact). Stats are ~0.2% of a ResNet's tree, so exact mixing for
    # them costs nothing. None => compress everything (raw trees without
    # a model_state key are unaffected by "auto"); or a callable
    # ``path -> bool`` (True = compress that leaf).
    compress_filter: Any = "auto"
    faults: FaultConfig | None = None  # None => no fault model
    # Ratio consensus (see consensus.pushsum). Three values:
    #   False  — plain gossip; faults fold at the receiver, which is
    #            mean-preserving only on symmetric topologies (rejected
    #            otherwise below);
    #   True   — always push-sum;
    #   "auto" — push-sum engages exactly when the mixing matrix can go
    #            asymmetric under membership change (faults configured on
    #            a directed topology); symmetric graphs keep the cheaper
    #            receive-side fold, which coincides with push-sum there.
    #            This is the swarm subsystem's default: recovery weights
    #            stay a convex combination under ANY alive mask.
    push_sum: bool | str = False
    # Overlap gossip (combine-then-adapt): the round becomes
    #   z_{k+1} = z_k + u_k + (W - I) z_k        (u_k = inner-loop updates)
    # i.e. the mixing correction is computed from the PRE-inner params and
    # applied one round late. The correction's ppermutes depend only on
    # z_k — not on the inner loop — so XLA's latency-hiding scheduler can
    # run the communication UNDER the H local steps (the point: comm cost
    # vanishes on slow links/DCN). Mean-exact (sum_i correction_i = 0 for
    # doubly stochastic W); this is the classic CTA diffusion recurrence
    # x <- W x - lr g(x) (Sayed, "Adaptation, Learning, and Optimization
    # over Networks", 2014), so standard convergence results apply.
    overlap: bool = False
    # Consensus iterations per round. CHOCO's stable consensus step
    # size shrinks with the compression ratio (the r4 frontier study:
    # at 30M params the shipped 1/64 codec diverges at gamma 0.5 and
    # merely plateaus-at-chance at gamma 0.1 — docs/convergence.md);
    # running T iterations at a SMALL gamma multiplies the per-round
    # contraction (~(1 - c*gamma*omega)^T) while every iteration stays
    # inside the stability region. Each iteration re-compresses the
    # current innovation and ships a fresh payload, so wire bytes per
    # round multiply by T (wire_bytes_per_round accounts for it).
    gossip_steps: int = 1
    # Exact-gossip warmup for compressed configs: rounds < N mix the
    # params DENSELY while running the same innovation exchange to warm
    # xhat/s, then round N switches to pure CHOCO with tracking state
    # already caught up. Motivated by the r4 frontier trajectories
    # (docs/convergence.md): under Adam the first ~50 rounds move params
    # violently (embedding tables especially) and a sparse codec cannot
    # track it — consensus error jumps ~7x in that window and never
    # recovers, while the post-warmup innovations are small enough for
    # top-k. The standard deep-gradient-compression recipe, adapted to
    # CHOCO tracking. Wire during warmup = dense + innovation payload.
    codec_warmup_rounds: int = 0
    # Periodic dense refresh: every K-th round runs the warmup-style
    # round (dense mixing + innovation tracking) even after warmup.
    # Bounds top-k's error-feedback drift — the r4 frontier shows a
    # warm-started 1/64 codec leaking consensus error ~linearly over
    # hundreds of rounds (never-shipped coordinates accumulate); one
    # dense round every K collapses the accumulated disagreement at an
    # amortized wire cost of dense/K (K=50: +2% of dense on top of the
    # codec payload). 0 = off.
    codec_refresh_every: int = 0
    # DDP-style wire bucketing (the default transport): pack the gossiped
    # leaves into dtype-homogeneous flat buffers, each leaf padded to the
    # codec's chunk alignment and each bucket capped at ~bucket_bytes of
    # ESTIMATED WIRE footprint (dense bytes for exact mixing, codec
    # payload for compressed). A round then runs O(#buckets) fused
    # compress/ppermute/decompress stages instead of O(#leaves) — at
    # GPT-2-medium scale that is ~5 wire stages instead of 292 per-leaf
    # dispatch groups — and while bucket i is in flight on the ICI,
    # bucket i+1's codec work has no data dependence on it, so the
    # scheduler overlaps compute with communication. Exact mixing is
    # bit-identical bucketed (elementwise math on a concatenation);
    # chunked codecs decode identically too (leaf-aligned packing — see
    # consensus/bucketing.py), so this is a transport change, not a
    # codec-semantics switch. Codecs that do not decompose per-chunk
    # (``bucket_alignment() is None``: global top-k, PowerSGD, sign) and
    # push-sum rounds keep the per-leaf path automatically. None => always per-leaf (the pre-bucketing wire).
    bucket_bytes: int | None = 4 * 2**20
    # Fused one-pass wire on the bucketed path: when the codec advertises
    # fused kernels (``Compressor.fused_wire()`` — the per-chunk int8/
    # int4/fp8 quantizers), each innovation exchange runs exactly ONE
    # pack+quantize kernel per bucket on the send side (delta, absmax,
    # quantize, wire pack and the CHOCO xhat update all in one VMEM pass)
    # and ONE dequantize+accumulate kernel per bucket on the receive
    # side, instead of the two-step chain whose every stage round-trips
    # HBM over the bucket. Payload bytes/layout are bit-identical to the
    # two-step path (a transport fusion, not a codec change). "auto"
    # (default): engage exactly when the bucketed path is active and the
    # codec supports it; True: require it (config error otherwise);
    # False: always two-step.
    fused_wire: bool | str = "auto"
    # Pipelined overlap gossip (requires ``overlap=True``): keep D
    # mixing corrections in flight — the correction computed from round
    # r's pre-inner params is applied at round r+D, so the collective
    # issued at round r has D full rounds of local compute to hide
    # under (cross-round slack for slow links/DCN, where one round's
    # inner loop is shorter than the wire latency). Each round's
    # correction is computed from the ANTICIPATED params z + sum(pending)
    # — the params as they will stand when it lands — which keeps the
    # shadow sequence on the exact gossip recurrence x <- W x (a naive
    # delayed correction x_{k+1} = x_k + (W-I) x_{k-D+1} DIVERGES on a
    # ring for D >= 2: the delay pushes the recurrence's eigenvalues
    # outside the unit circle). Mean-exact at any depth: every queued
    # correction sums to zero across workers for doubly stochastic W.
    # Depth 1 is plain overlap gossip, bit-identical to before.
    pipeline_depth: int = 1

    @property
    def push_sum_enabled(self) -> bool:
        """The resolved push-sum switch: ``"auto"`` engages ratio
        consensus exactly when faults are configured on an asymmetric
        (directed) topology — the one regime where receive-side masked
        mixing would bias the network mean."""
        if self.push_sum == "auto":
            return self.faults is not None and not self.topology.symmetric
        return bool(self.push_sum)

    def __post_init__(self):
        if self.push_sum not in (True, False, "auto"):
            raise ValueError(
                f"push_sum must be True, False or 'auto', got {self.push_sum!r}"
            )
        if self.fused_wire not in (True, False, "auto"):
            raise ValueError(
                f"fused_wire must be True, False or 'auto', got "
                f"{self.fused_wire!r}"
            )
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {self.pipeline_depth}"
            )
        if self.pipeline_depth > 1 and not self.overlap:
            raise NotImplementedError(
                "pipeline_depth > 1 is overlap-mode pipelining (corrections "
                "queued across rounds); it needs overlap=True — without "
                "overlap the round applies its own mixing immediately and "
                "there is nothing to pipeline"
            )
        if self.fused_wire is True:
            from consensusml_tpu.compress.kernels import fused_bucket_codec

            if self.compressor is None:
                raise NotImplementedError(
                    "fused_wire=True without a compressor has nothing to "
                    "fuse: exact bucketed mixing is already one collective "
                    "per bucket"
                )
            if self.bucket_bytes is None or self.push_sum_enabled:
                raise NotImplementedError(
                    "fused_wire=True requires the bucketed transport "
                    "(bucket_bytes set, no push_sum) — "
                    "the fused kernels are per-bucket by construction"
                )
            if fused_bucket_codec(self.compressor) is None:
                raise NotImplementedError(
                    f"fused_wire=True but {type(self.compressor).__name__} "
                    "advertises no fused wire kernels "
                    "(Compressor.fused_wire()): only the per-chunk int8/"
                    "int4/fp8 quantizers fuse; composed/sparse codecs keep "
                    "the two-step bucketed path (fused_wire='auto')"
                )
        if self.bucket_bytes is not None and self.bucket_bytes <= 0:
            raise ValueError(
                f"bucket_bytes must be positive (or None for the per-leaf "
                f"path), got {self.bucket_bytes}"
            )
        if self.gossip_steps < 1:
            raise ValueError(f"gossip_steps must be >= 1, got {self.gossip_steps}")
        if self.codec_warmup_rounds < 0:
            raise ValueError(
                f"codec_warmup_rounds must be >= 0, got {self.codec_warmup_rounds}"
            )
        if self.codec_warmup_rounds > 0 and self.compressor is None:
            raise NotImplementedError(
                "codec_warmup_rounds without a compressor is meaningless: "
                "exact mixing has no codec to warm up"
            )
        if self.codec_refresh_every < 0:
            raise ValueError(
                f"codec_refresh_every must be >= 0, got {self.codec_refresh_every}"
            )
        if self.codec_refresh_every > 0 and self.compressor is None:
            raise NotImplementedError(
                "codec_refresh_every without a compressor is meaningless: "
                "exact mixing is already dense every round"
            )
        if self.gossip_steps > 1 and self.push_sum_enabled:
            raise NotImplementedError(
                "gossip_steps > 1 with push-sum is not supported: the mass "
                "ratio's bias correction is defined per round, not per "
                "inner consensus iteration"
            )
        if self.gossip_steps > 1 and self.overlap:
            raise NotImplementedError(
                "gossip_steps > 1 with overlap gossip is not supported: "
                "the delayed correction is computed once per round"
            )
        if self.overlap and self.compressor is not None:
            # Lifted ONLY on the bucketed path: there the correction is one
            # CHOCO innovation exchange over the bucket buffers — the
            # tracking state rides per-bucket, and applying gamma*(s - xhat)
            # one round late is still mean-exact (sum_i s_i = sum_i xhat_i
            # for doubly stochastic W). The per-leaf path keeps the
            # original same-round-tracking restriction.
            if (
                self.bucket_bytes is None
                or self.compressor.bucket_alignment() is None
            ):
                raise NotImplementedError(
                    "overlap + compression is only supported on the bucketed "
                    "gossip path (bucket_bytes set, chunk-decomposable codec "
                    "with bucket_alignment() != None): "
                    "per-leaf CHOCO's innovation tracking is defined against "
                    "the same-round mixing update, not the one-round-delayed "
                    "correction"
                )
            if self.compressor.stochastic:
                raise NotImplementedError(
                    "overlap + a STOCHASTIC compressor is not supported: the "
                    "correction is computed alongside the inner loop, where "
                    "no per-round gossip rng is threaded"
                )
            if self.path_filter is not None:
                raise NotImplementedError(
                    "overlap + compression + path_filter is not supported "
                    "yet: the delayed compressed correction assumes the "
                    "whole tree gossips"
                )
            if self.codec_warmup_rounds > 0 or self.codec_refresh_every > 0:
                raise NotImplementedError(
                    "overlap + compression does not compose with "
                    "codec_warmup_rounds/codec_refresh_every yet: the dense "
                    "warm round and the delayed correction disagree about "
                    "which W application the tracking state saw"
                )
        if self.overlap and self.push_sum_enabled:
            raise NotImplementedError(
                "overlap + push-sum is not supported: the mass ratio must "
                "be updated with the same W application as the numerator, "
                "which the delayed correction splits across rounds"
            )
        if self.overlap and self.faults is not None:
            raise NotImplementedError(
                "overlap + fault injection is not supported yet: a dropped "
                "round would apply a correction computed against a W the "
                "peer never participated in"
            )
        if self.compressor is not None and self.faults is not None:
            raise NotImplementedError(
                "fault-tolerant COMPRESSED gossip is not supported yet: "
                "CHOCO's xhat tracking assumes every peer applies every "
                "innovation, which a dropped round violates; use exact "
                "gossip with faults, or compression without faults"
            )
        if self.compressor is not None and self.push_sum_enabled:
            raise NotImplementedError(
                "compressed push-sum is not supported: CHOCO's innovation "
                "tracking assumes the row-stochastic mixing update, not "
                "the biased-mass/ratio update"
            )
        if self.faults is not None and not self.topology.symmetric and not self.push_sum_enabled:
            raise NotImplementedError(
                "fault masking requires a SYMMETRIC topology: folding a "
                "dead peer's weight onto self keeps W doubly stochastic "
                "(mean-preserving) only when W = W^T; a directed graph "
                f"({self.topology.name}) would bias the network mean each "
                "faulty round. Use ring/torus/dense/exp with faults, a "
                "directed topology without faults, or push_sum=True "
                "(ratio consensus is mean-exact on any graph)"
            )


def _check_bucket_state(packed: list, xhat: Any) -> None:
    """Loud mismatch between the round's packed buffers and the CHOCO
    state layout: the usual cause is stacked params initialized without
    ``world_size`` (the bucketed state convention), which would
    otherwise surface as an opaque broadcast error."""
    hat_leaves = jax.tree.leaves(xhat)
    shapes = lambda xs: [tuple(b.shape) for b in xs]
    if len(hat_leaves) != len(packed) or shapes(hat_leaves) != shapes(packed):
        raise ValueError(
            "bucketed CHOCO state does not match this round's bucket "
            f"layout: params pack to {shapes(packed)} but the state holds "
            f"{shapes(hat_leaves)}. For stacked (simulated/host-side) "
            "params, init_state needs world_size=...; also rebuild state "
            "after changing bucket_bytes, the codec, or the tree."
        )


@functools.lru_cache(maxsize=64)
def _codec_wire_rate(comp: Compressor, align: int) -> int:
    """Wire bytes of one ``align``-sized chunk under ``comp`` — the linear
    rate the bucket planner uses to estimate a leaf's payload (compressors
    are frozen dataclasses, so the eval_shape probe runs once per codec)."""
    return comp.wire_bytes((align,), jnp.float32)


@dataclasses.dataclass(frozen=True)
class ConsensusEngine:
    config: GossipConfig

    @property
    def topology(self) -> Topology:
        return self.config.topology

    @property
    def compressed(self) -> bool:
        return self.config.compressor is not None

    # ---- bucketed wire ---------------------------------------------------
    @property
    def bucketed(self) -> bool:
        """Whether gossip rounds ride the bucketed wire (see
        ``GossipConfig.bucket_bytes``). Push-sum rounds and codecs that do
        not decompose per-chunk fall back to the per-leaf path."""
        cfg = self.config
        if cfg.bucket_bytes is None or cfg.push_sum_enabled:
            return False
        comp = cfg.compressor
        return comp is None or comp.bucket_alignment() is not None

    @property
    def fused_wire_active(self) -> bool:
        """Whether compressed rounds run the FUSED one-pass wire (see
        ``GossipConfig.fused_wire``): bucketed transport + a codec with
        fused kernels + the config not opting out. False always for
        exact mixing (nothing to quantize) and stochastic codecs (no
        per-round rng threads through the fused kernels)."""
        cfg = self.config
        if cfg.compressor is None or cfg.fused_wire is False:
            return False
        if not self.bucketed or cfg.compressor.stochastic:
            return False
        from consensusml_tpu.compress.kernels import fused_bucket_codec

        return fused_bucket_codec(cfg.compressor) is not None

    def _fused_plan(self, plan: BucketPlan) -> FusedWirePlan | None:
        """The fused wire for this round's bucket layout (None => the
        two-step bucketed path stays active)."""
        if not self.fused_wire_active:
            return None
        return build_fused_plan(plan, self.config.compressor)

    def _dense_plan(self, leaves: list, stacked: bool = False) -> BucketPlan:
        """Bucket layout for exactly-mixed leaves: original dtypes, no
        alignment padding, capped at the dense (== wire) bytes."""
        return build_plan(
            [((x.shape[1:] if stacked else x.shape), x.dtype) for x in leaves],
            bucket_bytes=self.config.bucket_bytes,
        )

    def _codec_plan(self, leaves: list, stacked: bool = False) -> BucketPlan:
        """Bucket layout for CHOCO leaves: everything is f32 by the time
        it is packed, leaves are padded to the codec's chunk alignment,
        and the cap is on the ESTIMATED CODEC PAYLOAD — the bytes actually
        in flight per pipeline stage."""
        comp = self.config.compressor
        align = comp.bucket_alignment()
        rate = _codec_wire_rate(comp, align)
        return build_plan(
            [((x.shape[1:] if stacked else x.shape), jnp.float32) for x in leaves],
            bucket_bytes=self.config.bucket_bytes,
            align=align,
            wire_bytes=lambda n, dtype: (n // align) * rate,
        )

    def bucket_plan(self, params: Any, stacked: bool = False) -> BucketPlan | None:
        """The static bucket layout one gossip round of ``params`` uses
        (None => the per-leaf path is active). Accepts shape structs
        (``jax.eval_shape`` output) — nothing is materialized. Pass
        ``stacked=True`` when leaves carry a leading worker axis."""
        if not self.bucketed:
            return None
        if self.compressed:
            part, _, _, _ = self._partition(params)
            return self._codec_plan(jax.tree.leaves(part), stacked=stacked)
        sel = params
        if self.config.path_filter is not None:
            sel, _ = self._select(params)
        return self._dense_plan(jax.tree.leaves(sel), stacked=stacked)

    def _mix_exact_leaves_collective(
        self, leaves: list, topo: Topology, n_iter: int,
        alive: jax.Array | None = None, alive_nbrs: list | None = None,
    ) -> list:
        """Exact-mix a leaf list ``n_iter`` times — bucketed when enabled
        (bit-identical to per-leaf: the mixing math is elementwise, so it
        commutes with concatenation)."""
        if self.bucketed and leaves:
            plan = self._dense_plan(leaves)
            with _span("bucket.pack", buckets=plan.num_buckets):
                bufs = plan.pack(leaves)
            with _span("bucket.mix", iters=n_iter):
                for _ in range(n_iter):
                    bufs = collectives.mix_buckets(
                        bufs, topo, alive, alive_nbrs
                    )
            with _span("bucket.unpack"):
                return plan.unpack(bufs)
        out = list(leaves)
        for _ in range(n_iter):
            if alive is not None:
                out = [
                    collectives.mix_masked(x, topo, alive, alive_nbrs)
                    for x in out
                ]
            else:
                out = [collectives.mix(x, topo) for x in out]
        return out

    def _mix_exact_tree_collective(
        self, tree: Any, topo: Topology, n_iter: int = 1,
        alive: jax.Array | None = None, alive_nbrs: list | None = None,
    ) -> Any:
        leaves, treedef = jax.tree.flatten(tree)
        return jax.tree.unflatten(
            treedef,
            self._mix_exact_leaves_collective(
                leaves, topo, n_iter, alive, alive_nbrs
            ),
        )

    def _mix_exact_leaves_simulated(
        self, leaves: list, w: jax.Array, n_iter: int
    ) -> list:
        if self.bucketed and leaves:
            plan = self._dense_plan(leaves, stacked=True)
            with _span("bucket.pack", buckets=plan.num_buckets):
                bufs = plan.pack(leaves, stacked=True)
            with _span("bucket.mix", iters=n_iter):
                for _ in range(n_iter):
                    bufs = [simulated.mix_stacked(b, w) for b in bufs]
            with _span("bucket.unpack"):
                return plan.unpack(bufs, stacked=True)
        out = list(leaves)
        for _ in range(n_iter):
            out = [simulated.mix_stacked(x, w) for x in out]
        return out

    def _mix_exact_tree_simulated(
        self, tree: Any, w: jax.Array, n_iter: int = 1
    ) -> Any:
        leaves, treedef = jax.tree.flatten(tree)
        return jax.tree.unflatten(
            treedef, self._mix_exact_leaves_simulated(leaves, w, n_iter)
        )

    # ---- compress-path filtering ----------------------------------------
    def _compress_filter(self):
        cf = self.config.compress_filter
        if cf == "auto":
            return lambda p: not (
                p and getattr(p[0], "key", None) == "model_state"
            )
        return cf

    def _partition(self, tree: Any):
        """One flatten, BOTH filters on the ORIGINAL tree paths:
        ``(compressed, exact_mixed, passthrough, rebuild)``.

        ``path_filter`` decides what gossips at all (non-gossiped leaves
        pass through untouched); ``compress_filter`` decides which
        gossiped leaves ride CHOCO vs plain mixing. Both must see the
        original paths — filtering in two stages would hand the second
        filter a flat list whose SequenceKey paths match nothing, which
        silently disabled the model_state exclusion. Returns
        ``(tree, None, None, None)`` when every leaf is compressed, so
        the common no-filter configs keep their exact state/payload tree
        structure (and existing checkpoints their layout).
        """
        pf = self.config.path_filter
        cf = self._compress_filter()
        if pf is None and cf is None:
            return tree, None, None, None
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        tags = []
        for p, _ in flat:
            if pf is not None and not pf(p):
                tags.append("r")
            elif cf is not None and not cf(p):
                tags.append("e")
            else:
                tags.append("c")
        if all(t == "c" for t in tags):
            return tree, None, None, None
        by = lambda t: [x for tg, (_, x) in zip(tags, flat) if tg == t]

        def rebuild(c_new: list, e_new: list, r_new: list) -> Any:
            its = {"c": iter(c_new), "e": iter(e_new), "r": iter(r_new)}
            return jax.tree.unflatten(
                treedef, [next(its[t]) for t in tags]
            )

        return by("c"), by("e"), by("r"), rebuild

    # ---- path filtering --------------------------------------------------
    def _select(self, tree: Any):
        """Split ``tree`` into the gossiped-leaf list + a rebuild closure.

        With a ``path_filter``, CHOCO runs on the selected leaves ONLY (a
        flat list is itself a pytree), so e.g. a LoRA run keeps xhat/s
        state for the adapters rather than for all 7B frozen weights.
        """
        flt = self.config.path_filter
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        sel = [x for p, x in flat if flt(p)]

        def rebuild(new_sel: list) -> Any:
            it = iter(new_sel)
            leaves = [next(it) if flt(p) else x for p, x in flat]
            return jax.tree.unflatten(treedef, leaves)

        return sel, rebuild

    # ---- state ----------------------------------------------------------
    def init_state(
        self, params: Any, world_size: int | None = None
    ) -> ChocoState | PushSumState | OverlapState | None:
        """Gossip state: zero CHOCO state shaped like ``params``, unit
        push-sum mass, zero overlap correction, or None for exact mixing.

        Works for both backends: pass per-worker params (collective) or
        stacked params with ``world_size`` (simulated / host-side stacked
        construction — push-sum mass needs the explicit worker count since
        it is a scalar, not params-shaped, and the bucketed CHOCO buffers
        need it to split the worker axis out of the flat domain).
        With a ``path_filter`` CHOCO state only covers the filtered
        (gossiped) leaves.
        """
        if self.config.push_sum_enabled:
            return pushsum_init(world_size)
        if self.config.overlap:
            sel = params
            if self.config.path_filter is not None:
                sel, _ = self._select(params)
            correction = jax.tree.map(jnp.zeros_like, sel)
            # pipeline_depth - 1 further zero corrections in flight: the
            # first depth-1 rounds apply nothing while the queue fills
            pending = tuple(
                jax.tree.map(jnp.zeros_like, sel)
                for _ in range(self.config.pipeline_depth - 1)
            )
            if not self.compressed:
                return OverlapState(correction=correction, pending=pending)
            # compressed overlap (bucketed path): the correction also
            # carries CHOCO tracking, per-bucket, over the
            # compressed-partition leaves
            ctree, _, _, _ = self._partition(params)
            zeros = self._bucket_zeros(ctree, world_size)
            return OverlapState(
                correction=correction,
                choco=ChocoState(xhat=zeros, s=[jnp.copy(z) for z in zeros]),
                pending=pending,
            )
        if not self.compressed:
            return None
        # CHOCO state covers only the compressed leaves: exact-mixed
        # leaves (BN stats under "auto") and non-gossiped leaves
        # (path_filter) carry no tracking
        params, _, _, _ = self._partition(params)
        if self.bucketed:
            # CHOCO state lives PER-BUCKET: one flat buffer per bucket
            # (leading worker axis when stacked), matching the bucketed
            # round's compress domain — so a round packs only the params
            # and the tracking buffers never pay a per-round repack
            # (measured 2.8x round speedup vs repacking tree state)
            zeros = self._bucket_zeros(params, world_size)
            return ChocoState(xhat=zeros, s=[jnp.copy(z) for z in zeros])
        zeros = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), params)
        return ChocoState(xhat=zeros, s=jax.tree.map(jnp.copy, zeros))

    def _bucket_zeros(
        self, ctree: Any, world_size: int | None
    ) -> list[jax.Array]:
        """Zero per-bucket f32 buffers for the compressed-partition tree
        (``(W, total)`` rows when ``world_size`` is given)."""
        plan = self._codec_plan(
            jax.tree.leaves(ctree), stacked=world_size is not None
        )
        shape = (
            (lambda b: (b.total,))
            if world_size is None
            else (lambda b: (world_size, b.total))
        )
        return [jnp.zeros(shape(b), jnp.float32) for b in plan.buckets]

    # ---- collective backend (call inside shard_map) ---------------------
    def round_collective(
        self,
        params: Any,
        state: ChocoState | None,
        alive: jax.Array | None = None,
        rng: jax.Array | None = None,
        step: jax.Array | None = None,
    ):
        """One gossip round, per-worker view. Returns (params, state).

        ``alive`` (scalar 0/1, only with ``config.faults``): this worker's
        participation flag — see :mod:`consensusml_tpu.consensus.faults`.
        ``rng``: this worker's key for stochastic codecs (random-k, QSGD).
        ``step``: round counter (required for time-varying topologies —
        selects the phase via ``lax.switch``; every worker holds the same
        count, so all branches agree across the mesh).
        """
        topo = self.topology
        if step is None and (
            self.config.codec_warmup_rounds > 0
            or self.config.codec_refresh_every > 0
        ):
            raise ValueError(
                "codec_warmup_rounds/codec_refresh_every need the round "
                "counter (step=...)"
            )
        if not topo.is_time_varying:
            with _span("gossip.round", backend="collective"):
                return self._phase_collective(
                    topo, params, state, alive, rng, step
                )
        if step is None:
            raise ValueError(
                f"{type(topo).__name__} is time-varying: round_collective "
                "needs the round counter (step=...)"
            )
        branches = [
            functools.partial(self._phase_collective, phase)
            for phase in topo.phases
        ]
        with _span("gossip.round", backend="collective", phases=topo.period):
            return jax.lax.switch(
                step % topo.period, branches, params, state, alive, rng, step
            )

    def _phase_collective(
        self,
        topo: Topology,
        params: Any,
        state: ChocoState | None,
        alive: jax.Array | None,
        rng: jax.Array | None,
        step: jax.Array | None = None,
    ):
        if self.config.push_sum_enabled:
            if self.config.path_filter is not None:
                sel, rebuild = self._select(params)
                mixed, new_state = pushsum_round_collective(sel, state, topo, alive)
                return rebuild(mixed), new_state
            return pushsum_round_collective(params, state, topo, alive)
        n_iter = self.config.gossip_steps
        if not self.compressed:
            flt = self.config.path_filter
            # exchange the alive flags once, not once per leaf/bucket
            alive_nbrs = (
                None
                if alive is None or topo.uses_psum
                else [
                    collectives.ppermute_shift(alive, topo, s)
                    for s in topo.shifts
                ]
            )
            if self.bucketed:
                # bucketed wire: one fused mix per dtype-homogeneous
                # bucket instead of one per leaf (same math elementwise)
                if flt is not None:
                    sel, rebuild = self._select(params)
                    return rebuild(
                        self._mix_exact_leaves_collective(
                            sel, topo, n_iter, alive, alive_nbrs
                        )
                    ), None
                return self._mix_exact_tree_collective(
                    params, topo, n_iter, alive, alive_nbrs
                ), None
            if alive is not None:
                mix_one = lambda x: collectives.mix_masked(
                    x, topo, alive, alive_nbrs
                )
                mix_all = lambda t: jax.tree.map(mix_one, t)
            else:
                mix_one = lambda x: collectives.mix(x, topo)
                mix_all = lambda t: collectives.mix_tree(t, topo)
            if flt is not None:
                for _ in range(n_iter):
                    params = jax.tree_util.tree_map_with_path(
                        lambda p, x: mix_one(x) if flt(p) else x, params
                    )
                return params, None
            for _ in range(n_iter):
                params = mix_all(params)
            return params, None

        comp = self.config.compressor
        # one partition over the original paths: CHOCO leaves / exact-mix
        # leaves (BN stats) / passthrough (path_filter-excluded)
        params, exact_leaves, rest_leaves, rebuild_split = self._partition(
            params
        )
        if exact_leaves is not None:
            # stay in step with the CHOCO leaves (bucketed when enabled)
            mixed_exact = self._mix_exact_leaves_collective(
                exact_leaves, topo, n_iter
            )
        f32 = lambda t: jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), t)
        x = f32(params)
        plan = treedef = fused = None
        xhat, s = state.xhat, state.s
        if self.bucketed:
            # bucketed wire: the whole CHOCO round — compress, ppermute,
            # decompress-accumulate, gamma update — runs on O(#buckets)
            # flat buffers. Only the params pay the pack/unpack; xhat/s
            # already LIVE per-bucket (init_state), so the tracking
            # buffers cross rounds without a repack.
            leaves, treedef = jax.tree.flatten(x)
            plan = self._codec_plan(leaves)
            fused = self._fused_plan(plan)
            with _span("bucket.pack", buckets=plan.num_buckets):
                x = plan.pack(leaves)
            _check_bucket_state(x, xhat)
        def _track(x, xhat, s, it_rng):
            """One innovation exchange: update xhat and s."""
            if fused is not None:
                return self._innovation_exchange_fused_collective(
                    topo, x, xhat, s, fused
                )
            return self._innovation_exchange_collective(
                topo, x, xhat, s, it_rng
            )

        def _choco(x, xhat, s):
            # T consensus iterations, each re-compressing the CURRENT
            # innovation (CHOCO-Gossip run T times — see gossip_steps)
            for it in range(n_iter):
                it_rng = (
                    rng
                    if n_iter == 1
                    else (None if rng is None else jax.random.fold_in(rng, it))
                )
                xhat, s = _track(x, xhat, s, it_rng)
                x = jax.tree.map(
                    lambda xi, si, hi: xi + self.config.gamma * (si - hi),
                    x, s, xhat,
                )
            return x, xhat, s

        def _warm(x, xhat, s):
            # warmup round: the params ride EXACT mixing (n_iter times,
            # matching what the exact engine with the same gossip_steps
            # would do — and the exact-partition leaves above); the same
            # innovation exchange still runs so xhat/s track x and the
            # switch to compressed rounds starts caught up
            xhat, s = _track(x, xhat, s, rng)
            for _ in range(n_iter):
                x = collectives.mix_tree(x, topo)
            return x, xhat, s

        warm = self.config.codec_warmup_rounds
        refresh = self.config.codec_refresh_every
        if warm > 0 or refresh > 0:
            pred = None
            if warm > 0:
                pred = step < warm
            if refresh > 0:
                hit = step % refresh == 0
                pred = hit if pred is None else jnp.logical_or(pred, hit)
            x, xhat, s = jax.lax.cond(pred, _warm, _choco, x, xhat, s)
        else:
            x, xhat, s = _choco(x, xhat, s)
        x_new = x
        if plan is not None:
            # params back to leaves (padding slots drop); xhat/s stay
            # per-bucket — that IS their steady-state layout
            with _span("bucket.unpack"):
                x_new = jax.tree.unflatten(treedef, plan.unpack(x_new))
        x_new = jax.tree.map(
            lambda new, old: new.astype(old.dtype), x_new, params
        )
        if rebuild_split is not None:
            x_new = rebuild_split(
                jax.tree.leaves(x_new), mixed_exact, rest_leaves
            )
        return x_new, ChocoState(xhat=xhat, s=s)

    def _innovation_exchange_collective(
        self, topo: Topology, x: Any, xhat: Any, s: Any, rng: jax.Array | None
    ):
        """One CHOCO innovation exchange (per-worker view): compress the
        innovation, ship it to every neighbor, accumulate. ``x``/``xhat``/
        ``s`` are matching pytrees — parameter leaves on the per-leaf
        path, flat bucket buffers on the bucketed path."""
        comp = self.config.compressor
        with _span("choco.innovation"):
            delta = jax.tree.map(jnp.subtract, x, xhat)
            with _span("choco.compress"):
                q = comp.compress_tree(delta, rng)
                dec_q = comp.decompress_tree(q, like=delta)
            xhat = jax.tree.map(jnp.add, xhat, dec_q)
            if topo.uses_psum:
                recv = jax.tree.map(
                    lambda d: jax.lax.pmean(d, topo.axis_names), dec_q
                )
            else:
                recv = jax.tree.map(lambda d: topo.self_weight * d, dec_q)
                # issue every shift's sends up front: bucket i+1's compress
                # has no data dependence on bucket i's in-flight ppermute, so
                # the latency-hiding scheduler pipelines codec work under the
                # wire (the DDP-style compute/comm overlap bucketing buys)
                with _span("choco.exchange", shifts=len(topo.shifts)):
                    inflight = [
                        collectives.ppermute_shift_tree(q, topo, shift)
                        for shift in topo.shifts
                    ]
                    for shift, q_nbr in zip(topo.shifts, inflight):
                        # fused decompress-accumulate: sparse codecs
                        # scatter-add straight into recv — no dense
                        # per-neighbor temporary
                        recv = comp.decompress_accumulate_tree(
                            q_nbr, recv, shift.weight
                        )
            return xhat, jax.tree.map(jnp.add, s, recv)

    def _innovation_exchange_simulated(
        self, x: Any, xhat: Any, s: Any, w: jax.Array, rng: jax.Array | None
    ):
        """Stacked-backend :meth:`_innovation_exchange_collective`: vmap
        the SAME compress/decompress path over the worker axis so the rng
        fold-in convention has one source of truth, then mix the decoded
        innovations with the mixing matrix."""
        comp = self.config.compressor
        delta = jax.tree.map(jnp.subtract, x, xhat)
        if comp.stochastic:
            dec_q = jax.vmap(
                lambda t, k: comp.decompress_tree(
                    comp.compress_tree(t, k), like=t
                )
            )(delta, rng)
        else:
            dec_q = jax.vmap(
                lambda t: comp.decompress_tree(comp.compress_tree(t), like=t)
            )(delta)
        xhat = jax.tree.map(jnp.add, xhat, dec_q)
        recv = simulated.mix_tree_stacked(dec_q, w)
        return xhat, jax.tree.map(jnp.add, s, recv)

    def _innovation_exchange_fused_collective(
        self, topo: Topology, x: list, xhat: list, s: list, fused: FusedWirePlan
    ):
        """The FUSED one-pass wire's innovation exchange (per-worker
        view): one pack+quantize kernel per bucket produces the payload
        AND the xhat update, the payloads ride ``ppermute`` exactly as on
        the two-step path (same leaves, same bytes, same traced
        collective count), and one dequantize+accumulate kernel per
        bucket folds self + every neighbor into ``s``. Bit-identical
        semantics to :meth:`_innovation_exchange_collective` under the
        same codec impl — only the number of HBM round-trips changes."""
        with _span("choco.innovation", fused=True):
            q, xhat = fused.encode(x, xhat)
            if topo.uses_psum:
                # dense: pmean over the decoded innovation, as unfused
                dec = fused.decode(q)
                recv = [jax.lax.pmean(d, topo.axis_names) for d in dec]
                return xhat, [si + r for si, r in zip(s, recv)]
            with _span("choco.exchange", shifts=len(topo.shifts)):
                # all shifts' sends up front: bucket i+1's encode has no
                # data dependence on bucket i's in-flight ppermute
                inflight = [
                    collectives.ppermute_shift_tree(q, topo, shift)
                    for shift in topo.shifts
                ]
            weights = (topo.self_weight,) + tuple(
                sh.weight for sh in topo.shifts
            )
            sources = [
                [qb] + [nbr[i] for nbr in inflight] for i, qb in enumerate(q)
            ]
            return xhat, fused.decode_accumulate(s, sources, weights)

    def _innovation_exchange_fused_simulated(
        self, x: list, xhat: list, s: list, w: jax.Array, fused: FusedWirePlan
    ):
        """Stacked-backend fused exchange: the SAME encode kernels run
        over the stacked ``(W, total)`` buffers (the worker axis just
        adds chunk rows), then the decoded innovations mix through the
        matrix — the cross-validation oracle for the collective path."""
        q, xhat = fused.encode(x, xhat)
        dec = fused.decode(q)
        recv = [simulated.mix_stacked(d, w) for d in dec]
        return xhat, [si + r for si, r in zip(s, recv)]

    # ---- overlap gossip (combine-then-adapt) ----------------------------
    def apply_correction(self, tree: Any, state: OverlapState) -> Any:
        """Start-of-round combine: add last round's ``(W - I) z`` to the
        gossiped leaves (others pass through untouched)."""
        if self.config.path_filter is not None:
            sel, rebuild = self._select(tree)
            return rebuild(jax.tree.map(jnp.add, sel, state.correction))
        return jax.tree.map(jnp.add, tree, state.correction)

    def _correction(self, mix_fn, tree: Any, pending: tuple) -> Any:
        """The next correction ``(W - I) z_hat`` from this round's
        pre-inner params. ``z_hat`` anticipates the still-queued
        corrections (``pending``) so that under ``pipeline_depth > 1``
        the correction is computed against the params AS THEY WILL STAND
        when it finally lands — the shadow sequence then follows the
        plain gossip recurrence at any depth (see
        ``GossipConfig.pipeline_depth``; a naive delayed ``(W - I) z``
        diverges for depth >= 2)."""
        sel = tree
        if self.config.path_filter is not None:
            sel, _ = self._select(tree)
        for p in pending:
            sel = jax.tree.map(jnp.add, sel, p)
        mixed = mix_fn(sel)
        return jax.tree.map(
            lambda m, t: (m - t).astype(t.dtype), mixed, sel
        )

    def _push_correction(
        self, state: OverlapState | None, corr: Any, choco: Any
    ) -> OverlapState:
        """Rotate the pipeline queue: this round's (just-applied) head
        drops, ``corr`` joins at the back. Depth 1 degenerates to the
        original single-correction carry."""
        pending = () if state is None else tuple(state.pending)
        queue = pending + (corr,)
        return OverlapState(
            correction=queue[0], choco=choco, pending=queue[1:]
        )

    def _correction_compressed(
        self, topo: Topology, tree: Any, state: OverlapState, stacked_w=None
    ) -> OverlapState:
        """Compressed overlap correction (bucketed path only): one CHOCO
        innovation exchange on the pre-inner params ``z``, yielding
        ``gamma * (s - xhat)`` to apply at the next round's start. The
        exchange depends only on ``z`` — not on the inner loop — so its
        ppermutes schedule UNDER the local steps, exactly like the exact
        overlap correction, and Metropolis-doubly-stochastic W keeps
        ``sum_i (s_i - xhat_i) = 0`` so the delayed application is
        mean-exact. ``stacked_w``: mixing matrix => simulated backend.
        Returns ``(correction, choco)``; the caller rotates the pipeline
        queue (:meth:`_push_correction`).
        """
        for p in state.pending:
            # pipeline_depth > 1: anticipate the still-queued corrections
            # so the innovation tracks the params as they will stand when
            # this correction lands (see _correction)
            tree = jax.tree.map(jnp.add, tree, p)
        f32 = lambda t: jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), t)
        ctree, exact_leaves, rest_leaves, rebuild_split = self._partition(
            tree
        )
        stacked = stacked_w is not None
        leaves, treedef = jax.tree.flatten(f32(ctree))
        plan = self._codec_plan(leaves, stacked=stacked)
        fused = self._fused_plan(plan)
        x = plan.pack(leaves, stacked=stacked)
        xhat, s = state.choco.xhat, state.choco.s  # already per-bucket
        _check_bucket_state(x, xhat)
        if stacked:
            if fused is not None:
                xhat, s = self._innovation_exchange_fused_simulated(
                    x, xhat, s, stacked_w, fused
                )
            else:
                xhat, s = self._innovation_exchange_simulated(
                    x, xhat, s, stacked_w, None
                )
        elif fused is not None:
            xhat, s = self._innovation_exchange_fused_collective(
                topo, x, xhat, s, fused
            )
        else:
            xhat, s = self._innovation_exchange_collective(
                topo, x, xhat, s, None
            )
        corr = jax.tree.map(
            lambda si, hi: self.config.gamma * (si - hi), s, xhat
        )
        unflat = lambda bufs: jax.tree.unflatten(
            treedef, plan.unpack(bufs, stacked=stacked)
        )
        corr_c = jax.tree.map(
            lambda c, t: c.astype(t.dtype), unflat(corr), ctree
        )
        choco = ChocoState(xhat=xhat, s=s)  # stays per-bucket
        if rebuild_split is None:
            return corr_c, choco
        # exact-partition leaves (BN stats under the "auto" filter) get
        # the plain (W - I) z correction; path_filter is rejected at
        # config time, so the passthrough list is always empty here
        if stacked:
            mixed = self._mix_exact_leaves_simulated(exact_leaves, stacked_w, 1)
        else:
            mixed = self._mix_exact_leaves_collective(exact_leaves, topo, 1)
        corr_e = [
            (m - e).astype(e.dtype) for m, e in zip(mixed, exact_leaves)
        ]
        zeros_r = [jnp.zeros_like(r) for r in rest_leaves]
        return rebuild_split(jax.tree.leaves(corr_c), corr_e, zeros_r), choco

    def correction_collective(
        self, tree: Any, state: OverlapState | None = None,
        step: jax.Array | None = None,
    ) -> OverlapState:
        """Next round's correction from this round's pre-inner params.

        Issued alongside (not after) the inner loop: the ppermutes here
        depend only on ``tree``, so the scheduler overlaps them with the
        local steps. With a (bucketed) compressor, ``state`` must be the
        current ``OverlapState`` — its CHOCO tracking advances each round.
        """
        topo = self.topology
        if self.compressed:
            if state is None or state.choco is None:
                raise ValueError(
                    "compressed overlap needs the OverlapState carrying "
                    "CHOCO tracking (from init_state)"
                )
            if not topo.is_time_varying:
                corr, choco = self._correction_compressed(topo, tree, state)
            else:
                if step is None:
                    raise ValueError(
                        f"{type(topo).__name__} is time-varying: "
                        "correction_collective needs the round counter "
                        "(step=...)"
                    )
                branches = [
                    functools.partial(self._correction_compressed, phase)
                    for phase in topo.phases
                ]
                corr, choco = jax.lax.switch(
                    step % topo.period, branches, tree, state
                )
            return self._push_correction(state, corr, choco)
        if state is None and self.config.pipeline_depth > 1:
            raise ValueError(
                "pipeline_depth > 1 needs the current OverlapState (the "
                "in-flight correction queue) passed to "
                "correction_collective"
            )
        pending = () if state is None else tuple(state.pending)
        if not topo.is_time_varying:
            corr = self._correction(
                lambda t: self._mix_exact_tree_collective(t, topo), tree,
                pending,
            )
            return self._push_correction(state, corr, None)
        if step is None:
            raise ValueError(
                f"{type(topo).__name__} is time-varying: "
                "correction_collective needs the round counter (step=...)"
            )
        branches = [
            functools.partial(
                lambda phase, t: self._correction(
                    lambda s: self._mix_exact_tree_collective(s, phase), t,
                    pending,
                ),
                phase,
            )
            for phase in topo.phases
        ]
        corr = jax.lax.switch(step % topo.period, branches, tree)
        return self._push_correction(state, corr, None)

    def correction_simulated(
        self, tree: Any, w: jax.Array, state: OverlapState | None = None
    ) -> OverlapState:
        """Stacked-backend correction via the mixing matrix (w already
        phase-selected by the caller): ``(W - I) z`` exact, or the CHOCO
        innovation correction when a (bucketed) compressor is configured."""
        if self.compressed:
            if state is None or state.choco is None:
                raise ValueError(
                    "compressed overlap needs the OverlapState carrying "
                    "CHOCO tracking (from init_state)"
                )
            corr, choco = self._correction_compressed(
                self.topology, tree, state, stacked_w=w
            )
            return self._push_correction(state, corr, choco)
        if state is None and self.config.pipeline_depth > 1:
            raise ValueError(
                "pipeline_depth > 1 needs the current OverlapState (the "
                "in-flight correction queue) passed to correction_simulated"
            )
        pending = () if state is None else tuple(state.pending)
        corr = self._correction(
            lambda t: self._mix_exact_tree_simulated(t, w), tree, pending
        )
        return self._push_correction(state, corr, None)

    # ---- simulated backend (stacked leading worker axis) ----------------
    def round_simulated(
        self,
        params: Any,
        state: ChocoState | None,
        w: jax.Array,
        alive: jax.Array | None = None,
        rng: jax.Array | None = None,
        step: jax.Array | None = None,
    ):
        """One gossip round on stacked arrays (leading axis = workers).

        ``alive`` (``(world,)`` of 0/1, only with ``config.faults``): the
        per-worker participation flags for this round. ``rng``: stacked
        ``(world,)`` keys for stochastic codecs — the same per-worker draws
        the collective backend makes. ``step``: round counter (required
        when ``codec_warmup_rounds > 0``).
        """
        with _span("gossip.round", backend="simulated"):
            return self._round_simulated(params, state, w, alive, rng, step)

    def _round_simulated(
        self,
        params: Any,
        state: ChocoState | None,
        w: jax.Array,
        alive: jax.Array | None = None,
        rng: jax.Array | None = None,
        step: jax.Array | None = None,
    ):
        if step is None and (
            self.config.codec_warmup_rounds > 0
            or self.config.codec_refresh_every > 0
        ):
            raise ValueError(
                "codec_warmup_rounds/codec_refresh_every need the round "
                "counter (step=...)"
            )
        n_iter = self.config.gossip_steps
        if self.config.push_sum_enabled:
            if self.config.path_filter is not None:
                sel, rebuild = self._select(params)
                mixed, new_state = pushsum_round_simulated(sel, state, w, alive)
                return rebuild(mixed), new_state
            return pushsum_round_simulated(params, state, w, alive)
        if not self.compressed:
            if alive is not None:
                w = masked_mixing_matrix(w, alive)
            flt = self.config.path_filter
            if self.bucketed:
                # bucketed wire (same layout as the collective backend:
                # the plan is built from per-worker shapes)
                if flt is not None:
                    sel, rebuild = self._select(params)
                    return rebuild(
                        self._mix_exact_leaves_simulated(sel, w, n_iter)
                    ), None
                return self._mix_exact_tree_simulated(params, w, n_iter), None
            if flt is not None:
                for _ in range(n_iter):
                    params = jax.tree_util.tree_map_with_path(
                        lambda p, x: simulated.mix_stacked(x, w) if flt(p) else x,
                        params,
                    )
                return params, None
            for _ in range(n_iter):
                params = simulated.mix_tree_stacked(params, w)
            return params, None

        comp = self.config.compressor
        # same partition as the collective backend (original paths)
        params, exact_leaves, rest_leaves, rebuild_split = self._partition(
            params
        )
        if exact_leaves is not None:
            # stay in step with the CHOCO leaves (bucketed when enabled)
            mixed_exact = self._mix_exact_leaves_simulated(
                exact_leaves, w, n_iter
            )
        f32 = lambda t: jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), t)
        x = f32(params)
        plan = treedef = fused = None
        xhat, s = state.xhat, state.s
        if self.bucketed:
            # same bucket layout as the collective backend (per-worker
            # shapes), stacked (W, total) buffers; xhat/s already live
            # per-bucket (init_state with world_size)
            leaves, treedef = jax.tree.flatten(x)
            plan = self._codec_plan(leaves, stacked=True)
            fused = self._fused_plan(plan)
            with _span("bucket.pack", buckets=plan.num_buckets):
                x = plan.pack(leaves, stacked=True)
            _check_bucket_state(x, xhat)

        def _track(x, xhat, s, it_rng):
            # vmaps the SAME compress_tree/decompress_tree path the
            # collective backend runs, so the per-leaf rng fold-in
            # convention has one source of truth and the backends draw
            # identical randomness (incl. the per-iteration fold)
            if fused is not None:
                return self._innovation_exchange_fused_simulated(
                    x, xhat, s, w, fused
                )
            return self._innovation_exchange_simulated(x, xhat, s, w, it_rng)

        if comp.stochastic and rng is None:
            raise ValueError(
                f"{type(comp).__name__} is stochastic and needs stacked rng"
            )

        def _choco(x, xhat, s):
            for it in range(n_iter):
                it_rng = (
                    rng
                    if (n_iter == 1 or rng is None)
                    else jax.vmap(lambda k: jax.random.fold_in(k, it))(rng)
                )
                xhat, s = _track(x, xhat, s, it_rng)
                x = jax.tree.map(
                    lambda xi, si, hi: xi + self.config.gamma * (si - hi),
                    x, s, xhat,
                )
            return x, xhat, s

        def _warm(x, xhat, s):
            xhat, s = _track(x, xhat, s, rng)
            for _ in range(n_iter):  # match the exact engine at this T
                x = simulated.mix_tree_stacked(x, w)
            return x, xhat, s

        warm = self.config.codec_warmup_rounds
        refresh = self.config.codec_refresh_every
        if warm > 0 or refresh > 0:
            pred = None
            if warm > 0:
                pred = step < warm
            if refresh > 0:
                hit = step % refresh == 0
                pred = hit if pred is None else jnp.logical_or(pred, hit)
            x, xhat, s = jax.lax.cond(pred, _warm, _choco, x, xhat, s)
        else:
            x, xhat, s = _choco(x, xhat, s)
        x_new = x
        if plan is not None:
            # params back to leaves; xhat/s stay per-bucket
            with _span("bucket.unpack"):
                x_new = jax.tree.unflatten(
                    treedef, plan.unpack(x_new, stacked=True)
                )
        x_new = jax.tree.map(lambda new, old: new.astype(old.dtype), x_new, params)
        if rebuild_split is not None:
            x_new = rebuild_split(
                jax.tree.leaves(x_new), mixed_exact, rest_leaves
            )
        return x_new, ChocoState(xhat=xhat, s=s)

    # ---- accounting -----------------------------------------------------
    def wire_bytes_per_round(self, params: Any) -> int:
        """Bytes ONE worker sends per STEADY-STATE gossip round.

        Exact mixing ships each gossiped leaf densely once per shift
        (dense topologies: one all-reduce pass counted as one send);
        compressed gossip ships the codec payload instead. Push-sum adds
        one f32 mass scalar per shift. Time-varying topologies report the
        per-period average. ``gossip_steps`` multiplies the payload.
        ``codec_warmup_rounds`` is NOT folded in: each warmup round runs
        ``gossip_steps`` DENSE mixing passes (every consensus iteration
        of a warm round ships the full params) plus ONE innovation
        payload to keep xhat tracking in step — a transient, not the
        steady state this accounting describes. Callers totalling a
        run's traffic should add ``warmup * (gossip_steps * dense +
        payload)`` bytes for the first ``codec_warmup_rounds`` rounds.
        """
        import numpy as np

        comp = self.config.compressor
        dense_bytes = lambda x: int(np.prod(x.shape)) * np.dtype(
            jnp.float32
        ).itemsize
        exact_payload = 0
        if comp is not None:
            # exact-mixed leaves (compress_filter, e.g. BN stats) ship
            # dense; path_filter-excluded leaves ship nothing
            params, exact_leaves, _, _ = self._partition(params)
            if exact_leaves is not None:
                exact_payload = sum(dense_bytes(x) for x in exact_leaves)
        elif self.config.path_filter is not None:
            params, _ = self._select(params)

        def leaf_bytes(x) -> int:
            if comp is None:
                return dense_bytes(x)
            return comp.wire_bytes(tuple(x.shape), jnp.float32)

        if comp is not None and self.bucketed:
            # one payload per BUCKET over the leaf-aligned packed length —
            # never larger than the per-leaf sum for chunk-decomposable
            # codecs (boundary padding matches the codec's own per-leaf
            # padding, and value-vector coalescing amortizes tail chunks)
            plan = self._codec_plan(jax.tree.leaves(params))
            payload = (
                sum(
                    comp.wire_bytes((b.total,), jnp.float32)
                    for b in plan.buckets
                )
                + exact_payload
            )
        else:
            payload = (
                sum(leaf_bytes(x) for x in jax.tree.leaves(params))
                + exact_payload
            )
        sends = self._sends_per_round()
        mass = 4 * sends if self.config.push_sum_enabled else 0
        # every extra consensus iteration ships a fresh payload
        return int(payload * sends * self.config.gossip_steps + mass)

    def _sends_per_round(self) -> float:
        """Neighbor sends per round (psum counts 1; time-varying
        topologies report the per-period average) — the one definition
        both the wire accounting and telemetry() divide by."""
        topo = self.topology
        if topo.is_time_varying:
            return sum(
                (1 if p.uses_psum else len(p.shifts)) for p in topo.phases
            ) / topo.period
        return 1 if topo.uses_psum else len(topo.shifts)

    # ---- metrics --------------------------------------------------------
    def consensus_error_collective(
        self, params: Any, shard_axes: tuple[str, ...] = ()
    ) -> jax.Array:
        return collectives.consensus_error(params, self.topology, shard_axes)

    def consensus_error_simulated(self, params: Any) -> jax.Array:
        return simulated.consensus_error_stacked(params, self.topology.world_size)

    # ---- telemetry ------------------------------------------------------
    def telemetry(self, params: Any) -> dict[str, float]:
        """Static per-round wire facts for the metrics registry (see
        docs/observability.md): bytes one worker sends per round and per
        neighbor send, the bucket count of the active wire layout, and
        the dense->wire compression ratio. ``params`` may be shape
        structs (``jax.eval_shape`` output) — nothing is materialized.
        """
        import numpy as np

        wire = self.wire_bytes_per_round(params)
        sends = max(self._sends_per_round(), 1e-9)
        sel = params
        if self.config.path_filter is not None:
            sel, _ = self._select(params)
        dense = sum(
            int(np.prod(x.shape)) * 4 for x in jax.tree.leaves(sel)
        )
        plan = self.bucket_plan(params)
        # one send's payload; gossip_steps multiplies the round total but
        # not the per-send size, and the ratio is dense vs ONE payload
        # (the codec's compression), not vs the round's repeat count
        per_send = wire / sends / max(self.config.gossip_steps, 1)
        fused_buckets = (
            plan.num_buckets if plan is not None and self.fused_wire_active
            else 0
        )
        # kernel launches one fused round traces: encode + decode per
        # bucket per innovation exchange (psum topologies decode via the
        # reduction, so only the encode kernel runs)
        stages = 1 if self.topology.uses_psum else 2
        return {
            "wire_bytes_per_round": float(wire),
            "wire_bytes_per_neighbor": float(per_send),
            "gossip_buckets": float(plan.num_buckets) if plan else 0.0,
            "compression_ratio": float(dense / per_send) if wire else 0.0,
            "neighbor_sends_per_round": float(sends),
            "wire_fused_buckets": float(fused_buckets),
            "wire_fused_kernel_calls_per_round": float(
                stages * fused_buckets * self.config.gossip_steps
            ),
            "gossip_pipeline_depth": float(self.config.pipeline_depth),
        }

    def register_costs(
        self, ledger: Any, params: Any, *, name: str = "gossip.round"
    ) -> Any:
        """Lower + compile ONE simulated gossip round over ``params``
        into the cost ledger (:mod:`consensusml_tpu.obs.costs`), tagged
        with the active bucket plan.

        ``params`` is the STACKED gossiped tree (leading worker axis);
        shape structs are fine — nothing is materialized or executed,
        and the jit dispatch caches are untouched (AOT lowering). The
        row's ``meta`` carries the transport facts the attribution
        report labels buckets with: bucket count and per-bucket packed
        element counts from :meth:`bucket_plan`, per-worker wire bytes,
        fused-wire/pipeline state. Overlap configs register their
        transport twin (``overlap=False``) — the innovation exchange is
        the same program family; the delayed-correction bookkeeping
        lives in the train step's own row.

        Stochastic codecs thread per-worker rng; warmup/refresh configs
        thread the round counter — both become abstract arguments here
        so every config family lowers. Returns the
        :class:`~consensusml_tpu.obs.costs.ExecutableCost` row.
        """
        eng = self
        if self.config.overlap:
            eng = ConsensusEngine(
                dataclasses.replace(
                    self.config, overlap=False, pipeline_depth=1
                )
            )
        topo = eng.topology
        w = (
            simulated.phase_matrices(topo)[0]
            if topo.is_time_varying
            else simulated.mixing_matrix(topo)
        )
        state = jax.eval_shape(
            lambda p: eng.init_state(p, world_size=topo.world_size), params
        )
        extra_names: list[str] = []
        extra_args: list[Any] = []
        if (
            eng.config.codec_warmup_rounds > 0
            or eng.config.codec_refresh_every > 0
        ):
            extra_names.append("step")
            extra_args.append(jax.ShapeDtypeStruct((), jnp.int32))
        comp = eng.config.compressor
        if comp is not None and comp.stochastic:
            extra_names.append("rng")
            extra_args.append(
                jax.eval_shape(
                    lambda: jax.vmap(jax.random.key)(
                        jnp.arange(topo.world_size)
                    )
                )
            )

        def round_fn(p, s, *extra):
            kw = dict(zip(extra_names, extra))
            return eng.round_simulated(
                p, s, w, None, kw.get("rng"), step=kw.get("step")
            )

        per_worker = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params
        )
        plan = eng.bucket_plan(params, stacked=True)
        meta = {
            "topology": type(topo).__name__,
            "world": topo.world_size,
            "buckets": plan.num_buckets if plan is not None else 0,
            "bucket_elems": (
                [int(b.total) for b in plan.buckets]
                if plan is not None
                else []
            ),
            "wire_bytes_per_round": eng.wire_bytes_per_round(per_worker),
            "fused_wire": eng.fused_wire_active,
            "pipeline_depth": self.config.pipeline_depth,
            "gossip_steps": eng.config.gossip_steps,
            "overlap_twin": self.config.overlap,
        }
        # round_fn goes in BARE: the ledger jit-wraps it at the AOT
        # boundary (costs.register), keeping this module free of a jit
        # entry point that exists only for analysis
        return ledger.register(
            name, round_fn, params, state, *extra_args, meta=meta
        )

    def choco_residual(self, state: Any) -> float | None:
        """Host-side CHOCO tracking residual ``||s - xhat||`` from a
        gossip state (ChocoState, or an OverlapState carrying one) —
        the quantity whose growth signals the codec losing track of the
        params (docs/convergence.md frontier). None for exact mixing.
        Fetches the state to host; sample it at ``--telemetry-every``
        cadence, not every round."""
        choco = getattr(state, "choco", state)
        if not isinstance(choco, ChocoState):
            return None
        # ONE batched fetch of both trees: per-leaf device_get pairs
        # serialized 2x#leaves transfers on the telemetry path
        # (cml-check host-sync:host-sync:consensusml_tpu/consensus/
        # engine.py:ConsensusEngine.choco_residual:device_get); the
        # remaining single sync is this metric's documented cost
        s_host, hat_host = jax.device_get(
            (jax.tree.leaves(choco.s), jax.tree.leaves(choco.xhat))
        )
        sq = 0.0
        for si, hi in zip(s_host, hat_host):
            d = si.astype("float64") - hi.astype("float64")
            sq += float((d ** 2).sum())
        return float(sq) ** 0.5
