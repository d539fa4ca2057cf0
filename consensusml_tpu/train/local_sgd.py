"""Local-SGD train step builders for both execution backends.

``loss_fn(params, model_state, batch, rng) -> (scalar loss, new_model_state)``
(or ``(scalar loss, LossAux(new_model_state, metrics, first_step))`` where
the model counts or shows something on the device: the counters ride out in
the round's metrics summed over the inner steps and the workers, what it
shows of the round's first inner step as it is, one entry a worker)
is user code (a model from :mod:`consensusml_tpu.models` or anything else);
``model_state`` carries non-gradient mutables (BatchNorm running stats —
pass ``{}`` for stateless models). A *round* consumes a batch of shape
``(H, B, ...)`` per worker: H microbatches for the inner loop, then one
gossip round (params AND model_state are gossip-averaged jointly, so BN
statistics reach consensus along with the weights), then the
consensus-error measurement — all in one XLA program.

Collective backend: per-worker code wrapped in ``shard_map`` over the
topology's mesh; global arrays carry the mesh's leading worker axes.
Simulated backend: ``vmap`` over a flat leading worker axis on one device,
gossip via the mixing matrix. Cross-validated in tests/test_local_sgd.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from consensusml_tpu.comm import WorkerMesh, simulated
from consensusml_tpu.consensus import (
    ChocoState,
    ConsensusEngine,
    GossipConfig,
    draw_alive,
    tree_all_finite,
)
from consensusml_tpu.obs import span as _span
from consensusml_tpu.train.outer import SlowMoConfig, slowmo_init, slowmo_update

__all__ = [
    "LocalSGDConfig",
    "LossAux",
    "TrainState",
    "batch_placement",
    "init_state",
    "init_stacked_state",
    "make_collective_train_step",
    "make_simulated_train_step",
]

LossFn = Callable[[Any, Any, Any, jax.Array], tuple[jax.Array, Any]]


class LossAux(NamedTuple):
    """What a ``loss_fn`` may return in place of the bare model state: the
    state, a dict of device counters (an expert layer's rows per expert)
    that the round's ``metrics`` carry out under the same keys, summed over
    the round's inner steps and over the workers, and a dict of what the
    model shows of ONE step (the experts each token chose, the size of each
    scan's or delta rule's output): the round's
    ``metrics`` carry the FIRST inner step's, stacked over the workers.
    Device values, fetched by whoever wants them."""

    model_state: Any
    metrics: dict
    first_step: dict = {}


class TrainState(NamedTuple):
    step: jax.Array  # outer-round counter
    params: Any
    model_state: Any  # non-gradient mutables (BN stats, ...); {} if none
    opt_state: Any
    gossip: Any  # ChocoState | PushSumState | OverlapState | None per GossipConfig
    rng: jax.Array
    outer: Any = None  # SlowMo {x, u} when LocalSGDConfig.outer is set


@dataclasses.dataclass(frozen=True)
class LocalSGDConfig:
    """One decentralized training round = H local steps + one gossip round
    (+ an optional SlowMo slow-momentum step on the mixed params)."""

    gossip: GossipConfig
    optimizer: optax.GradientTransformation
    h: int = 1  # local (inner) steps between gossip rounds
    outer: SlowMoConfig | None = None  # None => mixed params used as-is
    # gossip-wire bucketing knob, surfaced here so training configs and
    # the CLI override it in one place: anything but the "inherit"
    # sentinel replaces gossip.bucket_bytes (None or 0 => per-leaf wire;
    # see GossipConfig.bucket_bytes for the semantics)
    bucket_bytes: int | None | str = "inherit"

    def __post_init__(self):
        if self.bucket_bytes != "inherit":
            object.__setattr__(
                self,
                "gossip",
                dataclasses.replace(
                    self.gossip, bucket_bytes=self.bucket_bytes or None
                ),
            )
        if self.gossip.overlap and self.outer is not None:
            raise NotImplementedError(
                "overlap gossip + SlowMo is not supported: SlowMo's slow "
                "momentum steps on the same-round mixed params, which "
                "overlap mode never materializes"
            )

    def engine(self) -> ConsensusEngine:
        return ConsensusEngine(self.gossip)


# ---------------------------------------------------------------------------
# state init
# ---------------------------------------------------------------------------


def _gossiped(params: Any, model_state: Any) -> dict[str, Any]:
    """The tree that rides the gossip round: weights + BN-style stats."""
    return {"params": params, "model_state": model_state}


def init_state(cfg: LocalSGDConfig, params: Any, rng: jax.Array, model_state: Any = None) -> TrainState:
    """Per-worker (unstacked) state — used inside the collective backend."""
    model_state = {} if model_state is None else model_state
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        model_state=model_state,
        opt_state=cfg.optimizer.init(params),
        gossip=cfg.engine().init_state(_gossiped(params, model_state)),
        rng=rng,
        outer=slowmo_init(params) if cfg.outer is not None else None,
    )


def init_stacked_state(
    cfg: LocalSGDConfig,
    init_params: Callable[[jax.Array], Any],
    rng: jax.Array,
    world_size: int,
    *,
    with_model_state: bool | None = None,
) -> TrainState:
    """Stacked state with per-worker independent inits (simulated backend,
    or host-side construction for the collective backend).

    ``init_params(rng)`` returns either ``params`` or ``(params,
    model_state)``. By default a length-2 tuple result is treated as the
    latter; if your *params themselves* are a tuple pytree, pass
    ``with_model_state=False`` explicitly. Each worker gets its own init
    rng — decentralized training starts from DISAGREEING replicas and
    consensus pulls them together (that is the point of the
    consensus-error metric).
    """
    rngs = jax.random.split(rng, world_size)
    if with_model_state is None:
        probe = jax.eval_shape(init_params, rngs[0])
        has_state = isinstance(probe, tuple) and len(probe) == 2
    else:
        has_state = with_model_state
    if has_state:
        params, model_state = jax.vmap(init_params)(rngs)
    else:
        params = jax.vmap(init_params)(rngs)
        model_state = {}
    opt_state = jax.vmap(cfg.optimizer.init)(params)
    return TrainState(
        # per-worker step counter so every leaf carries the worker axis
        # (required for sharding under the collective backend)
        step=jnp.zeros((world_size,), jnp.int32),
        params=params,
        model_state=model_state,
        opt_state=opt_state,
        gossip=cfg.engine().init_state(
            _gossiped(params, model_state), world_size=world_size
        ),
        rng=jax.vmap(jax.random.fold_in, in_axes=(0, None))(rngs, 1),
        outer=slowmo_init(params) if cfg.outer is not None else None,
    )


def batch_placement(backend: str, wmesh: WorkerMesh | None = None):
    """Where a round batch should live for ``backend``'s train step.

    Hand the result to ``DevicePrefetcher(placement=...)`` so batches
    are staged exactly where the jitted step consumes them — both step
    builders accept already-on-device batches as-is (a committed array
    with the right placement is used in place; only host arrays pay a
    dispatch-time transfer), so a prefetched batch crosses the host→
    device boundary exactly once.

    - ``"collective"`` (single-process): the mesh's flat-stacked
      sharding — leading ``(W, ...)`` axis split over the worker axes,
      matching the step's ``shard_map`` in_specs, so jit neither
      reshards nor re-transfers.
    - ``"simulated"`` (or no mesh): ``None`` — the default device.

    Multi-controller runs return ``None`` too: ``device_put`` cannot
    target non-addressable shards; the train loop assembles global
    arrays via ``WorkerMesh.shard_stacked`` instead (which skips leaves
    that already carry the target sharding).
    """
    if (
        backend == "collective"
        and wmesh is not None
        and jax.process_count() == 1
    ):
        return wmesh.stacked_sharding()
    return None


# ---------------------------------------------------------------------------
# shared inner loop
# ---------------------------------------------------------------------------


def _inner_loop(
    cfg: LocalSGDConfig, loss_fn: LossFn, params, model_state, opt_state, rng, batch
):
    """H local optimizer steps via lax.scan. ``batch`` leaves: (H, ...)."""
    for leaf in jax.tree.leaves(batch):
        if leaf.shape[0] != cfg.h:
            raise ValueError(
                f"batch leading (inner-step) axis is {leaf.shape[0]} but "
                f"LocalSGDConfig.h={cfg.h}; each round batch must carry "
                "exactly h microbatches per worker"
            )

    def body(carry, microbatch):
        params, model_state, opt_state, rng = carry
        rng, sub = jax.random.split(rng)
        # trace-time spans, named scopes afterwards: the device ops of an
        # inner step split into forward + backward (jvp / transpose inside
        # train.grad, by JAX's own naming) and the optimizer
        with _span("train.grad"):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, model_state, microbatch, sub)
        model_state, *handed = aux if isinstance(aux, LossAux) else (aux, {}, {})
        with _span("train.optimizer"):
            updates, opt_state = cfg.optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return (params, model_state, opt_state, rng), (loss, handed)

    with _span("train.inner_loop", h=cfg.h):
        (params, model_state, opt_state, rng), (losses, (counted, shown)) = jax.lax.scan(
            body, (params, model_state, opt_state, rng), batch
        )
    handed = (
        jax.tree.map(lambda x: jnp.sum(x, axis=0), counted),
        jax.tree.map(lambda x: x[0], shown),
    )
    return params, model_state, opt_state, rng, jnp.mean(losses), handed


# ---------------------------------------------------------------------------
# collective backend
# ---------------------------------------------------------------------------


def _squeeze(tree: Any, n_axes: int) -> Any:
    return jax.tree.map(lambda x: x.reshape(x.shape[n_axes:]), tree)


def _unsqueeze(tree: Any, n_axes: int) -> Any:
    return jax.tree.map(lambda x: x.reshape((1,) * n_axes + x.shape), tree)


def _handed_collective(handed, axis_names) -> dict:
    """What a loss handed out (:class:`LossAux`), as round metrics: the
    counters summed over the mesh's workers, the first step's values
    gathered, one entry a worker."""
    counted, shown = handed
    rank, world = jax.lax.axis_index(axis_names), jax.lax.axis_size(axis_names)
    # each worker's into its own row of zeros, then summed: psum's result is
    # typed as the same on every worker, which all_gather's is not
    rows = jax.tree.map(lambda x: jnp.zeros((world, *x.shape), x.dtype).at[rank].set(x), shown)
    return jax.lax.psum({**counted, **rows}, axis_names)


def make_collective_train_step(
    cfg: LocalSGDConfig, loss_fn: LossFn, wmesh: WorkerMesh, rules=None
) -> Callable[[TrainState, Any], tuple[TrainState, dict[str, jax.Array]]]:
    """Build the jitted global train step for a device mesh.

    Inputs are GLOBAL stacked arrays with a FLAT leading worker axis —
    every ``TrainState`` leaf and batch leaf is ``(W, ...)`` in row-major
    rank order, exactly as :func:`init_stacked_state` and the data loaders
    produce (the same layout the simulated backend consumes, so the two
    backends are drop-in interchangeable). For multi-axis topologies
    (torus) the step reshapes ``W -> mesh_shape`` inside jit; with the
    sharding from :meth:`WorkerMesh.stacked_sharding` that reshape is
    layout-preserving (no data movement). Returns ``(new_state, metrics)``
    with replicated scalar metrics: mean loss and post-gossip consensus
    error — the reference's headline pair.

    ``rules`` (a :mod:`consensusml_tpu.parallel.sharding` rule list) is
    required when ``wmesh`` has MANUAL model axes (pipeline parallelism):
    the rules say which state dims are sharded over those axes, so the
    step can build per-leaf ``shard_map`` specs — e.g.
    ``pipeline_pp_rules()`` for a loss_fn built on ``pipeline_apply``
    whose stage-stacked params live under ``stages/``. The loss_fn must
    return a loss replicated over the manual model axes (use
    ``pipeline_last_stage_mean``). Gossip then exchanges each device's
    layer shard with the same stage of neighboring workers — stage-local
    traffic, no pp-axis gather.

    Compressed gossip under PP is STAGE-LOCAL: each device runs the codec
    on its own layer shard. Chunk-local codecs (``ChunkedTopKCompressor``
    with the chunk dividing the per-stage leaf size) are therefore
    bit-identical to the unsharded semantics; a global-per-leaf top-k
    (``TopKCompressor``) selects per shard instead, which changes WHICH
    elements ship (still contractive, just not oracle-identical — the
    cross-backend test pins the chunk-aligned case).
    """
    engine = cfg.engine()
    topo = wmesh.topology
    mesh_shape = topo.mesh_shape
    n_axes = len(mesh_shape)
    world = topo.world_size
    worker = P(*topo.axis_names)

    to_mesh = lambda t: jax.tree.map(
        lambda x: x.reshape(*mesh_shape, *x.shape[1:]), t
    )
    to_flat = lambda t: jax.tree.map(
        lambda x: x.reshape(world, *x.shape[n_axes:]), t
    )

    # With a model submesh (WorkerMesh.model_axes), shard_map goes
    # partial-manual: gossip axes are manual (ppermute/psum written here),
    # model axes stay auto — XLA inserts the intra-worker tensor-parallel
    # collectives from the param sharding annotations. Axes listed in
    # manual_model_axes (pp) are ALSO manual: their collectives live in
    # the loss_fn (pipeline_apply's stage ppermute), and state leaves are
    # sharded over them per `rules` (handled below via per-leaf specs).
    manual = wmesh.manual_axes()
    shard_kwargs = {} if manual is None else {"axis_names": manual}
    mm_axes = tuple(wmesh.manual_model_axes)
    if mm_axes:
        unsupported = [
            name
            for name, on in [
                ("overlap gossip", cfg.gossip.overlap),
                ("fault injection", cfg.gossip.faults is not None),
                ("SlowMo outer", cfg.outer is not None),
                # CHOCO's bucketed tracking state is one flat buffer per
                # bucket, laid out for the WHOLE tree: it cannot shard
                # over the stage axis the way per-leaf state does
                (
                    "the bucketed compressed wire (use the per-leaf wire: "
                    "LocalSGDConfig(bucket_bytes=0))",
                    engine.compressed and engine.bucketed,
                ),
            ]
            if on
        ]
        if unsupported:
            # each needs a per-worker scalar consistent ACROSS the model
            # shards (alive flags / finite checks / outer momentum norms)
            # — composable later, rejected loudly now
            raise NotImplementedError(
                f"{', '.join(unsupported)} not supported with manual model "
                f"axes {mm_axes} (pipeline-parallel workers)"
            )
    faults = cfg.gossip.faults
    comp = cfg.gossip.compressor
    stochastic_comp = comp is not None and comp.stochastic

    def sharded_round(state: TrainState, batch: Any):
        state = _squeeze(state, n_axes)
        batch = _squeeze(batch, n_axes)
        if cfg.gossip.overlap:
            # combine-then-adapt: apply last round's correction, then run
            # the inner loop on z WHILE this round's correction (ppermutes
            # on z, independent of the local steps) is in flight
            z = engine.apply_correction(
                _gossiped(state.params, state.model_state), state.gossip
            )
            gossip = engine.correction_collective(
                z, state.gossip, step=state.step
            )
            # post-gossip measurement point, same as every other mode:
            # z is the params right after the mixing correction landed
            err = engine.consensus_error_collective(z["params"])
            params, model_state, opt_state, rng, loss, handed = _inner_loop(
                cfg, loss_fn, z["params"], z["model_state"], state.opt_state,
                state.rng, batch,
            )
            new_state = TrainState(
                step=state.step + 1,
                params=params,
                model_state=model_state,
                opt_state=opt_state,
                gossip=gossip,
                rng=rng,
                outer=state.outer,
            )
            metrics = {
                "loss": jax.lax.pmean(loss, topo.axis_names),
                "consensus_error": err,
                **_handed_collective(handed, topo.axis_names),
            }
            return _unsqueeze(new_state, n_axes), metrics
        params, model_state, opt_state, rng, loss, handed = _inner_loop(
            cfg, loss_fn, state.params, state.model_state, state.opt_state, state.rng, batch
        )
        if faults is None:
            alive = None
            mean_loss = jax.lax.pmean(loss, topo.axis_names)
        else:
            rng, fsub = jax.random.split(rng)
            inject = draw_alive(fsub, faults.drop_prob)  # comm failure: local
            # steps survive, the worker just misses this gossip round
            ok = (
                # model_state gossips too, so it must pass the finite check
                tree_all_finite(loss, (params, model_state))
                if faults.detect_nonfinite
                else jnp.ones((), jnp.float32)
            )
            # a non-finite inner loop is rolled back entirely so the NaN
            # neither persists locally nor reaches the wire
            revert = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(ok > 0, a, b), new, old
            )
            params = revert(params, state.params)
            model_state = revert(model_state, state.model_state)
            opt_state = revert(opt_state, state.opt_state)
            alive = inject * ok
            n_ok = jax.lax.psum(ok, topo.axis_names)
            mean_loss = jax.lax.psum(ok * loss, topo.axis_names) / jnp.maximum(
                n_ok, 1.0
            )
        if stochastic_comp:
            rng, gsub = jax.random.split(rng)
        else:
            gsub = None
        mixed, gossip = engine.round_collective(
            _gossiped(params, model_state), state.gossip, alive, gsub,
            step=state.step,
        )
        params, model_state = mixed["params"], mixed["model_state"]
        outer = state.outer
        if cfg.outer is not None:
            params, outer = slowmo_update(cfg.outer, params, outer)
        with _span("train.consensus_error"):
            err = engine.consensus_error_collective(params, shard_axes=mm_axes)
        new_state = TrainState(
            step=state.step + 1,
            params=params,
            model_state=model_state,
            opt_state=opt_state,
            gossip=gossip,
            rng=rng,
            outer=outer,
        )
        metrics = {
            "loss": mean_loss,
            "consensus_error": err,
            **_handed_collective(handed, topo.axis_names),
        }
        if faults is not None:
            metrics["alive_frac"] = jax.lax.pmean(alive, topo.axis_names)
            # the per-rank mask (rank-ordered), for the labeled per-worker
            # drop/recovery counters (consensus.faults.record_fault_metrics)
            metrics["alive_mask"] = jnp.reshape(
                jax.lax.all_gather(alive, topo.axis_names), (world,)
            )
        return _unsqueeze(new_state, n_axes), metrics

    # donate the old TrainState so XLA updates params/opt buffers in place —
    # without this every round copies the full replica set through HBM
    def _wrap(sharded):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def jitted_step(state: TrainState, batch: Any):
            new_state, metrics = sharded(to_mesh(state), to_mesh(batch))
            return to_flat(new_state), metrics

        return jitted_step

    if not mm_axes:
        jitted_step = _wrap(
            jax.shard_map(
                sharded_round,
                mesh=wmesh.mesh,
                in_specs=(worker, worker),
                out_specs=(worker, P()),
                **shard_kwargs,
            )
        )
        if manual is None:
            return jitted_step

        def train_step(state: TrainState, batch: Any):
            # auto-axis sharding propagation needs the ambient mesh set
            with jax.sharding.set_mesh(wmesh.mesh):
                return jitted_step(state, batch)

        # the underlying jit object, for .lower()/AOT inspection (full-scale
        # shape smoke tests trace without executing); callers must set the
        # ambient mesh themselves when using it directly
        train_step._jitted = jitted_step
        return train_step

    # ---- manual model axes (pipeline-parallel workers) ------------------
    # shard_map specs must spell out which state dims ride the manual
    # model axes (there is no auto mode to infer them), and those dims
    # are per-leaf (stage-stacked kernels vs per-worker scalars), so the
    # specs come from `rules` and the concrete state/batch structure —
    # built lazily on first call and cached by tree structure.
    from consensusml_tpu.parallel.sharding import spec_for_path

    if rules is None:
        raise ValueError(
            f"manual model axes {mm_axes} need sharding `rules` naming the "
            "state dims that ride them (e.g. pipeline_pp_rules() for "
            "stage-stacked params under 'stages/'); without rules every "
            "leaf would silently replicate over the pipeline axis"
        )

    def specs_for(tree, expect_manual=False):
        hits = [0]

        def one(path, leaf):
            pathstr = jax.tree_util.keystr(path, simple=True, separator="/")
            tail = spec_for_path(pathstr, leaf.ndim - 1, rules)
            # auto model axes (tp) stay out of manual specs — XLA carries
            # them through the arrays' own shardings
            tail = tuple(a if a in mm_axes else None for a in tail)
            hits[0] += any(a is not None for a in tail)
            return P(*topo.axis_names, *tail)

        specs = jax.tree.map_with_path(one, tree)
        if expect_manual and not hits[0]:
            raise ValueError(
                f"no state leaf matched the sharding rules for manual model "
                f"axes {mm_axes} — the stage-stacked params would replicate "
                "over the pipeline axis; check the rule patterns against "
                "the param paths"
            )
        return specs

    cache: dict = {}

    def train_step(state: TrainState, batch: Any):
        ranks = lambda t: tuple(x.ndim for x in jax.tree.leaves(t))
        key = (
            jax.tree.structure(state), ranks(state),
            jax.tree.structure(batch), ranks(batch),
        )
        if key not in cache:
            state_specs = specs_for(state, expect_manual=True)
            cache[key] = _wrap(
                jax.shard_map(
                    sharded_round,
                    mesh=wmesh.mesh,
                    in_specs=(state_specs, specs_for(batch)),
                    out_specs=(state_specs, P()),
                    **shard_kwargs,
                )
            )
        with jax.sharding.set_mesh(wmesh.mesh):
            return cache[key](state, batch)

    return train_step


# ---------------------------------------------------------------------------
# simulated backend
# ---------------------------------------------------------------------------


def _handed_simulated(handed) -> dict:
    """What a loss handed out (:class:`LossAux`), as round metrics: the
    counters summed over the stacked workers, the first step's values as
    ``vmap`` stacked them."""
    counted, shown = handed
    return {**jax.tree.map(lambda x: jnp.sum(x, axis=0), counted), **shown}


def make_simulated_train_step(
    cfg: LocalSGDConfig, loss_fn: LossFn, external_alive: bool = False
) -> Callable[..., tuple[TrainState, dict[str, jax.Array]]]:
    """Build the jitted train step for stacked workers on ONE device.

    State/batch leaves carry a flat leading worker axis (N, ...). The inner
    loop vmaps over workers; gossip is an einsum with the mixing matrix.
    Reference parity: the CPU-simulated-workers mode (BASELINE.json
    configs[0]).

    ``external_alive=True`` (the swarm churn harness): the returned step's
    signature becomes ``step(state, batch, alive, frozen)`` with two
    ``(world,)`` 0/1 float masks replacing the rng fault draw —
    ``alive[i]=0`` means worker ``i`` misses this gossip round (straggler
    or dropped), ``frozen[i]=1`` additionally rolls its inner loop back
    entirely (a PREEMPTED member: its replica must stay untouched until
    it rejoins, where ``drop_prob`` faults model a mere comm blip whose
    local steps survive). Requires ``cfg.gossip.faults`` for the masked
    gossip plumbing; use ``FaultConfig(drop_prob=0.0)`` for a purely
    scheduled fault model.
    """
    engine = cfg.engine()
    topo = cfg.gossip.topology
    # time-varying topologies: stack per-phase matrices once, index by round
    w_all = (
        simulated.phase_matrices(topo)
        if topo.is_time_varying
        else simulated.mixing_matrix(topo)
    )
    faults = cfg.gossip.faults
    comp = cfg.gossip.compressor
    stochastic_comp = comp is not None and comp.stochastic
    if external_alive and faults is None:
        raise ValueError(
            "external_alive needs cfg.gossip.faults (the alive-mask gossip "
            "plumbing); use FaultConfig(drop_prob=0.0) for scheduled-only "
            "churn"
        )

    def _round(state: TrainState, batch: Any, alive_in, frozen):
        def worker(params, model_state, opt_state, rng, batch):
            return _inner_loop(cfg, loss_fn, params, model_state, opt_state, rng, batch)

        if cfg.gossip.overlap:
            w = (
                w_all[state.step[0] % topo.period]
                if topo.is_time_varying
                else w_all
            )
            z = engine.apply_correction(
                _gossiped(state.params, state.model_state), state.gossip
            )
            gossip = engine.correction_simulated(z, w, state.gossip)
            # post-gossip measurement point, same as every other mode
            err = engine.consensus_error_simulated(z["params"])
            params, model_state, opt_state, rng, losses, handed = jax.vmap(worker)(
                z["params"], z["model_state"], state.opt_state, state.rng, batch
            )
            new_state = TrainState(
                step=state.step + 1,
                params=params,
                model_state=model_state,
                opt_state=opt_state,
                gossip=gossip,
                rng=rng,
                outer=state.outer,
            )
            return new_state, {
                "loss": jnp.mean(losses),
                "consensus_error": err,
                **_handed_simulated(handed),
            }
        params, model_state, opt_state, rng, losses, handed = jax.vmap(worker)(
            state.params, state.model_state, state.opt_state, state.rng, batch
        )
        if faults is None:
            alive = None
            mean_loss = jnp.mean(losses)
        else:
            if alive_in is None:
                # identical per-worker draws/checks as the collective backend
                rng, fsub = (
                    lambda s: (s[:, 0], s[:, 1])
                )(jax.vmap(jax.random.split)(rng))
                inject = jax.vmap(draw_alive, in_axes=(0, None))(
                    fsub, faults.drop_prob
                )
            else:
                inject = alive_in  # scheduled churn: deterministic masks
            ok = (
                # model_state gossips too, so it must pass the finite check
                jax.vmap(tree_all_finite)(losses, (params, model_state))
                if faults.detect_nonfinite
                else jnp.ones_like(losses)
            )
            # rows to roll back: non-finite inner loops always; frozen
            # (preempted) members too — their replica is elsewhere, the
            # local steps this program ran for them never happened
            keep = ok if frozen is None else ok * (1.0 - frozen)
            bc = lambda m, x: m.reshape(m.shape + (1,) * (x.ndim - 1))
            revert = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(bc(keep, a) > 0, a, b), new, old
            )
            params = revert(params, state.params)
            model_state = revert(model_state, state.model_state)
            opt_state = revert(opt_state, state.opt_state)
            alive = inject * keep
            mean_loss = jnp.sum(keep * losses) / jnp.maximum(
                jnp.sum(keep), 1.0
            )
        if stochastic_comp:
            rng, gsub = (
                lambda s: (s[:, 0], s[:, 1])
            )(jax.vmap(jax.random.split)(rng))
        else:
            gsub = None
        w = (
            w_all[state.step[0] % topo.period] if topo.is_time_varying else w_all
        )
        mixed, gossip = engine.round_simulated(
            _gossiped(params, model_state), state.gossip, w, alive, gsub,
            step=state.step[0],
        )
        params, model_state = mixed["params"], mixed["model_state"]
        outer = state.outer
        if cfg.outer is not None:
            # elementwise update — identical math on stacked worker arrays
            params, outer = slowmo_update(cfg.outer, params, outer)
        with _span("train.consensus_error"):
            err = engine.consensus_error_simulated(params)
        new_state = TrainState(
            step=state.step + 1,
            params=params,
            model_state=model_state,
            opt_state=opt_state,
            gossip=gossip,
            rng=rng,
            outer=outer,
        )
        metrics = {"loss": mean_loss, "consensus_error": err, **_handed_simulated(handed)}
        if faults is not None:
            metrics["alive_frac"] = jnp.mean(alive)
            metrics["alive_mask"] = alive
        return new_state, metrics

    if external_alive:

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state: TrainState, batch: Any, alive, frozen):
            return _round(state, batch, alive, frozen)

    else:

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state: TrainState, batch: Any):
            return _round(state, batch, None, None)

    return train_step
