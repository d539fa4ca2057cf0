"""Training driver for the ``xing4`` configurations: local-SGD rounds under exact
gossip, one chip's share of an expert-parallel deployment.

``drivers/train_qwen3_next.py``'s driver with what names the model replaced: the
shipped recipe's decoder by the pattern ``LDLELELELE`` (latent attention, a dense
or an expert MLP) on four hyper-connected streams with its multi-token-prediction
module, ``reference/xing4.py`` and ``reference/train_xing4.py`` in place of that
reference, and out of round 1's own metrics the size of every latent attention's
output (``mla_rms``), of every stream a sub-block wrote (``mhc_stream_rms``) and
the two-ahead loss (``mtp_loss``) beside the chosen experts. The round loop, the
feed, the rows and the judging are the base driver's.

For the per-layer metrics that time the program's own scopes
(``readers/scope_time.py``) a TRACED run leaves ``stats["op_scopes"]``: the
compiled round's instructions by name, each with the ``op_name`` the compiler
kept for it, read from the compiled round's text after the window.

``setup`` imports the program's new modules FIRST: on a tree without them the
run ends at once with exit code 3 (``run.py``: the program is not in this
checkout) instead of failing somewhere inside a compile.
"""

from __future__ import annotations

import dataclasses
import re
import statistics
import sys
import time

import numpy as np

import flops_xing4 as flops
import schedule
from drivers.train import CHECKED_ROUNDS, _floats
from drivers.train import Driver as TrainDriver
from reference import xing4 as ref
from reference import train as ref_train
from reference import train_xing4 as ref_rounds


SCOPED = ("mhc.", "mla.", "mtp.", "mlp.dense")  # the scope families ``scope_time`` reads


def program_sizes(mc) -> dict:
    """The reference's ``sizes`` as the program's model config states them
    (what ``reference.xing4.sizes_of`` reads from a configuration file)."""
    layers = len(mc.pattern) // 2
    sizes = {
        "vocab": mc.vocab_size, "hidden": mc.hidden, "layers": layers,
        "dense_layers": mc.pattern.count("D"), "heads": mc.heads,
        "q_rank": mc.q_lora_rank, "kv_rank": mc.kv_lora_rank, "nope_dim": mc.nope_dim,
        "rope_dim": mc.rope_dim, "v_dim": mc.v_dim, "rope_theta": float(mc.rope_theta),
        "rope_factor": float(mc.rope_factor), "beta_fast": float(mc.beta_fast),
        "beta_slow": float(mc.beta_slow), "original_max_len": mc.original_max_len,
        "mscale_all_dim": float(mc.mscale_all_dim), "dense_width": mc.dense_width,
        "experts": mc.experts, "held": mc.held, "held_start": mc.held_start, "top_k": mc.top_k,
        "route_scale": float(mc.route_scale), "expert_width": mc.expert_width,
        "shared_width": mc.shared_width, "score_correction": mc.score_correction,
        "eps": mc.norm_eps, "streams": mc.streams, "sinkhorn": mc.sinkhorn_iters,
        "hc_eps": mc.hc_eps, "clamp_min": float(mc.hc_clamp_min), "clamp_max": float(mc.hc_clamp_max),
        "mtp_layers": int(mc.mtp), "mtp_lambda": mc.mtp_lambda,
    }
    described = (mc.moe_scores, mc.moe_activation, mc.shared_gate, mc.zero_centred_norm)
    if mc.pattern != ref.pattern_of(sizes) or described != ("sigmoid", "swiglu", False, False):
        raise RuntimeError(f"the recipe's decoder is not the reference's: {mc.pattern} {described}")
    return sizes


_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name} of a compiled program's text, for the
    instructions whose ``op_name`` names one of the ``SCOPED`` families."""
    found = {}
    for line in hlo_text.splitlines():
        at = line.find('op_name="')
        if at < 0:
            continue
        scope = line[at + 9 : line.find('"', at + 9)]
        named = _INSTRUCTION.match(line)
        if named and any(s in scope for s in SCOPED):
            found[named.group(1)] = scope
    return found


class Driver(TrainDriver):
    def __init__(self, cell: dict):
        self.cell = cell
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.recipe = self.config["train"]
        self.sizes = ref.sizes_of(self.config)
        self.workers = int(self.traffic["workers"])
        self.seed = int(cell["seed"]) & schedule.SEED_MASK
        self.state = self.step = self.feed = None
        self.program = {}
        self.counted = []  # per round: (rows per held expert (E blocks, held), absent pairs (E blocks,))
        self.shapes, self.traced, self.stats = None, False, {}

    # -- set-up -----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        try:  # first: a tree without latent attention or the share's decoder ends here, at once
            from consensusml_tpu.models import hyper_connections, mla  # noqa: F401
            from consensusml_tpu.models.nemotron_h import (
                NemotronHLM, nemotron_h_loss_fn, xing4_share)  # noqa: F401
        except ImportError as e:
            print(f"bench: the program has no latent-attention decoder on hyper-connected streams ({e}): no result",
                  file=sys.stderr, flush=True)
            raise SystemExit(3)
        import jax
        import jax.numpy as jnp

        from consensusml_tpu import configs
        from consensusml_tpu.data.prefetch import prefetch_to_device
        from consensusml_tpu.train import batch_placement
        from consensusml_tpu.train.local_sgd import TrainState

        recipe, sizes = self.recipe, self.sizes
        marks = [("process", self.cell.get("process_t0", time.monotonic())), ("start", time.monotonic())]
        if self.traffic.get("backend", "simulated") != "simulated":
            raise NotImplementedError("this driver stacks workers on one chip (simulated)")
        bundle = configs.build(recipe["recipe"], recipe["scale"], world=self.workers)
        mc = bundle.model.config
        ran = program_sizes(mc)
        gossip = "exact" if bundle.cfg.gossip.compressor is None else "compressed"
        ran.update(h=bundle.cfg.h, learning_rate=bundle.base_lr, gossip=gossip,
                   warmup_steps=bundle.base_warmup_steps)
        stated = {**sizes, **{k: recipe[k] for k in ("h", "learning_rate", "gossip")},
                  "warmup_steps": recipe.get("warmup_steps", 0)}
        if ran != stated:
            differ = {k: (ran[k], stated[k]) for k in ran if ran[k] != stated[k]}
            raise RuntimeError(f"the recipe and the configuration differ (ran, stated): {differ}")
        model = NemotronHLM(config=dataclasses.replace(
            mc, dtype=jnp.dtype(self.config["compute_dtype"])))
        cfg = bundle.cfg
        self.step = self.build_step(cfg, nemotron_h_loss_fn(model))
        seeds = jnp.asarray(self.worker_seeds())
        marks.append(("build", time.monotonic()))

        def init(seeds, run_seed):  # the seeds are arguments: one compiled initialiser serves every seed
            params = jax.vmap(lambda s: ref.init_params(s, sizes))(seeds)
            keys = jax.random.split(jax.random.key(run_seed), self.workers)
            return TrainState(
                step=jnp.zeros((self.workers,), jnp.int32),
                params=params,
                model_state={},
                opt_state=jax.vmap(cfg.optimizer.init)(params),
                gossip=cfg.engine().init_state(
                    {"params": params, "model_state": {}}, world_size=self.workers
                ),
                rng=jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, 1),
            )

        self.state = jax.block_until_ready(
            jax.jit(init)(seeds, jnp.uint32(self.seed & 0x7FFFFFFF)))
        marks.append(("state", time.monotonic()))
        self.succ = schedule.successor_table(self.seed, sizes["vocab"])
        self.checked_rows = []
        self.program = {"loss": [], "mtp_loss": [], "consensus_error": []}
        self.feed = iter(prefetch_to_device(
            self._source(), 2, placement=batch_placement("simulated")
        ))
        norms = jax.jit(ref_train.leaf_norms)
        # the first parameters are made again from the seed rather than kept
        delta = jax.jit(lambda now, seed: ref_train.leaf_norms(
            jax.tree.map(lambda a, b: a[0] - b, now, ref.init_params(seed, sizes))))
        for rnd in range(CHECKED_ROUNDS):
            t0 = time.monotonic()
            loss, err = self._round(shown=rnd == 0, checked=True)
            self.program["loss"].append(loss)
            self.program["consensus_error"].append(err)
            if rnd == 0:
                mu = jax.tree.map(lambda x: x[0], self.state.opt_state[0].mu)
                self.program["mu_norms"] = jax.device_get(norms(mu))
                self.program["mu"] = jax.device_get(mu)  # 3.7 GB on the host
                del mu
            print(f"bench: round {rnd} {time.monotonic() - t0:.2f} s loss {loss:.4f} "
                  f"rows through held experts a step {self.counted[-1][0].sum() / recipe['h']:.0f}",
                  file=sys.stderr, flush=True)
        self.program["delta_norms"] = jax.device_get(delta(self.state.params, seeds[0]))
        self.counted = []  # the window's alone
        marks.append(("checked_rounds", time.monotonic()))
        print(
            "bench: set-up seconds "
            + " ".join(f"{b[0]}={b[1] - a[1]:.1f}" for a, b in zip(marks, marks[1:])),
            file=sys.stderr, flush=True,
        )

    def _round(self, shown: bool = False, checked: bool = False):
        """One compiled round, fenced by the fetch of its loss, its counters
        fetched with it. ``shown``: keep what the round's FIRST step shows of
        itself, out of the same compiled round that the window times: the
        experts every token chose in each expert layer, the size of each latent
        attention's output and of each stream a sub-block wrote
        (``LossAux.first_step``, worker 0's). ``checked``: keep the round's mean
        two-ahead loss."""
        import jax

        batch = next(self.feed)
        if self.shapes is None:  # what the compiled round was called with, for ``release``
            self.shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), (self.state, batch))
        self.state, metrics = self.step(self.state, batch)
        loss = float(metrics["loss"])  # the fetch is the execution fence
        self.counted.append(jax.device_get((metrics["moe_rows"], metrics["moe_absent_pairs"])))
        if checked:  # summed over the inner steps and the workers, like the counters
            self.program["mtp_loss"].append(float(metrics["mtp_loss"]) / (self.recipe["h"] * self.workers))
        if shown:
            routes, mla, streams = jax.device_get(
                (metrics["moe_chosen"][0], metrics["mla_rms"][0], metrics["mhc_stream_rms"][0]))
            rows = (self.recipe["batch"], self.recipe["seq"])
            self.program["routes"] = [r.reshape(rows + r.shape[-1:]) for r in routes]
            self.program["mla_rms"], self.program["stream_rms"] = list(mla), list(streams)
        return loss, float(metrics["consensus_error"])

    # -- the window -------------------------------------------------------

    def window(self, seconds: float, process_t0: float) -> dict:
        import jax
        import jax.numpy as jnp

        from consensusml_tpu.obs import get_registry

        self.traced = jax.profiler.TraceAnnotation.is_enabled()  # a profiler session is open
        stall = get_registry().counter("consensusml_feed_stall_seconds_total")
        stall0 = stall.value
        walls, losses, errs = [], [], []
        t0 = last = time.monotonic()
        while last - t0 < seconds:
            loss, err = self._round()
            now = time.monotonic()
            walls.append(now - last)
            losses.append(loss)
            errs.append(err)
            last = now
        elapsed = last - t0
        rounds = len(walls)
        sizes, h, rows, seq = self.sizes, self.recipe["h"], self.recipe["batch"], self.recipe["seq"]
        per_round = self.workers * h * rows * seq
        self.window_losses, self.window_errs = losses, errs
        steps = rounds * h * self.workers
        n_attn, n_exp = flops.kinds(sizes)["L"], flops.kinds(sizes)["E"]
        per_expert = np.sum([c[0] for c in self.counted], axis=0)  # (E blocks, held)
        routed = float(per_expert.sum())
        # the (group, row tile) pairs the grouped product visited, a layer and step at its round's mean load
        tile = int(self.recipe["gmm_row_tile"])
        visited = h * sum(flops.gmm_tile_pairs(layer / h, tile) for c in self.counted for layer in c[0])
        absent = float(np.sum([c[1] for c in self.counted]))
        course = [self.counted[i][0].sum() / h for i in (0, rounds // 2, -1)]
        print("bench: rows through held experts a step, the window's first, middle and last round: "
              + " ".join(f"{x:.0f}" for x in course), file=sys.stderr, flush=True)
        itemsize = jnp.dtype(self.config["compute_dtype"]).itemsize
        stats = {
            "rounds": rounds,
            "round_p50_ms": 1e3 * statistics.median(walls),
            "feed_stall_ms": 1e3 * (stall.value - stall0) / rounds,
            "model_flops": steps * flops.train_flops(sizes, rows, seq, 0.0) + flops.routed_flops(sizes, routed),
            "flash_fwd_flops": steps * n_attn * flops.attention_flops(sizes, rows, seq, False),
            "flash_fwd_bytes": steps * n_attn * flops.attention_bytes(sizes, rows, seq, itemsize, False),
            "flash_bwd_flops": steps * n_attn * flops.attention_flops(sizes, rows, seq, True),
            "flash_bwd_bytes": steps * n_attn * flops.attention_bytes(sizes, rows, seq, itemsize, True),
            "moe_gmm_flops": flops.routed_flops(sizes, routed),
            "moe_gmm_bytes": flops.routed_bytes(sizes, routed, steps * n_exp, itemsize),
            "moe_rows_per_step": routed / steps,
            "moe_absent_pairs_per_step": absent / steps,
            "moe_load_max_over_mean": float(per_expert.max() / per_expert.mean()),
            "moe_gmm_tile_fill_pct": 100.0 * routed / (visited * tile) if visited else None,
            "mhc_min_bytes": steps * flops.residual_bytes(sizes, rows * seq, itemsize),
            "final_loss": losses[-1],
        }
        self.stats = stats  # a traced run adds ``op_scopes`` after the window (``release``)
        stats["flash_flops"] = stats["flash_fwd_flops"] + stats["flash_bwd_flops"]
        stats["flash_bytes"] = stats["flash_fwd_bytes"] + stats["flash_bwd_bytes"]
        return {
            "attempted": rounds,
            "failed": sum(1 for x in losses if not np.isfinite(x)),
            "end_to_end": {
                "train_tokens_per_s": rounds * per_round / elapsed,
                "setup_s": t0 - process_t0,
            },
            "stats": stats,
        }

    # -- after the window -------------------------------------------------

    def release(self) -> None:
        """Frees the state; after a TRACED window first reads, from the compiled
        round's own text, which scope each instruction was traced in (the
        round is in the compile cache: lowered again, not compiled again)."""
        step, shapes = self.step, self.shapes
        super().release()
        if self.traced and shapes is not None and hasattr(step, "lower"):
            t0 = time.monotonic()
            self.stats["op_scopes"] = op_scopes(step.lower(*shapes).compile().as_text())
            print(f"bench: {len(self.stats['op_scopes'])} instructions under the program's scopes "
                  f"({time.monotonic() - t0:.1f} s)", file=sys.stderr, flush=True)

    def readings(self, precisions=(), faults=(), program: bool = True) -> dict:
        """Each compared number, by side: the program against the float32
        reference, then the reference in each lower precision (or with a
        fault planted) put in the program's place. ``program=False``: the
        controls alone, on a driver that was never set up (the rows come from
        the seed either way): a planted fault needs the reference and not the
        program, whose set-up is minutes and whose first moment 3.7 GB of host
        memory."""
        import jax

        if self.workers != 1:
            raise NotImplementedError("the plain reference follows one worker")
        sizes = self.sizes
        init = jax.jit(lambda s: ref.init_params(s, sizes))
        seed = self.worker_seeds()[0]
        if program:
            rows = [r[0] for r in self.checked_rows[:CHECKED_ROUNDS]]
        else:
            self.succ = schedule.successor_table(self.seed, sizes["vocab"])
            rows = [self.rows(rnd)[0] for rnd in range(CHECKED_ROUNDS)]

        def follow(precision="f32", faults=()):
            # made anew each time: the rounds update the parameters in place; the first
            # parameters are made again at the end rather than kept on the host meanwhile
            return ref_rounds.follow(init(seed), rows, sizes, self.recipe, precision, faults,
                                     start=lambda: init(seed))

        truth = follow()
        grads = _floats(truth["grad_norms"])
        keep = grads >= 1e-3 * np.median(grads)  # leaves whose gradient is nought move by round-off alone

        def compare(side: dict, log: bool = False) -> dict:
            diff = ref_train.leaf_diff_norms(side["mu"], truth["mu"])
            if log:
                norms = _floats(truth["mu_norms"])
                _log_worst_leaves("moment_diff", diff, _floats(diff) / np.maximum(norms, np.median(norms)))
                gaps = ref_train.leaf_gaps(side["delta_norms"], truth["delta_norms"])
                _log_worst_leaves("change_norm_gap", diff, np.where(keep, gaps, 0.0))
            return {
                **{f"loss_gap_round{i + 1}": abs(side["loss"][i] - truth["loss"][i])
                   for i in range(CHECKED_ROUNDS)},
                "moment_diff": ref_train.worst_leaf_share(diff, truth["mu_norms"]),
                "moment_norm_gap": ref_train.worst_leaf_gap(side["mu_norms"], truth["mu_norms"]),
                "change_norm_gap": ref_train.worst_leaf_gap(
                    side["delta_norms"], truth["delta_norms"], keep),
                "change_norm_gap_mean": ref_train.mean_leaf_gap(
                    side["delta_norms"], truth["delta_norms"], keep),
                "routing_disagreement": ref_rounds.routing_disagreement(
                    side["routes"], truth["routes"]),
                "mla_rms_gap": ref_rounds.rms_gap(side["mla_rms"], truth["mla_rms"]),
                "mhc_stream_rms_gap": ref_rounds.rms_gap(side["stream_rms"], truth["stream_rms"]),
                "mtp_loss_gap_round1": abs(side["mtp_loss"][0] - truth["mtp_loss"][0]),
            }

        out = {"program": compare(self.program, log=True)} if program else {}
        for precision in precisions:
            out[precision] = compare(follow(precision))
            _log_side(precision, out[precision])
        for fault in faults:
            _forget_compiled()
            out[fault] = compare(follow(faults=(fault,)))
            _log_side(fault, out[fault])
        self.left_out_leaves = int((~keep).sum())
        return out


def _log_side(side: str, numbers: dict) -> None:
    """A control's numbers to stderr as soon as they are read: a call that
    ends early keeps what it had."""
    import json

    print(f"bench: side {side}: {json.dumps({k: float(v) for k, v in numbers.items()})}",
          file=sys.stderr, flush=True)


def _forget_compiled() -> None:
    """A planted fault's compiled reference is used once, and compiling a
    float32 gradient of this size takes gigabytes of host memory that the
    allocator keeps: twice a call ended inside the planted faults at the
    machine's 40 GiB (PERF.md section 6, PR 33). Before each fault: drop every
    compiled program of this process and hand the freed pages back."""
    import ctypes
    import gc

    import jax

    ref_rounds._grad_fn.cache_clear()
    jax.clear_caches()
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:  # no glibc: nothing to trim
        pass


def _log_worst_leaves(number: str, tree, shares, count: int = 3) -> None:
    """Which leaves read worst on a compared number (``shares``: a value a
    leaf of ``tree``, in its order): the worst few, to stderr."""
    import jax

    paths = [jax.tree_util.keystr(path, simple=True, separator="/")
             for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    worst = ", ".join(f"{paths[i]} {shares[i]:.3f}" for i in np.argsort(-shares)[:count])
    print(f"bench: {number}, worst leaves: {worst}", file=sys.stderr, flush=True)
