"""Training driver for the ``qwen3_next`` configurations: local-SGD rounds under
exact gossip, one chip's share of an expert-parallel deployment.

``drivers/train_nemotron_h.py``'s driver with what names the model replaced:
the shipped recipe's decoder by the pattern ``GEGEGEAE`` (Gated DeltaNet, gated
attention, an expert layer after each), ``reference/qwen3_next.py`` and
``reference/train_qwen3_next.py`` in place of the hybrid's reference, the
size of every delta rule's output (``gdn_rms``) out of round 1's own metrics
where the hybrid's driver reads its scans', three matrices an expert, and how
full the grouped product's row tiles were over the window
(``moe_gmm_tile_fill_pct``). The round loop, the feed, the rows and the judging
are the base driver's.

``setup`` imports the program's new modules FIRST: on a tree without them the
run ends at once with exit code 3 (``run.py``: the program is not in this
checkout) instead of failing somewhere inside a compile.
"""

from __future__ import annotations

import dataclasses
import statistics
import sys
import time

import numpy as np

import flops_qwen3_next as flops
import schedule
from drivers.train import CHECKED_ROUNDS, _floats
from drivers.train import Driver as TrainDriver
from reference import qwen3_next as ref
from reference import train as ref_train
from reference import train_qwen3_next as ref_rounds


def program_sizes(mc) -> dict:
    """The reference's ``sizes`` as the program's model config states them
    (what ``reference.qwen3_next.sizes_of`` reads from a configuration file)."""
    layers = len(mc.pattern) // 2
    interval = mc.pattern[::2].index("A") + 1 if "A" in mc.pattern else layers + 1
    sizes = {
        "vocab": mc.vocab_size, "hidden": mc.hidden, "layers": layers, "interval": interval,
        "key_heads": mc.gdn_key_heads, "value_heads": mc.gdn_value_heads,
        "key_dim": mc.gdn_key_dim, "value_dim": mc.gdn_value_dim, "conv": mc.conv_kernel,
        "chunk": mc.gdn_chunk, "dt_min": mc.dt_min, "dt_max": mc.dt_max, "dt_floor": mc.dt_floor,
        "heads": mc.heads, "kv_heads": mc.kv_heads, "head_dim": mc.head_dim,
        "rotary_dim": mc.rotary_dim, "rope_theta": mc.rope_theta, "experts": mc.experts,
        "held": mc.held, "held_start": mc.held_start, "top_k": mc.top_k,
        "expert_width": mc.expert_width, "shared_width": mc.shared_width,
        "score_correction": mc.score_correction, "eps": mc.norm_eps,
    }
    described = (mc.moe_scores, mc.moe_activation, mc.shared_gate, mc.zero_centred_norm, mc.route_scale)
    if mc.pattern != ref.pattern_of(sizes) or described != ("softmax", "swiglu", True, True, 1.0):
        raise RuntimeError(f"the recipe's decoder is not the reference's: {mc.pattern} {described}")
    return sizes


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

    # -- set-up -----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        try:  # first: a tree without the delta rule or the share's decoder ends here, at once
            from consensusml_tpu.models import gated_delta  # noqa: F401
            from consensusml_tpu.models.nemotron_h import (
                NemotronHLM, nemotron_h_loss_fn, qwen3_next_share)  # noqa: F401
        except ImportError as e:
            print(f"bench: the program has no Gated DeltaNet decoder ({e}): no result",
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
        self.program = {"loss": [], "consensus_error": []}
        self.feed = iter(prefetch_to_device(
            self._source(), 2, placement=batch_placement("simulated")
        ))
        norms = jax.jit(ref_train.leaf_norms)
        # the first parameters are made again from the seed rather than kept
        delta = jax.jit(lambda now, seed: ref_train.leaf_norms(
            jax.tree.map(lambda a, b: a[0] - b, now, ref.init_params(seed, sizes))))
        for rnd in range(CHECKED_ROUNDS):
            t0 = time.monotonic()
            loss, err = self._round(shown=rnd == 0)
            self.program["loss"].append(loss)
            self.program["consensus_error"].append(err)
            if rnd == 0:
                mu = jax.tree.map(lambda x: x[0], self.state.opt_state[0].mu)
                self.program["mu_norms"] = jax.device_get(norms(mu))
                self.program["mu"] = jax.device_get(mu)  # 2.7 GB on the host
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

    def _round(self, shown: bool = False):
        """One compiled round, fenced by the fetch of its loss, its counters
        fetched with it. ``shown``: keep what the round's FIRST step shows of
        itself, out of the same compiled round that the window times: the
        experts every token chose in each expert layer and the size of each
        delta rule's output (``LossAux.first_step``, worker 0's)."""
        import jax

        batch = next(self.feed)
        self.state, metrics = self.step(self.state, batch)
        loss = float(metrics["loss"])  # the fetch is the execution fence
        self.counted.append(jax.device_get((metrics["moe_rows"], metrics["moe_absent_pairs"])))
        if shown:
            routes, rms = jax.device_get((metrics["moe_chosen"][0], metrics["gdn_rms"][0]))
            rows = (self.recipe["batch"], self.recipe["seq"])
            self.program["routes"] = [r.reshape(rows + r.shape[-1:]) for r in routes]
            self.program["gdn_rms"] = list(rms)
        return loss, float(metrics["consensus_error"])

    # -- the window -------------------------------------------------------

    def window(self, seconds: float, process_t0: float) -> dict:
        import jax.numpy as jnp

        from consensusml_tpu.obs import get_registry

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
        n_attn, n_exp = flops.kinds(sizes)["A"], flops.kinds(sizes)["E"]
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
            "gdn_scan_flops": steps * flops.kinds(sizes)["G"] * flops.gdn_scan_flops(sizes, rows * seq),
            "gdn_scan_bytes": steps * flops.kinds(sizes)["G"] * flops.gdn_scan_bytes(sizes, rows * seq, itemsize),
            "final_loss": losses[-1],
        }
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

    def readings(self, precisions=(), faults=()) -> dict:
        """Each compared number, by side: the program against the float32
        reference, then the reference in each lower precision (or with a
        fault planted) put in the program's place."""
        import jax

        if self.workers != 1:
            raise NotImplementedError("the plain reference follows one worker")
        sizes = self.sizes
        init = jax.jit(lambda s: ref.init_params(s, sizes))
        seed = self.worker_seeds()[0]
        rows = [r[0] for r in self.checked_rows[:CHECKED_ROUNDS]]

        def follow(precision="f32", faults=()):
            # made anew each time: the rounds update the parameters in place
            return ref_rounds.follow(init(seed), rows, sizes, self.recipe, precision, faults)

        truth = follow()
        grads = _floats(truth["grad_norms"])
        keep = grads >= 1e-3 * np.median(grads)  # leaves whose gradient is nought move by round-off alone

        def compare(side: dict, log: bool = False) -> dict:
            diff = ref_train.leaf_diff_norms(side["mu"], truth["mu"])
            if log:
                _log_worst_leaves(diff, truth["mu_norms"])
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
                "gdn_rms_gap": ref_rounds.gdn_rms_gap(side["gdn_rms"], truth["gdn_rms"]),
            }

        out = {"program": compare(self.program, log=True)}
        for precision in precisions:
            out[precision] = compare(follow(precision))
        for fault in faults:
            out[fault] = compare(follow(faults=(fault,)))
        self.left_out_leaves = int((~keep).sum())
        return out


def _log_worst_leaves(diff_norms, reference_norms, count: int = 3) -> None:
    """Which leaves of the first moment lie furthest from the reference's: the
    worst few, by the measure ``moment_diff`` takes, to stderr."""
    import jax

    norms = _floats(reference_norms)
    shares = _floats(diff_norms) / np.maximum(norms, np.median(norms))
    paths = [jax.tree_util.keystr(path, simple=True, separator="/")
             for path, _ in jax.tree_util.tree_flatten_with_path(diff_norms)[0]]
    worst = ", ".join(f"{paths[i]} {shares[i]:.3f}" for i in np.argsort(-shares)[:count])
    print(f"bench: moment_diff, worst leaves: {worst}", file=sys.stderr, flush=True)
