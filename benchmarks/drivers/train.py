"""Training driver: local-SGD rounds under CHOCO gossip, workers stacked on a chip.

Builds what ``train.main`` builds for ``--backend simulated`` — the shipped
recipe from ``configs.build``, ``make_simulated_train_step``, the stacked
state, the device prefetcher — with the codec live from round 0, dropout at the
configuration's value and the weights and token rows made by the benchmark from
the seed. It holds the state itself, because ``correct`` compares the optimizer's first
moment and the parameters' change against the plain reference and
``train.main`` takes no state in and hands none out (PERF.md section 7).

Set-up drives that one object (compiled step + state) through the rounds the
reference follows and hands the same object to the window. The window runs
whole rounds until ``--seconds`` are spent; every round ends at the fetch of
its loss, which is the execution fence ``train.py`` uses.
"""

from __future__ import annotations

import dataclasses
import gc
import statistics
import sys
import time

import numpy as np

import flops
import schedule
from reference import gpt2 as ref
from reference import train as ref_train

CHECKED_ROUNDS = 3


class Driver:
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

    # -- what the program is given ---------------------------------------

    def worker_seeds(self):
        """One weight seed per worker, by plain JAX from the run's seed."""
        import jax

        keys = jax.random.split(jax.random.key(self.seed & 0x7FFFFFFF), self.workers)
        return [np.uint32(jax.random.bits(k, dtype=np.uint32)) for k in keys]

    def rows(self, rnd: int) -> np.ndarray:
        shape = (self.workers, self.recipe["h"], self.recipe["batch"], self.recipe["seq"])
        return schedule.train_round(self.seed, self.succ, rnd, shape)

    def _source(self):
        rnd = 0
        while True:
            rows = self.rows(rnd)
            if rnd < CHECKED_ROUNDS:
                self.checked_rows.append(rows)
            yield {"input_ids": rows}
            rnd += 1

    def build_step(self, cfg, loss_fn):
        """The compiled round (a seam for the tests' planted faults)."""
        from consensusml_tpu.train import make_simulated_train_step

        return make_simulated_train_step(cfg, loss_fn)

    # -- set-up -----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        import jax
        import jax.numpy as jnp

        from consensusml_tpu import configs
        from consensusml_tpu.data.prefetch import prefetch_to_device
        from consensusml_tpu.models.gpt2 import GPT2LM, gpt2_loss_fn
        from consensusml_tpu.train import batch_placement
        from consensusml_tpu.train.local_sgd import TrainState

        recipe, sizes = self.recipe, self.sizes
        marks = [("process", self.cell.get("process_t0", time.monotonic())), ("start", time.monotonic())]
        if self.traffic.get("backend", "simulated") != "simulated":
            raise NotImplementedError("this driver stacks workers on one chip (simulated)")
        bundle = configs.build(recipe["recipe"], recipe["scale"], world=self.workers)
        mc = bundle.model.config
        ran = {"vocab": mc.vocab_size, "hidden": mc.hidden, "layers": mc.layers,
               "heads": mc.heads, "positions": mc.max_len, "mlp": mc.mlp_dim}
        stated = {k: sizes[k] for k in ran}
        if ran != stated or bundle.cfg.h != recipe["h"] or bundle.base_lr != recipe["learning_rate"]:
            raise RuntimeError(f"the recipe runs {ran}, the configuration states {stated}")
        drops = {float(self.config[k]) for k in ("resid_pdrop", "embd_pdrop", "attn_pdrop")}
        if len(drops) != 1:
            raise RuntimeError(f"the program has one dropout rate, the configuration states {drops}")
        model = GPT2LM(config=dataclasses.replace(
            mc, dropout=drops.pop(), dtype=jnp.dtype(self.config["compute_dtype"])
        ))
        cfg = dataclasses.replace(
            bundle.cfg,
            gossip=dataclasses.replace(
                bundle.cfg.gossip,
                codec_warmup_rounds=int(recipe["codec_warmup_rounds"]),
                codec_refresh_every=int(recipe["codec_refresh_every"]),
            ),
        )
        self.step = self.build_step(cfg, gpt2_loss_fn(model))
        seeds = jnp.asarray(self.worker_seeds())
        marks.append(("build", time.monotonic()))

        # the seeds are arguments, not constants of the program: one compiled
        # initialiser serves every seed from the cache
        def init(seeds, run_seed):
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
        self.feed = iter(prefetch_to_device(
            self._source(), 2, placement=batch_placement("simulated")
        ))
        norms = jax.jit(ref_train.leaf_norms_split)
        # the first parameters are made again from the seed rather than kept: the
        # state and the step's workspace leave no room for a copy (7 + 7.9 GB of 15.75)
        delta = jax.jit(lambda now, seed: ref_train.leaf_norms_split(
            jax.tree.map(lambda a, b: a[0] - b, now, ref.init_params(seed, sizes))))
        track = jax.jit(lambda xs: jnp.sqrt(sum(jnp.sum(jnp.square(x), axis=-1) for x in jax.tree.leaves(xs))))
        self.program = {"loss": [], "consensus_error": []}
        for rnd in range(CHECKED_ROUNDS):
            t0 = time.monotonic()
            loss, err = self._round()
            self.program["loss"].append(loss)
            self.program["consensus_error"].append(err)
            if rnd == 0:
                mu = jax.tree.map(lambda x: x[0], self.state.opt_state[0].mu)
                self.program["mu_norms"] = jax.device_get(norms(mu))
                # the moment itself goes to the host (1.4 GB): its difference from the
                # reference's is first order in a rounding error, a gap of norms second
                self.program["mu"] = jax.device_get(mu)
                del mu
                self.program["track_norm"] = float(np.asarray(track(self.state.gossip.xhat))[0])
            print(f"bench: round {rnd} {time.monotonic() - t0:.2f} s loss {loss:.4f}",
                  file=sys.stderr, flush=True)
        self.program["delta_norms"] = jax.device_get(delta(self.state.params, seeds[0]))
        marks.append(("checked_rounds", time.monotonic()))
        print(
            "bench: set-up seconds "
            + " ".join(f"{b[0]}={b[1] - a[1]:.1f}" for a, b in zip(marks, marks[1:])),
            file=sys.stderr, flush=True,
        )

    def _round(self):
        batch = next(self.feed)
        self.state, metrics = self.step(self.state, batch)
        loss = float(metrics["loss"])  # the fetch is the execution fence
        return loss, float(metrics["consensus_error"])

    # -- the window -------------------------------------------------------

    def window(self, seconds: float, process_t0: float) -> dict:
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
        per_round = self.workers * self.recipe["h"] * self.recipe["batch"] * self.recipe["seq"]
        self.window_losses, self.window_errs = losses, errs
        n_params = sum(int(np.prod(x.shape[1:])) for x in _leaves(self.state.params))
        steps = rounds * self.recipe["h"]
        layers, rows, seq = self.sizes["layers"], self.recipe["batch"], self.recipe["seq"]
        import jax.numpy as jnp

        itemsize = jnp.dtype(self.config["compute_dtype"]).itemsize  # attention's operands
        stats = {
            "rounds": rounds,
            "round_p50_ms": 1e3 * statistics.median(walls),
            "feed_stall_ms": 1e3 * (stall.value - stall0) / rounds,
            "model_flops": rounds * per_round * flops.train_flops_per_token(self.sizes, seq),
            "flash_fwd_flops": steps * layers * flops.attention_flops(self.sizes, rows, seq, False),
            "flash_fwd_bytes": steps * layers * flops.attention_bytes(self.sizes, rows, seq, itemsize, False),
            "flash_bwd_flops": steps * layers * flops.attention_flops(self.sizes, rows, seq, True),
            "flash_bwd_bytes": steps * layers * flops.attention_bytes(self.sizes, rows, seq, itemsize, True),
            "codec_bytes": rounds * flops.codec_bytes(n_params, self.recipe["codec_chunk"], self.recipe["codec_k"]),
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

    def release(self) -> None:
        close = getattr(self.feed, "close", None)
        if close is not None:
            close()
        self.state = self.step = self.feed = None
        gc.collect()

    def close(self) -> None:
        self.release()

    def readings(self, precisions=(), faults=()) -> dict:
        """Each compared number, by side: the program against the float32
        reference, then the reference computed in each lower precision (or
        with a fault planted) put in the program's place."""
        import jax

        if self.workers != 1:
            raise NotImplementedError("the plain reference follows one worker")
        sizes = self.sizes
        params0 = jax.jit(lambda s: ref.init_params(s, sizes))(self.worker_seeds()[0])
        rows = [r[0] for r in self.checked_rows[:CHECKED_ROUNDS]]
        block = int(self.traffic["check"].get("rows_per_block", 2))

        def follow(precision="f32", faults=()):
            return ref_train.follow(params0, rows, sizes, self.recipe, precision, block, faults=faults)

        truth = follow(faults=tuple(f for f in faults if f == "codec_int4"))
        # leaves whose gradient is nought to rounding move by round-off alone
        grads = _floats(truth["grad_norms"])
        keep = grads >= 1e-3 * np.median(grads)

        def compare(side: dict) -> dict:
            diff = ref_train.leaf_diff_norms(side["mu"], truth["mu"])
            return {
                **{f"loss_gap_round{i + 1}": abs(side["loss"][i] - truth["loss"][i])
                   for i in range(CHECKED_ROUNDS)},
                "moment_diff": ref_train.worst_leaf_share(diff, truth["mu_norms"]),
                "moment_norm_gap": ref_train.worst_leaf_gap(side["mu_norms"], truth["mu_norms"]),
                "change_norm_gap": ref_train.worst_leaf_gap(
                    side["delta_norms"], truth["delta_norms"], keep),
                "change_norm_gap_mean": ref_train.mean_leaf_gap(
                    side["delta_norms"], truth["delta_norms"], keep),
                "track_norm_gap": abs(side["track_norm"] - truth["track_norm"]) / truth["track_norm"],
            }

        out = {"program": compare(self.program)}
        for precision in precisions:
            out[precision] = compare(follow(precision))
        for fault in faults:
            if fault == "codec_int4":  # int4 where the recipe states int8: the tracked norm alone differs
                out[fault] = compare(dict(truth, track_norm=truth["track_norm_int4"]))
            else:
                out[fault] = compare(follow(faults=(fault,)))
        self.left_out_leaves = int((~keep).sum())
        return out

    def judge(self, read: dict) -> list:
        """One side's numbers, each beside its limit."""
        limits = self.traffic["check"]
        return [_check(name, read[name], float(limits[name])) for name in sorted(read) if name in limits]

    def check(self) -> list:
        checks = self.judge(self.readings()["program"])
        finite = all(np.isfinite(x) for x in self.window_losses + self.window_errs)
        differ = self.workers == 1 or all(e > 0 for e in self.window_errs)
        checks.append(_check("nonfinite_or_collapsed_rounds", int(not (finite and differ)), 0))
        return checks


def _leaves(tree):
    import jax

    return jax.tree.leaves(tree)


def _floats(tree) -> np.ndarray:
    return np.asarray([float(x) for x in _leaves(tree)])


def _check(name: str, value, limit) -> dict:
    return {"name": name, "value": value, "limit": limit, "ok": bool(value <= limit)}
