"""GPT-2 batch/remat MFU sweep (docs/perf.md; VERDICT r2 item 3).

The r2 claim "no step-time lever left at this workload shape" was only
measured at batch 4 — but batch is itself the lever: optimizer cost and
reductions amortize over more tokens. This sweeps batch x remat on the
real chip and reports tokens/s and MFU so the claim either gains data or
the headline rises. Each variant runs in a fresh subprocess (clean XLA
client, honest compile; OOM in one variant cannot poison the next).

MFU = model FLOPs / wall / peak. Model FLOPs per token = 6*N_base (N
excluding the untied position table... we use 6*N_params, the standard
PaLM convention) + 12*L*H*S (attention scores+values, causal halved),
peak = 197 TFLOP/s bf16 (TPU v5e chip).

Usage: python tools/lm_sweep.py [--batches 4,8,16] [--remat auto]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _cast_state_adamw(lr, dtype):
    """AdamW whose mu/nu live in ``dtype`` (bf16 halves the optimizer
    state's HBM traffic — the measured ~12 ms/step 4xf32 pass,
    docs/perf.md). The update upcasts to f32, computes, downcasts; XLA
    fuses the casts into the elementwise update so the only change is
    wire format. bf16 keeps f32's exponent range, so nu (squared grads)
    cannot overflow; the mantissa loss shows up (or doesn't) in the
    sweep's loss column."""
    import jax
    import jax.numpy as jnp
    import optax

    inner = optax.adamw(lr)

    def down(x):
        if hasattr(x, "dtype") and x.dtype == jnp.float32 and getattr(x, "ndim", 0) > 0:
            return x.astype(dtype)
        return x

    def up(x):
        if hasattr(x, "dtype") and x.dtype == dtype:
            return x.astype(jnp.float32)
        return x

    def init(params):
        return jax.tree.map(down, inner.init(params))

    def update(grads, state, params=None):
        updates, new_state = inner.update(
            grads, jax.tree.map(up, state), params
        )
        return updates, jax.tree.map(down, new_state)

    return optax.GradientTransformation(init, update)


def run_variant(batch: int, remat: bool, steps: int, opt: str = "f32",
                norm: str = "flax", loss: str = "dense") -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM, gpt2_loss_fn

    if loss not in ("dense", "chunked"):
        raise ValueError(f"unknown loss impl {loss!r} (dense, chunked)")
    cfg = GPT2Config(remat=remat, norm_impl=norm,
                     loss_vocab_chunk=8192 if loss == "chunked" else 0)
    model = GPT2LM(config=cfg)
    s = 1024
    rng = np.random.default_rng(0)
    batch_data = {
        "input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(batch, s)), jnp.int32
        )
    }
    loss_fn = gpt2_loss_fn(model)
    tx = (
        _cast_state_adamw(2e-4, jnp.bfloat16)
        if opt == "bf16"
        else optax.adamw(2e-4)
    )
    params = model.init(jax.random.key(0), batch_data["input_ids"][:1])["params"]
    n_params = sum(x.size for x in jax.tree.leaves(params))
    carry0 = (params, tx.init(params), jax.random.key(1))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def multi(carry):
        def body(c, _):
            params, opt_state, key = c
            key, sub = jax.random.split(key)
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, {}, batch_data, sub
            )
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state, key), loss

        return jax.lax.scan(body, carry, None, length=steps)

    carry, losses = multi(carry0)
    float(losses[-1])  # compile + first run fence
    t0 = time.time()
    carry, losses = multi(carry)
    final = float(losses[-1])
    dt = time.time() - t0
    tokens_sec = batch * s * steps / dt
    # 6*N per token (fwd+bwd) + attention: 12*L*H*S covers fwd+bwd of the
    # QK^T and PV matmuls already (4*S*H fwd per layer x3), causal halved
    attn = 12 * cfg.layers * cfg.hidden * s // 2
    flops_tok = 6 * n_params + attn
    # the one peaks table, keyed by device_kind: an unknown device is an
    # error, not an MFU against some other chip's roofline
    from consensusml_tpu.obs.costs import device_peaks

    mfu = tokens_sec * flops_tok / device_peaks(jax.devices()[0].device_kind)[0]
    out = {
        "batch": batch,
        "remat": remat,
        "opt_state": opt,
        "norm": norm,
        "loss_impl": loss,
        "tokens_sec": round(tokens_sec, 1),
        "step_ms": round(1000 * dt / steps, 2),
        "mfu": round(mfu, 4),
        "loss": round(final, 3),
    }
    # runtime peak where the backend exposes it (libtpu does; the CPU
    # backend does not — never report a fake 0.0)
    stats = jax.local_devices()[0].memory_stats() or {}
    if stats.get("peak_bytes_in_use"):
        out["peak_hbm_gib"] = round(stats["peak_bytes_in_use"] / 1024**3, 2)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="4,8,16")
    ap.add_argument(
        "--remat",
        default="auto",
        choices=("auto", "on", "off", "both"),
        help="auto: off for small batches, on past 8 (the HBM bound)",
    )
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--opts", default="f32",
                    help="comma list of optimizer-state dtypes to sweep "
                         "(f32, bf16) — bf16 mu/nu halves optimizer HBM "
                         "traffic (VERDICT r3 item 9 lever)")
    ap.add_argument("--norms", default="flax",
                    help="comma list of LN impls to sweep (flax, pallas) "
                         "— the fused-LN kernel (models/fused_ln.py, "
                         "VERDICT r4 item 5b lever)")
    ap.add_argument("--losses", default="dense",
                    help="comma list of LM-head loss impls to sweep "
                         "(dense, chunked) — chunked never materializes "
                         "the (B,S,V) logits (losses.chunked_vocab_lm_loss)")
    args = ap.parse_args()

    variants = []
    for b in (int(x) for x in args.batches.split(",")):
        for opt in args.opts.split(","):
            for norm in args.norms.split(","):
                for lo in args.losses.split(","):
                    if args.remat == "both":
                        variants += [
                            (b, False, opt, norm, lo), (b, True, opt, norm, lo)
                        ]
                    elif args.remat == "auto":
                        variants.append((b, b > 8, opt, norm, lo))
                    else:
                        variants.append((b, args.remat == "on", opt, norm, lo))

    rows = []
    for batch, remat, opt, norm, lo in variants:
        env = dict(os.environ)
        env["LM_SWEEP_ONE"] = json.dumps(
            {"batch": batch, "remat": remat, "steps": args.steps, "opt": opt,
             "norm": norm, "loss": lo}
        )
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--_worker"],
            capture_output=True, text=True, timeout=1800, env=env,
            cwd=REPO,
        )
        got = None
        for line in proc.stdout.splitlines():
            if line.startswith("ONE_RESULT "):
                got = json.loads(line[len("ONE_RESULT "):])
        if got is None:
            got = {
                "batch": batch,
                "remat": remat,
                "opt_state": opt,
                "norm": norm,
                "loss_impl": lo,
                "error": (proc.stderr or proc.stdout)[-400:],
            }
        rows.append(got)
        print(f"# {json.dumps(got)}", file=sys.stderr, flush=True)
    print(json.dumps(rows, indent=2))


if __name__ == "__main__":
    if "--_worker" in sys.argv:
        spec = json.loads(os.environ["LM_SWEEP_ONE"])
        print(
            "ONE_RESULT "
            + json.dumps(
                run_variant(
                    spec["batch"],
                    spec["remat"],
                    spec["steps"],
                    spec.get("opt", "f32"),
                    spec.get("norm", "flax"),
                    spec.get("loss", "dense"),
                )
            ),
            flush=True,
        )
    else:
        main()
