"""On-chip codec stage/parameter sweep (docs/perf.md's codec section).

Times each STAGE of a compressed-gossip round at full-model scale (one
355M-element vector ~= GPT-2-medium flattened) and sweeps the top-k
kernel's (chunk, k) and implementation space — the data behind:

- why a full CHOCO round costs what it costs (which stage dominates),
- the chunk/k quality-vs-cost frontier at fixed sparsity ratio,
- the large-k story (VERDICT r2 item 7): Pallas k-extraction vs the
  XLA lax.top_k fallback as k grows.

Usage: python tools/codec_sweep.py [--elems 354823168] [--reps 5]
Timing fence: host value fetch (an execution barrier on every backend).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _time(fn, *args, reps=5):
    import jax
    import jax.numpy as jnp

    fence = lambda out: float(jnp.ravel(jax.tree.leaves(out)[0])[0])
    fence(fn(*args))  # compile + first-run fence
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    fence(out)
    return 1000 * (time.time() - t0) / reps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--elems", type=int, default=354_823_168)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from consensusml_tpu.compress import kernels

    n = args.elems
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    rows = {"elems": n, "platform": jax.default_backend(), "stage_ms": {}}
    stage = rows["stage_ms"]

    # --- stages of one fused round at the shipped (512, 8) ---------------
    chunks = x.reshape(-1, 512)
    topk = jax.jit(lambda c: kernels.chunked_topk(c, 8))
    stage["topk_512_8_pallas"] = _time(topk, chunks, reps=args.reps)
    vals, lidx = topk(chunks)
    stage["int8_quant_on_winners"] = _time(
        jax.jit(
            # truncate to a 128-multiple: timing only, parity irrelevant
            lambda v: kernels.quantize_int8(
                v.reshape(-1)[: v.size // 128 * 128].reshape(-1, 128)
            )
        ),
        vals,
        reps=args.reps,
    )
    gidx = (
        lidx + (jnp.arange(chunks.shape[0], dtype=jnp.int32) * 512)[:, None]
    ).reshape(-1)
    flatv = vals.reshape(-1)
    stage["scatter_add_decompress"] = _time(
        jax.jit(lambda g, v: jnp.zeros((n,), jnp.float32).at[g].add(v)),
        gidx,
        flatv,
        reps=args.reps,
    )
    parts = [n // 3, n // 3, n - 2 * (n // 3)]
    pieces = list(jnp.split(x, np.cumsum(parts)[:-1]))
    stage["concat_3_pieces"] = _time(
        jax.jit(lambda *p: jnp.concatenate(p)), *pieces, reps=args.reps
    )
    stage["elementwise_axpy"] = _time(
        jax.jit(lambda a, b: a + 0.5 * b), x, x, reps=args.reps
    )

    # --- (chunk, k) frontier at the same 1/64 ratio ----------------------
    rows["ratio_frontier_ms"] = {}
    for chunk, k in ((128, 2), (256, 4), (512, 8), (1024, 16)):
        c = x[: n // chunk * chunk].reshape(-1, chunk)
        rows["ratio_frontier_ms"][f"pallas_{chunk}_{k}"] = _time(
            jax.jit(lambda c, k=k: kernels.chunked_topk(c, k)), c,
            reps=args.reps,
        )

    # --- large-k: pallas extraction vs lax.top_k (VERDICT item 7) --------
    rows["large_k_ms"] = {}
    m = n // 8 // 512 * 512  # keep the sweep affordable; 512-aligned
    small = x[:m].reshape(-1, 512)
    for k in (8, 32, 64, 128):
        rows["large_k_ms"][f"pallas_512_{k}"] = _time(
            jax.jit(lambda c, k=k: kernels.chunked_topk(c, k)), small,
            reps=args.reps,
        )
        rows["large_k_ms"][f"laxtopk_512_{k}"] = _time(
            jax.jit(lambda c, k=k: jax.lax.top_k(jnp.abs(c), k)), small,
            reps=args.reps,
        )

    for key in ("stage_ms", "ratio_frontier_ms", "large_k_ms"):
        rows[key] = {k: round(v, 2) for k, v in rows[key].items()}
    print(json.dumps(rows, indent=2))


if __name__ == "__main__":
    main()
