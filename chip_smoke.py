#!/usr/bin/env python
"""Chip smoke: the main path, once, on the accelerator, in one process.

    python chip_smoke.py

GPT-2-medium at its real widths takes a few rounds through ``train.main``
(AdamW, CHOCO gossip over the chunked top-k + int8 Pallas codec, flash
attention; one worker per chip — simulated world-1 on one chip, ring-N
with real ``ppermute`` on N), exports the consensus-mean artifact, and
an ``Engine`` behind a ``ServeServer`` answers a handful of greedy and
sampled requests over the socket. Every phase checks its own output and
a failed check ends the run: there is no fallback and no retry.

Exits 2 before doing anything else when JAX's default backend is not a
TPU. The last stdout line is one JSON object with ``"ok"`` and the
device as JAX reports it. The phases are functions of the scale so
``tests/test_chip_smoke.py`` rehearses them at ``smoke`` scale on the CPU
mesh; this script itself is always full width.
"""

from __future__ import annotations

import gc
import json
import math
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


def device_report() -> dict:
    """What JAX found, printed before anything runs."""
    from importlib import metadata

    import jax

    from consensusml_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    versions = {
        pkg: metadata.version(pkg) for pkg in ("jax", "jaxlib", "libtpu", "flax")
    }
    source = (
        "JAX_COMPILATION_CACHE_DIR"
        if os.environ.get("JAX_COMPILATION_CACHE_DIR")
        else "checkout default"
    )
    print(f"chip_smoke: device {json.dumps(report)}", flush=True)
    print(f"chip_smoke: versions {json.dumps(versions)}", flush=True)
    print(
        f"chip_smoke: hbm limit per device, GiB {_hbm_gib('bytes_limit')}",
        flush=True,
    )
    print(f"chip_smoke: compile cache {cache_dir} ({source})", flush=True)
    return report


class CacheCounter:
    """Persistent-compile-cache hits and misses, from JAX's own events."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def take(self) -> dict:
        out = {"cache_hits": self.hits, "cache_misses": self.misses}
        self.hits = self.misses = 0
        return out


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


def _hbm_gib(key: str) -> list[float | None]:
    """One ``memory_stats`` field per device in GiB, None where the
    runtime reports none (the CPU backend)."""
    import jax

    from consensusml_tpu.obs.memviz import device_memory_stats

    out = []
    for d in jax.devices():
        value = (device_memory_stats(d) or {}).get(key)
        out.append(None if value is None else round(value / 2**30, 3))
    return out


def peak_hbm_gib() -> list[float | None]:
    """Per-device high-water mark."""
    return _hbm_gib("peak_bytes_in_use")


def train_phase(
    scale: str, device: str, workers: int, rounds: int, workdir: str
) -> dict:
    """``train.main`` for a few rounds with the compressed branch live
    from round 0, then the export. Returns the phase record; raises when
    a round is not finite or the state is not where it should be."""
    import jax

    import train
    from consensusml_tpu.obs import get_registry

    art = os.path.join(workdir, "artifact")
    metrics_path = os.path.join(workdir, "train_metrics.jsonl")
    backend = "collective" if workers > 1 else "simulated"
    argv = [
        "--config", "gpt2_topk", "--scale", scale, "--device", device,
        "--workers", str(workers), "--backend", backend,
        "--rounds", str(rounds),
        # the full recipe spends rounds 0-49 in exact warmup: switch it
        # off so the codec kernels execute, not only compile
        "--codec-warmup", "0", "--codec-refresh", "0",
        "--log-every", "1", "--metrics-out", metrics_path,
        "--export-serving", art,
    ]
    print(f"chip_smoke: train.main {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    rc = train.main(argv)
    wall = time.perf_counter() - t0
    _check(rc == 0, f"train.main returned {rc}")

    with open(metrics_path) as f:
        rows = [json.loads(line) for line in f]
    _check(len(rows) == rounds, f"{len(rows)} metric rows for {rounds} rounds")
    for r in rows:
        _check(
            math.isfinite(r["loss"]) and math.isfinite(r["consensus_error"]),
            f"round {r['round']} not finite: {r}",
        )
    if workers > 1:
        _check(
            all(r["consensus_error"] > 0 for r in rows),
            "consensus error is 0 on more than one worker: the replicas "
            "never disagreed, so they are not separate workers",
        )
    # the logger's clock starts before round 0, so differences are rounds;
    # steady is the median after round 0 (on four chips round 1 has run
    # ~6.5 s long once, cause not known — every round's seconds are kept)
    walls = [r["wall_s"] for r in rows]
    per_round = [walls[0]] + [b - a for a, b in zip(walls, walls[1:])]
    steady = statistics.median(per_round[1:]) if rounds > 1 else per_round[0]

    holders = int(get_registry().gauge("consensusml_state_devices").value)
    _check(
        holders == workers,
        f"train state sits on {holders} device(s), expected {workers}",
    )
    peaks = peak_hbm_gib()
    if workers > 1 and all(p is not None for p in peaks):
        # "everything on the first chip" cannot pass: every chip's
        # high-water mark has to be of the order of the busiest one's
        _check(
            min(peaks[:workers]) > 0.5 * max(peaks[:workers]),
            f"per-device peak HBM is lopsided: {peaks}",
        )
    _check(
        os.path.exists(os.path.join(art, "serve_meta.json")),
        "export wrote no serve_meta.json",
    )
    gc.collect()  # the train state must be gone before the engine loads
    return {
        "workers": workers,
        "backend": backend,
        "rounds": rounds,
        "losses": [round(r["loss"], 4) for r in rows],
        "consensus_errors": [round(r["consensus_error"], 4) for r in rows],
        "round_s": [round(t, 3) for t in per_round],
        "steady_round_s": round(steady, 3),
        "compile_s": round(max(per_round[0] - steady, 0.0), 2),
        "wall_s": round(wall, 2),
        "state_devices": holders,
        "peak_hbm_gib": peaks,
        "artifact": art,
    }


def serve_phase(
    art: str,
    *,
    num_slots: int,
    max_len: int,
    max_new_tokens: int,
    n_requests: int,
    prompt_lens: tuple[int, int],
) -> dict:
    """``load_engine`` -> ``ServeServer`` -> mixed greedy/sampled traffic
    over the socket. Raises on any error, short stream, nondeterministic
    greedy pair or post-warmup compile."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import loadgen

    from consensusml_tpu.serve import ServeConfig, load_engine
    from consensusml_tpu.serve.server import ServeServer

    t0 = time.perf_counter()
    engine = load_engine(
        art,
        ServeConfig(
            num_slots=num_slots, max_len=max_len,
            max_new_tokens=max_new_tokens,
        ),
    )
    server = ServeServer(engine)
    try:
        load_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        counts = engine.warmup()
        warmup_s = time.perf_counter() - t0
        print(
            f"chip_smoke: engine kv_impl={engine.config.kv_impl} "
            f"attn_impl={engine.attn_impl} buckets={list(engine.buckets)} "
            f"compile_counts={counts}",
            flush=True,
        )
        host, port = server.address
        submit = loadgen._socket_submit(host, port)
        vocab = engine._dm.vocab_size
        t0 = time.perf_counter()
        reports = {}
        for name, temperature, top_p, seed in [
            ("greedy", 0.0, 1.0, 1),
            ("sampled", 0.8, 0.9, 2),
        ]:
            rep = loadgen.run_loadgen(
                submit,
                n_requests=n_requests,
                rate_rps=8.0,
                prompt_lens=prompt_lens,
                vocab=vocab,
                max_new_tokens=max_new_tokens,
                seed=seed,
                temperature=temperature,
                top_p=top_p,
            )
            _check(rep["errors"] == 0, f"{name}: {rep['error_sample']}")
            _check(
                rep["completed"] == n_requests
                and rep["tokens_out"] == n_requests * max_new_tokens,
                f"{name}: {rep['completed']}/{n_requests} streams, "
                f"{rep['tokens_out']} tokens for "
                f"{n_requests * max_new_tokens} asked",
            )
            reports[name] = {
                k: rep[k]
                for k in ("completed", "tokens_out", "ttft_p50_ms",
                          "latency_p50_ms", "wall_s")
            }
        # two identical greedy submissions, one after the other
        ids = [(7 * i + 3) % (vocab - 1) for i in range(prompt_lens[0] + 5)]
        greedy = {"temperature": 0.0, "top_p": 1.0, "seed": 0}
        first = submit(ids, max_new_tokens, None, greedy)["tokens"]
        second = submit(ids, max_new_tokens, None, greedy)["tokens"]
        _check(
            len(first) == max_new_tokens and first == second,
            f"greedy replay differs: {first} vs {second}",
        )
        _check(
            all(0 <= t < vocab for t in first), f"token out of range: {first}"
        )
        steady_s = time.perf_counter() - t0
        after = engine.compile_counts()
        _check(
            after == counts,
            f"compiled after warmup: {counts} -> {after}",
        )
        stats = engine.stats()
    finally:
        server.shutdown()
    return {
        "attn_impl": engine.attn_impl,
        "compile_counts": counts,
        "load_s": round(load_s, 2),
        "warmup_compile_s": round(warmup_s, 2),
        "steady_s": round(steady_s, 2),
        "requests": reports,
        "greedy_replay": first,
        "decode_steps": stats.get("decode_steps"),
        "peak_hbm_gib": peak_hbm_gib(),
    }


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print(
            f"chip_smoke: default backend is {jax.default_backend()!r}, not "
            "'tpu' — this script only runs on the accelerator",
            file=sys.stderr,
        )
        return 2
    device = device_report()
    cache = CacheCounter()
    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        train_rec = train_phase(
            "full", "tpu", workers=device["count"], rounds=4, workdir=workdir
        )
        train_rec.update(cache.take())
        print(f"chip_smoke: train {json.dumps(train_rec)}", flush=True)
        serve_rec = serve_phase(
            train_rec["artifact"],
            num_slots=8,
            max_len=1024,
            max_new_tokens=8,
            n_requests=6,
            prompt_lens=(4, 200),
        )
        serve_rec.update(cache.take())
        print(f"chip_smoke: serve {json.dumps(serve_rec)}", flush=True)
    record = {
        "device": device,
        "train": {k: v for k, v in train_rec.items() if k != "artifact"},
        "serve": serve_rec,
        "total_s": round(time.perf_counter() - t_start, 1),
    }
    out_dir = os.path.join(ROOT, "chiprun_out")
    if os.path.isdir(out_dir):
        with open(os.path.join(out_dir, "chip_smoke.json"), "a") as f:
            f.write(json.dumps(record) + "\n")
    print(f"chip_smoke: total {record['total_s']} s", flush=True)
    print(final_line(device), flush=True)
    return 0


def final_line(device: dict) -> str:
    """The last stdout line: ``ok`` and the device, nothing else — the
    phase records are printed on their own lines above it."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


if __name__ == "__main__":
    sys.exit(main())
