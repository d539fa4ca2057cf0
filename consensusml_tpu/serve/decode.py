"""KV-cache decode: bucketed prefill, slot insertion, fused decode step.

The compiled-program contract that makes serving cheap is the same one
training lives by (docs/static_analysis.md): FIXED shapes everywhere.
The engine runs exactly three jitted program families and nothing else:

- ``prefill(params, cache, ids (1, L), length, slot)`` — full causal
  forward over one padded prompt bucket ``L``, per-layer K/V written
  into cache row ``slot`` via ``dynamic_update_slice``, argmax of the
  last REAL token's logits as the first generated token. One compile per
  prompt-length bucket (warmup); the bucket set is static.
- ``decode(params, cache, tokens (S,), positions (S,))`` — one token for
  ALL ``S`` slots at once, each slot writing/attending at its own
  position (:func:`models.attention.update_kv_cache` /
  :func:`~consensusml_tpu.models.attention.cached_attention`). Slot fill
  level is DATA (the lengths vector), never shape, so every decode step
  of every mix of in-flight requests reuses one executable — the
  zero-recompile contract cml-check's decode jaxpr pass pins.
- ``score(params, ids (B, S))`` — the prefill-only batch scoring path:
  literally the eval forward, which is what makes the export→serve
  golden parity test bit-exact.

Free slots still compute (their lane is masked garbage) — wasted FLOPs
bounded by ``1/S``, the standard continuous-batching trade against
recompiling per occupancy pattern.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from consensusml_tpu.pallas_util import on_tpu

__all__ = [
    "DecodeModel",
    "supports_decode",
    "init_cache",
    "prefill_buckets",
    "make_prefill_fn",
    "make_decode_fn",
    "make_score_fn",
]


def supports_decode(model: Any) -> bool:
    """Does this model implement the serving forward contract
    (``positions``/``kv_cache``/``return_kv`` kwargs)? True for the
    causal-LM families (GPT-2, Llama)."""
    from consensusml_tpu.models.gpt2 import GPT2LM
    from consensusml_tpu.models.llama import LlamaLM

    return isinstance(model, (GPT2LM, LlamaLM))


@dataclasses.dataclass(frozen=True)
class DecodeModel:
    """A causal LM plus the cache geometry the engine needs off it."""

    model: Any
    layers: int
    kv_heads: int
    head_dim: int
    max_len: int
    vocab_size: int
    cache_dtype: Any

    @classmethod
    def wrap(cls, model: Any) -> "DecodeModel":
        if not supports_decode(model):
            raise ValueError(
                f"{type(model).__name__} has no KV-cache decode path; "
                "serving needs a causal LM (GPT2LM / LlamaLM)"
            )
        c = model.config
        return cls(
            model=model,
            layers=c.layers,
            kv_heads=getattr(c, "kv_heads", c.heads),
            head_dim=getattr(c, "head_dim", c.hidden // c.heads),
            max_len=c.max_len,
            vocab_size=c.vocab_size,
            cache_dtype=c.dtype,
        )


def init_cache(dm: DecodeModel, num_slots: int, max_len: int) -> list[dict]:
    """Per-layer ``{"k", "v"}`` slot caches, ``(S, T, kv_heads, d)`` in the
    model's compute dtype. Llama-GQA caches pre-repeat heads (the read
    expands); ~2 * layers * S * T * kv_heads * d * itemsize bytes total."""
    shape = (num_slots, max_len, dm.kv_heads, dm.head_dim)
    return [
        {
            "k": jnp.zeros(shape, dm.cache_dtype),
            "v": jnp.zeros(shape, dm.cache_dtype),
        }
        for _ in range(dm.layers)
    ]


def prefill_buckets(max_len: int, smallest: int = 8) -> tuple[int, ...]:
    """Power-of-two prompt-length buckets up to ``max_len``: each prompt
    pads to the smallest bucket that fits, so prefill compiles once per
    bucket (log2 many programs) instead of once per prompt length."""
    buckets = []
    b = smallest
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


def _donate_cache() -> tuple[int, ...]:
    # cache donation halves steady-state HBM traffic on the chip; the CPU
    # backend has no donation support and would warn every compile
    return (1,) if on_tpu() else ()


def make_prefill_fn(dm: DecodeModel) -> Callable:
    """``prefill(params, cache, ids (1, L), length, slot, temperature,
    top_p, seed)`` -> ``(first_token, last_logits (V,), new_cache)``.

    One jit executable per padded bucket length ``L`` (compiled at
    warmup). Pad tokens DO run through the model — causal masking keeps
    every real position's logits exact, and the pad rows written into the
    cache sit beyond ``length`` where the decode mask never reads them.
    The first generated token samples IN-JIT under the request's
    ``(temperature, top_p, seed)`` at fold position ``length - 1``
    (:mod:`consensusml_tpu.serve.sampling`); ``temperature = 0`` is the
    original greedy argmax bit for bit.
    """
    from consensusml_tpu.serve.sampling import sample_token

    model = dm.model

    def prefill(params, cache, ids, length, slot, temperature, top_p, seed):
        logits, kvs = model.apply(
            {"params": params}, ids, deterministic=True, return_kv=True
        )
        last = logits[0, length - 1]  # (V,) — last REAL token's logits
        new_cache = []
        for layer_cache, (k, v) in zip(cache, kvs):
            new_cache.append(
                {
                    "k": jax.lax.dynamic_update_slice(
                        layer_cache["k"],
                        jnp.asarray(k, layer_cache["k"].dtype),
                        (slot, 0, 0, 0),
                    ),
                    "v": jax.lax.dynamic_update_slice(
                        layer_cache["v"],
                        jnp.asarray(v, layer_cache["v"].dtype),
                        (slot, 0, 0, 0),
                    ),
                }
            )
        tok = sample_token(
            last[None], temperature[None], top_p[None], seed[None],
            (length - 1)[None],
        )[0]
        return tok, last, new_cache

    return jax.jit(prefill, donate_argnums=_donate_cache())


def make_decode_fn(dm: DecodeModel) -> Callable:
    """``decode(params, cache, tokens (S,), positions (S,), temperature
    (S,), top_p (S,), seeds (S,))`` -> ``(next_tokens (S,), new_cache)``.
    Sampling happens inside the jit under per-slot fold keys — the host
    only ever fetches S int32s per step, and greedy lanes (temperature
    0) are the argmax special case of the SAME executable."""
    from consensusml_tpu.serve.sampling import sample_token

    model = dm.model

    def decode(params, cache, tokens, positions, temperature, top_p, seeds):
        logits, new_cache = model.apply(
            {"params": params},
            tokens[:, None],
            deterministic=True,
            positions=positions,
            kv_cache=cache,
        )
        toks = sample_token(
            logits[:, 0], temperature, top_p, seeds, positions
        )
        return toks, new_cache

    return jax.jit(decode, donate_argnums=_donate_cache())


def make_score_fn(dm: DecodeModel) -> Callable:
    """``score(params, ids (B, S))`` -> f32 logits ``(B, S, V)`` — the
    prefill-only scoring path, traced identically to the held-out eval
    forward (golden parity: export→serve == evaluate's mean model)."""
    model = dm.model

    def score(params, ids):
        return model.apply({"params": params}, ids, deterministic=True)

    return jax.jit(score)
