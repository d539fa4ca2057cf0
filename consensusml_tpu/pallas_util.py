"""What every ``pl.pallas_call`` site in the package shares.

Kernels run in three places: compiled by Mosaic on a TPU, interpreted on
the CPU test mesh, and — on either — inside the train step's
``jax.shard_map``, which type-checks varying manual axes (``check_vma``,
on by default and left on: the pipeline and ring-attention code rely on
its psum/pvary transposes). Two things follow for a ``pallas_call``
there, and both are decided from the operands, never by the caller:

- every ``out_shape`` must say over which manual axes the output varies
  (:func:`out_struct` — the union of the operands' axes; empty outside
  ``shard_map``, so one code path serves both);
- the HLO interpreter re-evaluates the kernel jaxpr (traced with the
  check off) on varying blocks and unvarying literals, which the check
  rejects at lowering time, so interpreted calls with varying operands
  go to the Mosaic TPU interpreter instead (:func:`interpret_arg`). The
  compiled path never sees the difference.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = ["on_tpu", "out_struct", "interpret_arg", "varying", "call_once"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _vma(operands) -> frozenset:
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def varying(*operands: jax.Array) -> bool:
    """Whether any operand varies over a manual mesh axis (the call is inside
    a checked ``shard_map``): a kernel from a library that builds its own
    ``out_shape``, without ``vma``, cannot be called there."""
    return bool(_vma(operands))


def out_struct(shape, dtype, *operands: jax.Array) -> jax.ShapeDtypeStruct:
    """``out_shape`` entry for a ``pallas_call`` over ``operands``."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=_vma(operands))


def interpret_arg(interpret: bool, *operands: jax.Array) -> Any:
    """The ``interpret=`` argument for a ``pallas_call`` over ``operands``."""
    if interpret and _vma(operands):
        return pltpu.InterpretParams()
    return interpret


def call_once(traced: dict, key, call, operands):
    """``call(*operands)`` (a ``pl.pallas_call``), its kernel traced ONCE
    per ``key`` (the kernel and its statics) and operand types, the traces
    kept in the calling module's ``traced``: Pallas traces a kernel anew at
    every call site, and 24 layers x 3 kernels of straight-line tiles cost
    the benchmark's cell ~9 s of set-up so (PERF.md section 6, PR 26). The
    cached equation is bound under the caller's name stack, so the device op
    keeps the scope it is found by."""
    key = (*key, tuple(jax.typeof(x) for x in operands))
    if key not in traced:
        traced[key] = jax.make_jaxpr(call)(*operands)
    closed = traced[key]
    return jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *operands)
