"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

SURVEY.md §2 records no TP/PP evidence in the reference; like ring/Ulysses
attention this is TPU-build-native capability. The design is the
collective-pipelining pattern (shard_map + ppermute), not a scheduler
process: layers are stacked on a leading axis and sharded over the ``pp``
mesh axis (each device holds ``L/P`` contiguous layers); microbatches
flow through stages with one ``ppermute`` hop per tick inside a
``lax.scan``. The whole schedule — bubbles included — is ONE traced XLA
program, so:

- the backward pass needs no hand-written schedule: ``jax.grad``
  differentiates through scan+ppermute and the transposed ppermute IS the
  reverse-direction pipeline;
- XLA's latency-hiding scheduler overlaps each tick's ppermute with the
  next tick's stage compute (the classic async-send/recv of a CUDA
  pipeline runtime, for free);
- it composes with the gossip worker axis and tensor-parallel axes on the
  same mesh, because it is just another named-axis collective.

Schedule: tick ``t`` has stage ``s`` processing microbatch ``m = t - s``
(valid when ``0 <= m < M``); ``T = M + P - 1`` ticks total. Bubble
fraction ``(P-1)/T`` — use ``M >> P``.

Call :func:`pipeline_apply` inside ``shard_map`` with the layer-stacked
params sharded ``P(axis_name)`` on their leading axis.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

__all__ = ["pipeline_apply", "pipeline_last_stage_mean"]


def _vma(x) -> frozenset:
    return jax.typeof(x).vma


def _varying(x: jax.Array, axes) -> jax.Array:
    """Mark ``x`` device-varying along ``axes`` it isn't already (VMA)."""
    missing = tuple(sorted(frozenset(axes) - _vma(x)))
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,  # (M, B, ...) — same on every stage (replicated)
    axis_name: str,
) -> jax.Array:
    """Run microbatches through the stage pipeline.

    ``stage_fn(stage_params, x) -> y`` applies THIS device's slice of the
    layer stack (params leaves carry a leading local-layers axis); ``x``
    and ``y`` must have identical shape/dtype (the activation that flows
    between stages).

    Returns ``(M, B, ...)`` outputs that are VALID ON THE LAST STAGE ONLY
    (other stages hold garbage from bubble ticks) — compute the loss
    there and reduce a scalar, e.g. with :func:`pipeline_last_stage_mean`.
    """
    p = jax.lax.axis_size(axis_name)
    s = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    ticks = m + p - 1
    perm = [(i, (i + 1) % p) for i in range(p)]

    def tick(carry, t):
        outs, act_in = carry
        # stage 0 ingests microbatch t; later stages take the ppermuted
        # activation (their microbatch t - s arrives exactly now)
        x_t = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, m - 1), keepdims=False
        )
        x_in = jnp.where(s == 0, x_t.astype(act_in.dtype), act_in)
        y = stage_fn(stage_params, x_in)
        idx = t - s  # my microbatch index this tick (negative/past-end = bubble)
        cidx = jnp.clip(idx, 0, m - 1)
        old = jax.lax.dynamic_index_in_dim(outs, cidx, keepdims=False)
        valid = jnp.logical_and(idx >= 0, idx < m)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, y, old), cidx, axis=0
        )
        act_out = jax.lax.ppermute(y, axis_name, perm)
        return (outs, act_out), None

    # the scan carries must enter with the SAME varying-manual-axes set the
    # tick body produces: {axis_name} for the ppermute, plus whatever the
    # params/microbatches are already varying over (the gossip worker axes
    # when pipelining runs inside the composed gossip-DP shard_map)
    varying_axes = {axis_name} | _vma(microbatches)
    for leaf in jax.tree.leaves(stage_params):
        varying_axes |= _vma(leaf)
    x0 = _varying(microbatches[0], varying_axes)
    y_shape = jax.eval_shape(stage_fn, stage_params, x0)
    if y_shape.shape != x0.shape:
        raise ValueError(
            f"stage_fn must preserve the activation shape (got {y_shape.shape} "
            f"from {x0.shape}) — stages chain into each other"
        )
    outs0 = jnp.zeros((m,) + x0.shape, y_shape.dtype)
    act0 = jnp.zeros(x0.shape, y_shape.dtype)
    # carries must already be device-varying before the first ppermute
    outs0 = _varying(outs0, varying_axes)
    act0 = _varying(act0, varying_axes)
    (outs, _), _ = jax.lax.scan(tick, (outs0, act0), jnp.arange(ticks))
    return outs


def pipeline_last_stage_mean(value: jax.Array, axis_name: str) -> jax.Array:
    """Reduce a per-stage scalar to the LAST stage's value, replicated.

    The pipeline's outputs (and hence any loss computed from them) are
    valid only on stage ``P-1``; this masks the other stages' garbage and
    broadcasts the real value everywhere with one ``psum``.
    """
    p = jax.lax.axis_size(axis_name)
    s = jax.lax.axis_index(axis_name)
    masked = jnp.where(s == p - 1, value, jnp.zeros_like(value))
    return jax.lax.psum(masked, axis_name)
