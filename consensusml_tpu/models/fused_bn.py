"""Fused BatchNorm(+ReLU) Pallas kernels for the ResNet hot path.

Reference parity: the reference trains ResNet-50 with standard BatchNorm
(BASELINE.json configs[1] + headline metric; SURVEY.md L5 — mount
empty). On TPU a ResNet step is HBM-bound on BN traffic rather than on
the convs, which made BN the candidate for this framework's "CUDA
kernel" moment. Rounds 1-5 found no gain (XLA's own BN emission at the
bandwidth floor, layout copies around the custom calls in-model); that
record went in PR 21 and no cell runs a ResNet, so the kernels are
**unmeasured on this installation** (ROADMAP C2). They are kept as a
tested opt-in (`ResNet(norm_impl="pallas")`) and parity oracle, NOT as
the default (`norm_impl="flax"`).

Design — minimum memory passes over the activation tensor A (all reads
bf16, all reduction arithmetic f32, matching flax's
``force_float32_reductions`` semantics):

- forward: 1 pass (read A) for per-channel sum/sumsq, then 1 read +
  1 write for ``y = act(x*scale + shift)`` with scale/shift pre-folded
  from (gamma, beta, mean, rsqrt) — 3 passes total;
- backward: 1 pass (read dy, x) for dbeta/dgamma, 1 pass (read dy, x,
  write dx) for the input gradient — 5 passes total. The ReLU mask is
  recomputed as ``x*scale + shift > 0`` instead of being stored, so the
  kernels need **zero residuals beyond tensors autodiff already keeps**.

Channels ride the 128-lane minor dimension; when C < 128 (ResNet stem,
stage-1 1x1 convs) consecutive rows are packed into one 128-lane row
(``x.reshape(M/p, C*p)``) so the VPU never runs half-empty — the
reductions fold the packed copies back with a (p, C) reshape-sum.

Statistics cotangents are treated as zero (the flax convention: the
``batch_stats`` collection is mutable state, not a differentiated
output); the module stop-gradients them before storing.

The ``jnp`` path implements identical math (same custom VJP, same f32
precision) for non-TPU backends and as the parity oracle; ``impl="auto"``
picks the Pallas kernels on TPU and the jnp path elsewhere.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from consensusml_tpu.pallas_util import interpret_arg, on_tpu, out_struct

__all__ = ["fused_batch_norm", "FusedBatchNorm"]

_LANE = 128


def _pow2_divisor(n: int, cap: int) -> int:
    d = 1
    while n % (d * 2) == 0 and d * 2 <= cap:
        d *= 2
    return d


def _plan(m: int, c: int, pack_small: bool = True):
    """Pick (pack p, block_m, block_c) for a (m, c) view, or None to
    fall back to the jnp path (shapes the kernels don't cover)."""
    if c < _LANE:
        if not pack_small or _LANE % c != 0:
            return None
        p = _LANE // c
        if m % p != 0:
            return None
    else:
        p = 1
        if c % _LANE != 0:
            return None
    c_eff, m_eff = c * p, m // p
    bc = next((b for b in (512, 384, 256, 128) if c_eff % b == 0), None)
    if bc is None:
        return None
    # ~0.5 MB bf16 blocks; bm must divide m_eff (grids don't mask)
    bm = _pow2_divisor(m_eff, max(8, 2**19 // (bc * 2)))
    if m_eff % 8 != 0:
        return None
    return p, m_eff, c_eff, bm, bc


def _fold_params(gamma, beta, mean, var, eps):
    rsqrt = jax.lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * rsqrt
    shift = beta.astype(jnp.float32) - mean * scale
    return scale, shift, rsqrt


def _pack(a2, p, m_eff, c_eff):
    return a2 if p == 1 else a2.reshape(m_eff, c_eff)


def _tile(v, p):
    return v if p == 1 else jnp.tile(v, p)


def _unfold_sum(s, p, c):
    """(c_eff,) packed per-lane sums -> (c,) per-channel sums."""
    return s if p == 1 else s.reshape(p, c).sum(axis=0)


# ---------------------------------------------------------------------------
# kernels — all operate on an (M, C) view, C on lanes, f32 accumulation
# ---------------------------------------------------------------------------


def _stats_kernel(x_ref, sum_ref, sq_ref):
    xf = x_ref[:].astype(jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        sq_ref[:] = jnp.zeros_like(sq_ref)

    sum_ref[:] += jnp.sum(xf, axis=0, keepdims=True)
    sq_ref[:] += jnp.sum(xf * xf, axis=0, keepdims=True)


def _norm_kernel(relu: bool, x_ref, scale_ref, shift_ref, y_ref):
    y = x_ref[:].astype(jnp.float32) * scale_ref[:] + shift_ref[:]
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[:] = y.astype(y_ref.dtype)


def _masked_g(relu, dy_ref, x_ref, scale_ref, shift_ref):
    g = dy_ref[:].astype(jnp.float32)
    if relu:
        z = x_ref[:].astype(jnp.float32) * scale_ref[:] + shift_ref[:]
        g = jnp.where(z > 0, g, 0.0)
    return g


def _bwd_reduce_kernel(relu: bool, dy_ref, x_ref, scale_ref, shift_ref,
                       mean_ref, rsqrt_ref, dbeta_ref, dgamma_ref):
    g = _masked_g(relu, dy_ref, x_ref, scale_ref, shift_ref)
    xhat = (x_ref[:].astype(jnp.float32) - mean_ref[:]) * rsqrt_ref[:]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dbeta_ref[:] = jnp.zeros_like(dbeta_ref)
        dgamma_ref[:] = jnp.zeros_like(dgamma_ref)

    dbeta_ref[:] += jnp.sum(g, axis=0, keepdims=True)
    dgamma_ref[:] += jnp.sum(g * xhat, axis=0, keepdims=True)


def _bwd_dx_kernel(relu: bool, dy_ref, x_ref, scale_ref, shift_ref,
                   mean_ref, rsqrt_ref, c1_ref, c2_ref, dx_ref):
    g = _masked_g(relu, dy_ref, x_ref, scale_ref, shift_ref)
    xhat = (x_ref[:].astype(jnp.float32) - mean_ref[:]) * rsqrt_ref[:]
    dx = scale_ref[:] * (g - c1_ref[:] - xhat * c2_ref[:])
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _grid_call(name, kernel, x2s, vecs, out_shapes, m, c, bm, bc, interpret):
    """pallas_call over grid (C/bc, M/bm): big (bm,bc) blocks for the
    arrays in ``x2s``/row-blocked outputs, (1,bc) lane-resident blocks
    for the per-channel ``vecs`` and reduction outputs (revisited across
    the inner M loop, so accumulators stay in VMEM)."""
    big = pl.BlockSpec((bm, bc), lambda ci, mi: (mi, ci), memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((1, bc), lambda ci, mi: (0, ci), memory_space=pltpu.VMEM)
    out_specs = [vec if s.shape[0] == 1 else big for s in out_shapes]
    operands = (*x2s, *[v.reshape(1, -1) for v in vecs])
    out_shapes = [out_struct(s.shape, s.dtype, *operands) for s in out_shapes]
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(c // bc, m // bm),
        in_specs=[big] * len(x2s) + [vec] * len(vecs),
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
        interpret=interpret_arg(interpret, *operands),
    )(*operands)


# ---------------------------------------------------------------------------
# functional forward/backward (custom VJP)
# ---------------------------------------------------------------------------


def _use_pallas(impl: str) -> bool:
    if impl == "auto":
        return on_tpu()
    return impl in ("pallas", "interpret")


def _stats(x2, impl, pack_small):
    m, c = x2.shape
    plan = _plan(m, c, pack_small) if _use_pallas(impl) else None
    if plan is None:
        xf = x2.astype(jnp.float32)
        return jnp.sum(xf, axis=0), jnp.sum(xf * xf, axis=0)
    p, m_eff, c_eff, bm, bc = plan
    xp = _pack(x2, p, m_eff, c_eff)
    s, sq = _grid_call(
        "fused_bn_stats",
        _stats_kernel, [xp], [],
        [jax.ShapeDtypeStruct((1, c_eff), jnp.float32)] * 2,
        m_eff, c_eff, bm, bc, impl == "interpret",
    )
    return _unfold_sum(s[0], p, c), _unfold_sum(sq[0], p, c)


def _normalize(x2, scale, shift, relu, out_dtype, impl, pack_small):
    m, c = x2.shape
    plan = _plan(m, c, pack_small) if _use_pallas(impl) else None
    if plan is None:
        y = x2.astype(jnp.float32) * scale + shift
        if relu:
            y = jnp.maximum(y, 0.0)
        return y.astype(out_dtype)
    p, m_eff, c_eff, bm, bc = plan
    y = _grid_call(
        "fused_bn_norm",
        functools.partial(_norm_kernel, relu),
        [_pack(x2, p, m_eff, c_eff)], [_tile(scale, p), _tile(shift, p)],
        [jax.ShapeDtypeStruct((m_eff, c_eff), out_dtype)],
        m_eff, c_eff, bm, bc, impl == "interpret",
    )
    return y.reshape(m, c)


def _bwd_reduce(dy2, x2, scale, shift, mean, rsqrt, relu, impl, pack_small):
    m, c = x2.shape
    plan = _plan(m, c, pack_small) if _use_pallas(impl) else None
    if plan is None:
        g = dy2.astype(jnp.float32)
        if relu:
            g = jnp.where(x2.astype(jnp.float32) * scale + shift > 0, g, 0.0)
        xhat = (x2.astype(jnp.float32) - mean) * rsqrt
        return jnp.sum(g, axis=0), jnp.sum(g * xhat, axis=0)
    p, m_eff, c_eff, bm, bc = plan
    db, dg = _grid_call(
        "fused_bn_bwd_reduce",
        functools.partial(_bwd_reduce_kernel, relu),
        [_pack(dy2, p, m_eff, c_eff), _pack(x2, p, m_eff, c_eff)],
        [_tile(v, p) for v in (scale, shift, mean, rsqrt)],
        [jax.ShapeDtypeStruct((1, c_eff), jnp.float32)] * 2,
        m_eff, c_eff, bm, bc, impl == "interpret",
    )
    return _unfold_sum(db[0], p, c), _unfold_sum(dg[0], p, c)


def _bwd_dx(dy2, x2, scale, shift, mean, rsqrt, c1, c2, relu, impl, pack_small):
    m, c = x2.shape
    plan = _plan(m, c, pack_small) if _use_pallas(impl) else None
    if plan is None:
        g = dy2.astype(jnp.float32)
        if relu:
            g = jnp.where(x2.astype(jnp.float32) * scale + shift > 0, g, 0.0)
        xhat = (x2.astype(jnp.float32) - mean) * rsqrt
        return (scale * (g - c1 - xhat * c2)).astype(x2.dtype)
    p, m_eff, c_eff, bm, bc = plan
    dx = _grid_call(
        "fused_bn_bwd_dx",
        functools.partial(_bwd_dx_kernel, relu),
        [_pack(dy2, p, m_eff, c_eff), _pack(x2, p, m_eff, c_eff)],
        [_tile(v, p) for v in (scale, shift, mean, rsqrt, c1, c2)],
        [jax.ShapeDtypeStruct((m_eff, c_eff), x2.dtype)],
        m_eff, c_eff, bm, bc, impl == "interpret",
    )
    return dx.reshape(m, c)


def _bn_train_fwd(x2, gamma, beta, eps, relu, impl, pack_small):
    m = x2.shape[0]
    s, sq = _stats(x2, impl, pack_small)
    mean = s / m
    var = jnp.maximum(sq / m - mean * mean, 0.0)
    scale, shift, rsqrt = _fold_params(gamma, beta, mean, var, eps)
    y = _normalize(x2, scale, shift, relu, x2.dtype, impl, pack_small)
    return (y, mean, var), (x2, scale, shift, mean, rsqrt)


def _bn_train_bwd(eps, relu, impl, pack_small, res, cts):
    dy2, _dmean, _dvar = cts  # stats cotangents are zero by convention
    x2, scale, shift, mean, rsqrt = res
    m = x2.shape[0]
    db, dg = _bwd_reduce(
        dy2, x2, scale, shift, mean, rsqrt, relu, impl, pack_small
    )
    dx = _bwd_dx(
        dy2, x2, scale, shift, mean, rsqrt, db / m, dg / m, relu, impl,
        pack_small,
    )
    return dx, dg, db


def _bn_train_out(x2, gamma, beta, eps, relu, impl, pack_small):
    (y, mean, var), _ = _bn_train_fwd(x2, gamma, beta, eps, relu, impl, pack_small)
    return y, mean, var


_bn_train_vjp = jax.custom_vjp(_bn_train_out, nondiff_argnums=(3, 4, 5, 6))
_bn_train_vjp.defvjp(_bn_train_fwd, _bn_train_bwd)


def fused_batch_norm(
    x: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    *,
    eps: float = 1e-5,
    act: Optional[str] = None,
    impl: str = "auto",
    pack_small: bool = True,
):
    """Training-mode fused BN over the last axis of ``x``.

    Returns ``(y, mean, var)`` with ``mean``/``var`` the f32 batch
    statistics (biased variance, flax ``use_fast_variance`` semantics).
    Gradients flow through the statistics into ``x`` exactly as in
    standard BN; the ``mean``/``var`` *outputs* are returned behind
    ``stop_gradient`` (mutable-state convention, made structural: the
    custom VJP drops their cotangents, so exposing grad-carrying outputs
    would silently differentiate to zero — a loss term on the returned
    statistics now raises/propagates nothing by construction instead).

    ``act``: ``None`` or ``"relu"`` (fused into the normalize pass and
    its backward mask). ``impl``: ``auto`` | ``pallas`` | ``jnp`` |
    ``interpret``.
    """
    if act not in (None, "relu"):
        raise ValueError(f"unsupported act {act!r}")
    if impl not in ("auto", "pallas", "jnp", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    c = x.shape[-1]
    x2 = x.reshape(-1, c)
    y, mean, var = _bn_train_vjp(
        x2, gamma, beta, eps, act == "relu", impl, pack_small
    )
    # structural: the VJP ignores stats cotangents, so make the outputs
    # visibly non-differentiable rather than silently zero-gradient
    return (
        y.reshape(x.shape),
        jax.lax.stop_gradient(mean),
        jax.lax.stop_gradient(var),
    )


# ---------------------------------------------------------------------------
# flax module
# ---------------------------------------------------------------------------


class FusedBatchNorm(nn.Module):
    """Drop-in BatchNorm(+ReLU) over the feature (last) axis.

    Matches ``nn.BatchNorm``'s state contract: f32 ``scale``/``bias``
    params and a ``batch_stats`` collection with ``mean``/``var``
    running statistics (momentum EMA), so trainers that gossip
    ``batch_stats`` (train/local_sgd.py) need no changes. Differences
    from the flax module are deliberate TPU choices: elementwise math in
    f32 fused into the statistics/normalize kernels (flax computes only
    the reductions in f32), and an optional fused ``act="relu"``.
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    act: Optional[str] = None
    impl: str = "auto"
    pack_small: bool = True
    scale_init: Callable = nn.initializers.ones_init()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        gamma = self.param("scale", self.scale_init, (c,), jnp.float32)
        beta = self.param("bias", self.bias_init, (c,), jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean", nn.initializers.zeros_init(), None, (c,), jnp.float32
        )
        ra_var = self.variable(
            "batch_stats", "var", nn.initializers.ones_init(), None, (c,), jnp.float32
        )
        if self.use_running_average:
            scale, shift, _ = _fold_params(
                gamma, beta, ra_mean.value, ra_var.value, self.epsilon
            )
            y = x.astype(jnp.float32) * scale + shift
            if self.act == "relu":
                y = jnp.maximum(y, 0.0)
            return y.astype(x.dtype)
        y, mean, var = fused_batch_norm(
            x, gamma, beta, eps=self.epsilon, act=self.act, impl=self.impl,
            pack_small=self.pack_small,
        )
        if not self.is_initializing():
            mean = jax.lax.stop_gradient(mean)
            var = jax.lax.stop_gradient(var)
            ra_mean.value = self.momentum * ra_mean.value + (1 - self.momentum) * mean
            ra_var.value = self.momentum * ra_var.value + (1 - self.momentum) * var
        return y
