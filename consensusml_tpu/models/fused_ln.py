"""Fused LayerNorm Pallas kernel for the transformer hot path.

Reference parity: the reference trains GPT-2/BERT with standard
LayerNorm (BASELINE.json configs[3,5]; SURVEY.md L5 — mount empty). In
``gpt2m_choco.solo`` the layernorm/loss reduction chain is
``convert_reduce_fusion``, 50 ms of a 586 ms round (PERF.md section 5);
this kernel is an attempt at that lever.

Why LN might beat XLA where BN could not (:mod:`.fused_bn`): LN's reduction is ROW-LOCAL (over the hidden/lane
dimension), so a (bm, H) block resident in VMEM computes statistics AND
normalizes in ONE read of the activation — XLA's emission reads the
tensor once for the stats reduce and again for the normalize
elementwise (2 reads + 1 write). Same asymmetry in the backward: the
row statistics are recomputed in-VMEM from the already-resident x
block, so the kernel needs zero residuals beyond tensors autodiff
already keeps (x, gamma), and dx + dgamma + dbeta land in one
(read dy, read x, write dx) pass.

Memory passes over the (M, H) activation:

- forward: 1 read + 1 write (XLA: 2 reads + 1 write);
- backward: 2 reads + 1 write (XLA: typically 3-4 reads + 1 write —
  separate dgamma/dbeta reduce and dx elementwise fusions).

dtype semantics: arithmetic is f32 regardless of input dtype (flax's
``nn.LayerNorm(dtype=f32)`` behavior). ``out_dtype`` controls the
OUTPUT precision: the transformer blocks feed LN straight into a bf16
matmul, so emitting bf16 from the kernel halves the write+re-read
traffic with numerics identical to "f32 out, cast at the matmul".
Parity vs flax is pinned in tests/test_fused_ln.py (interpreter mode +
jnp path); whether it wins in the cell is unmeasured on this
installation (ROADMAP C2), so flax stays the default.

Shapes covered: H a multiple of 128 lanes (all five reference configs:
256..1024) and rows divisible by 8 after flattening; anything else
falls back to the identical-math jnp path.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from consensusml_tpu.pallas_util import interpret_arg, on_tpu, out_struct

__all__ = ["fused_layer_norm", "FusedLayerNorm"]

_LANE = 128


def _use_pallas(impl: str) -> bool:
    if impl == "auto":
        return on_tpu()
    return impl in ("pallas", "interpret")


def _plan(m: int, h: int):
    """Rows-per-block for an (m, h) view, or None → jnp fallback.

    The whole hidden dim rides one block (row-local statistics), so h
    must tile the 128-lane minor and bm must divide m exactly (grids
    don't mask). The cap budgets VMEM for the BACKWARD kernel's worst
    case: ~6 f32 (bm, h) temporaries (xf/dyf/xhat/g + ins/outs) must sit
    under the ~16 MB scoped limit, so bm*h is held to 2^18 elements
    (≈ 6 MB of f32 temps + IO) — measured r5: 2^21/2 rows OOM'd Mosaic's
    scoped vmem at h=1024."""
    if h % _LANE != 0 or m % 8 != 0:
        return None
    bm = 8
    cap = max(8, 2**18 // h)
    while m % (bm * 2) == 0 and bm * 2 <= cap:
        bm *= 2
    return bm


def _row_stats(xf: jax.Array, eps: float):
    mu = jnp.mean(xf, axis=1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    return xc, jax.lax.rsqrt(var + eps)


def _ln_fwd_kernel(eps: float, x_ref, gamma_ref, beta_ref, y_ref):
    xc, rsig = _row_stats(x_ref[:].astype(jnp.float32), eps)
    y_ref[:] = (xc * rsig * gamma_ref[:] + beta_ref[:]).astype(y_ref.dtype)


def _ln_bwd_kernel(eps: float, dy_ref, x_ref, gamma_ref,
                   dx_ref, dgamma_ref, dbeta_ref):
    xc, rsig = _row_stats(x_ref[:].astype(jnp.float32), eps)
    xhat = xc * rsig
    dyf = dy_ref[:].astype(jnp.float32)
    g = dyf * gamma_ref[:]
    m1 = jnp.mean(g, axis=1, keepdims=True)
    m2 = jnp.mean(g * xhat, axis=1, keepdims=True)
    dx_ref[:] = (rsig * (g - m1 - xhat * m2)).astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dgamma_ref[:] = jnp.zeros_like(dgamma_ref)
        dbeta_ref[:] = jnp.zeros_like(dbeta_ref)

    dgamma_ref[:] += jnp.sum(dyf * xhat, axis=0, keepdims=True)
    dbeta_ref[:] += jnp.sum(dyf, axis=0, keepdims=True)


def _specs(bm: int, h: int):
    big = pl.BlockSpec((bm, h), lambda mi: (mi, 0), memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((1, h), lambda mi: (0, 0), memory_space=pltpu.VMEM)
    return big, vec


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_layer_norm(
    x: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    eps: float = 1e-6,
    out_dtype: Any = None,
    impl: str = "auto",
) -> jax.Array:
    """LayerNorm over the last axis: ``(x - mu) * rsqrt(var + eps) *
    gamma + beta``, f32 arithmetic, ``out_dtype`` output (default: f32,
    the flax convention)."""
    y, _ = _fwd(x, gamma, beta, eps, out_dtype, impl)
    return y


def _fwd(x, gamma, beta, eps, out_dtype, impl):
    out_dtype = out_dtype or jnp.float32
    shape = x.shape
    h = shape[-1]
    m = x.size // h
    x2 = x.reshape(m, h)
    bm = _plan(m, h) if _use_pallas(impl) else None
    if bm is None:
        xc, rsig = _row_stats(x2.astype(jnp.float32), eps)
        y = xc * rsig * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
        y2 = y.astype(out_dtype)
    else:
        big, vec = _specs(bm, h)
        operands = (x2, gamma.reshape(1, h), beta.reshape(1, h))
        y2 = pl.pallas_call(
            functools.partial(_ln_fwd_kernel, eps),
            name="fused_ln_fwd",
            grid=(m // bm,),
            in_specs=[big, vec, vec],
            out_specs=big,
            out_shape=out_struct((m, h), out_dtype, *operands),
            interpret=interpret_arg(impl == "interpret", *operands),
        )(*operands)
    return y2.reshape(shape), (x, gamma)


def _bwd(eps, out_dtype, impl, res, dy):
    x, gamma = res
    shape = x.shape
    h = shape[-1]
    m = x.size // h
    x2 = x.reshape(m, h)
    dy2 = dy.reshape(m, h)
    bm = _plan(m, h) if _use_pallas(impl) else None
    if bm is None:
        xc, rsig = _row_stats(x2.astype(jnp.float32), eps)
        xhat = xc * rsig
        dyf = dy2.astype(jnp.float32)
        g = dyf * gamma.astype(jnp.float32)
        m1 = jnp.mean(g, axis=1, keepdims=True)
        m2 = jnp.mean(g * xhat, axis=1, keepdims=True)
        dx2 = (rsig * (g - m1 - xhat * m2)).astype(x.dtype)
        dgamma = jnp.sum(dyf * xhat, axis=0)
        dbeta = jnp.sum(dyf, axis=0)
    else:
        big, vec = _specs(bm, h)
        operands = (dy2, x2, gamma.reshape(1, h))
        dx2, dgamma2, dbeta2 = pl.pallas_call(
            functools.partial(_ln_bwd_kernel, eps),
            name="fused_ln_bwd",
            grid=(m // bm,),
            in_specs=[big, big, vec],
            out_specs=[big, vec, vec],
            out_shape=[
                out_struct((m, h), x.dtype, *operands),
                out_struct((1, h), jnp.float32, *operands),
                out_struct((1, h), jnp.float32, *operands),
            ],
            interpret=interpret_arg(impl == "interpret", *operands),
        )(*operands)
        dgamma, dbeta = dgamma2[0], dbeta2[0]
    return (
        dx2.reshape(shape),
        dgamma.astype(gamma.dtype),
        dbeta.astype(gamma.dtype),
    )


fused_layer_norm.defvjp(
    lambda x, gamma, beta, eps, out_dtype, impl: _fwd(
        x, gamma, beta, eps, out_dtype, impl
    ),
    _bwd,
)


class FusedLayerNorm(nn.Module):
    """Drop-in for ``nn.LayerNorm(dtype=f32)`` backed by the fused
    kernel. ``out_dtype`` may be bf16 when the consumer is a bf16
    matmul (numerically identical to f32-out-then-cast, half the
    traffic)."""

    eps: float = 1e-6
    out_dtype: Any = None
    impl: str = "auto"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = x.shape[-1]
        gamma = self.param("scale", nn.initializers.ones, (h,), jnp.float32)
        beta = self.param("bias", nn.initializers.zeros, (h,), jnp.float32)
        return fused_layer_norm(
            x, gamma, beta, self.eps, self.out_dtype, self.impl
        )
