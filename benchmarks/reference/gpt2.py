"""Plain GPT-2 (Radford et al. 2019): weights from a seed, forward, loss.

The benchmark's yardstick for the GPT-2 configurations. Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels, no
cache, no batching tricks, nothing imported from the program. The weights
are made here, from the seed, and handed to the program — the reference
never takes anything the program has made.

Parameter tree (the layout a pre-LN GPT-2 with fused qkv needs; the
program's model is built to consume the same names):

    wte.embedding (V, H)   wpe.embedding (T, H)   ln_f.{scale,bias} (H,)
    h_<i>.ln_1 / ln_2 .{scale,bias} (H,)
    h_<i>.qkv.kernel (H, heads, 3*d)  .bias (heads, 3*d)   per head [q|k|v]
    h_<i>.out.kernel (heads, d, H)    .bias (H,)
    h_<i>.mlp_in.kernel (H, 4H) .bias (4H,)   h_<i>.mlp_out.kernel (4H, H) .bias (H,)

``precision`` selects what the matmuls see: ``"f32"`` is the reference;
``"fp8"`` and ``"int8"`` round both operands of every matmul first (the
lower-precision controls of ``correct``: fp8 is e4m3 and int8 absmax/127, each
with one scale per row, straight-through in the backward pass).
``"fp8_e5m2"`` rounds the operands, and the gradient that flows back into each
matmul, to e5m2.

Departures from the published model, each below the noise of bf16: none in
the equations; the tanh GELU is GPT-2's own, the LayerNorm epsilon the configuration's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INT_LEVELS = {"int8": 127.0}


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, by the published config's key names."""
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["n_embd"]),
        "layers": int(config["n_layer"]),
        "heads": int(config["n_head"]),
        "positions": int(config["n_positions"]),
        "mlp": int(config["n_inner"]),
        "eps": float(config["layer_norm_epsilon"]),
    }


def init_params(seed, sizes: dict) -> dict:
    """Every leaf drawn from the seed: N(0, 0.02) weights as published,
    output projections scaled by 1/sqrt(2*layers), and small random biases
    and LayerNorm offsets so that no term of the equations is multiplied by
    an exact 0 or 1 (a zero bias would hide a dropped bias)."""
    h, v, t = sizes["hidden"], sizes["vocab"], sizes["positions"]
    nh, m, n = sizes["heads"], sizes["mlp"], sizes["layers"]
    d = h // nh
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    resid = 0.02 / (2.0 * n) ** 0.5

    def normal(k, shape, std):
        return std * jax.random.normal(k, shape, jnp.float32)

    def ln(k):
        a, b = jax.random.split(k)
        return {"scale": 1.0 + normal(a, (h,), 0.02), "bias": normal(b, (h,), 0.02)}

    top = jax.random.split(key, n + 3)
    params = {
        "wte": {"embedding": normal(top[0], (v, h), 0.02)},
        "wpe": {"embedding": normal(top[1], (t, h), 0.01)},
        "ln_f": ln(top[2]),
    }
    for i in range(n):
        k = jax.random.split(top[3 + i], 10)
        params[f"h_{i}"] = {
            "ln_1": ln(k[0]),
            "ln_2": ln(k[1]),
            "qkv": {
                "kernel": normal(k[2], (h, nh, 3 * d), 0.02),
                "bias": normal(k[3], (nh, 3 * d), 0.02),
            },
            "out": {
                "kernel": normal(k[4], (nh, d, h), resid),
                "bias": normal(k[5], (h,), 0.02),
            },
            "mlp_in": {
                "kernel": normal(k[6], (h, m), 0.02),
                "bias": normal(k[7], (m,), 0.02),
            },
            "mlp_out": {
                "kernel": normal(k[8], (m, h), resid),
                "bias": normal(k[9], (h,), 0.02),
            },
        }
    return params


# -- what a matmul sees ------------------------------------------------------


def _ste(x, rounded):
    return x + jax.lax.stop_gradient(rounded - x)


FP8 = {"fp8": (jnp.float8_e4m3fn, 448.0), "fp8_e5m2": (jnp.float8_e5m2, 57344.0)}
# what a matmul's operands, and the gradient that flows back into it, are rounded to
TRAINING_FP8 = {"fp8_e5m2": ("fp8_e5m2", "fp8_e5m2")}


def _rounded(x, precision: str, axis: int):
    """``x`` at ``precision`` with one scale per row along ``axis``."""
    top = INT_LEVELS.get(precision) or FP8[precision][1]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if precision in INT_LEVELS:
        return jnp.clip(jnp.round(x / scale), -top, top) * scale
    return (x / scale).astype(FP8[precision][0]).astype(jnp.float32) * scale


def _round_operand(x, precision: str, axis: int):
    """Round one matmul operand; ``axis`` is its contraction axis (scales are
    per row along it: one per token / output channel). Straight-through: the
    backward pass sees the rounded operands and passes the gradient on whole."""
    if precision == "f32":
        return x
    return _ste(x, _rounded(x, TRAINING_FP8.get(precision, (precision,))[0], axis))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_cotangent(y, precision: str):
    """Identity forward; the gradient flowing back into the matmul is rounded."""
    return y


def _round_cotangent_fwd(y, precision):
    return y, None


def _round_cotangent_bwd(precision, _, g):
    return (_rounded(g, precision, -1),)


_round_cotangent.defvjp(_round_cotangent_fwd, _round_cotangent_bwd)


def _mm(spec: str, a, b, precision: str, a_axis: int, b_axis: int):
    a = _round_operand(a, precision, a_axis)
    b = _round_operand(b, precision, b_axis)
    y = jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision in TRAINING_FP8:
        y = _round_cotangent(y, TRAINING_FP8[precision][1])
    return y


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _block(x, p, sizes, precision):
    b, s, h = x.shape
    nh = sizes["heads"]
    d = h // nh
    y = _layer_norm(x, p["ln_1"], sizes["eps"])
    qkv = _mm("bsh,hnd->bsnd", y, p["qkv"]["kernel"], precision, -1, 0)
    qkv = qkv + p["qkv"]["bias"]
    q, k, v = qkv[..., :d], qkv[..., d : 2 * d], qkv[..., 2 * d :]
    scores = _mm("bsnd,btnd->bnst", q, k, precision, -1, -1) / d**0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = _mm("bnst,btnd->bsnd", probs, v, precision, -1, 1)
    out = _mm(
        "bsk,kh->bsh", attn.reshape(b, s, h), p["out"]["kernel"].reshape(h, h),
        precision, -1, 0,
    )
    x = x + out + p["out"]["bias"]
    y = _layer_norm(x, p["ln_2"], sizes["eps"])
    y = _mm("bsh,hm->bsm", y, p["mlp_in"]["kernel"], precision, -1, 0)
    y = _gelu(y + p["mlp_in"]["bias"])
    y = _mm("bsm,mh->bsh", y, p["mlp_out"]["kernel"], precision, -1, 0)
    return x + y + p["mlp_out"]["bias"]


def hidden_states(params, ids, sizes, precision: str = "f32", remat: bool = False):
    """Final-LayerNorm states (B, S, H) for token ids (B, S)."""
    s = ids.shape[1]
    x = params["wte"]["embedding"][ids] + params["wpe"]["embedding"][:s][None]
    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"h_{i}"] for i in range(sizes["layers"])],
    )

    def body(x, p):
        return _block(x, p, sizes, precision), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, stacked)
    return _layer_norm(x, params["ln_f"], sizes["eps"])


def logits_of(params, hidden, precision: str = "f32"):
    """Tied head: hidden (..., H) against the token embedding."""
    return _mm("...h,vh->...v", hidden, params["wte"]["embedding"], precision, -1, -1)


def lm_loss(params, ids, sizes, precision: str = "f32"):
    """Mean next-token cross-entropy over every position of every row."""
    hidden = hidden_states(params, ids, sizes, precision, remat=True)
    logits = logits_of(params, hidden[:, :-1], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)
