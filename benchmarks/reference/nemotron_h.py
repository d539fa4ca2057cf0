"""Plain Nemotron-H hybrid decoder (NVIDIA Nemotron 3 Nano): weights from a seed,
forward, loss.

The benchmark's yardstick for the ``nemotron_h`` configurations. Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels, no chunked
scan, no sorting, nothing imported from the program. The weights are made here,
from the seed, and handed to the program.

Block ``i`` is ``x <- x + Mixer_i(RMSNorm_i(x))`` with the mixer chosen by
character ``i`` of the pattern string: ``M`` a Mamba-2 mixer, ``E`` a mixture of
experts, ``*`` grouped-query attention. After the last block
``logits = W_head RMSNorm_f(x)``; embedding and head are untied. All
projections are without bias.

``M``  ``[z | xBC | dt] = W_in u``; ``xBC <- silu(conv1d(xBC))`` depthwise, causal,
       with bias (``conv[t] = sum_j w[j] xBC[t - (K-1) + j] + b``); ``xBC`` splits
       into ``x`` (T, heads, P), ``B`` and ``C`` (T, groups, N), head ``h`` using
       group ``h // (heads / groups)``; ``dt = softplus(dt + dt_bias)``,
       ``a = exp(dt A)``, ``A = -exp(A_log)``; per head the **token-by-token
       recurrence** ``S_t = a_t S_{t-1} + dt_t x_t (x) B_t``, ``S_0 = 0``,
       ``y_t = S_t C_t + D x_t`` (a ``lax.scan`` over time); then
       ``y <- GroupRMSNorm(y silu(z))`` (gate first) and ``out = W_out y``.
``*``  ``softmax(q k^T / sqrt(d)) v``, causal, each KV head serving
       ``heads / kv_heads`` query heads; **no rotary or learned positions**.
``E``  ``s = sigmoid(W_r x)``; the ``top_k`` experts ``I`` with the largest ``s + b``,
       ``b`` the published score-correction bias, for the choice alone: zeros, or
       (``score_correction`` ``"centred"``) minus each expert's mean score over the
       step's tokens; ``w_e = s_e / (sum_{j in I} s_j + 1e-20) * scale`` — normalised
       over the chosen experts, held here or not; ``f_e(x) = W2_e relu(W1_e x)^2``;
       ``out = sum_{e in I and held} w_e f_e(x) + f_shared(x)``. **The share**: of
       ``experts`` routed experts only ``held`` live here (``held_start`` onwards);
       what the absent ones would add is left out. Experts are a plain loop over
       the held ones with a mask; no token is dropped.

Memory, so that one 8,192-token row fits at the published widths: every block is
under ``jax.checkpoint``; the recurrence is a scan over 128-step stretches of the
same token-by-token scan, each stretch under ``jax.checkpoint`` (the equations
are unchanged: the backward pass recomputes a stretch from the state that entered
it); attention is dense over all keys for 256 queries at a time.

``precision``: ``"f32"`` is the reference; ``"fp8"`` rounds both operands of every
matrix product (``reference.gpt2._mm``). ``faults`` plants a fault in the
reference put in the program's place: ``"top5"`` (one expert fewer a token),
``"renorm_over_held"`` (weights normalised over the chosen experts that are held
here), ``"no_state_carry"`` (the recurrent state reset every ``chunk`` tokens).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.gpt2 import _mm

STRETCH = 128  # tokens of the recurrence between checkpoints
QUERY_BLOCK = 256


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, by the published config's key names
    (scalars and strings only: the dict is a cache key)."""
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["hidden_size"]),
        "pattern": str(config["hybrid_override_pattern"]),
        "depth_published": int(config["num_hidden_layers_published"]),
        "m_heads": int(config["mamba_num_heads"]),
        "m_head_dim": int(config["mamba_head_dim"]),
        "groups": int(config["n_groups"]),
        "state": int(config["ssm_state_size"]),
        "conv": int(config["conv_kernel"]),
        "chunk": int(config["chunk_size"]),
        "dt_min": float(config["time_step_min"]),
        "dt_max": float(config["time_step_max"]),
        "dt_floor": float(config["time_step_floor"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "experts": int(config["n_routed_experts_published"]),
        "held": int(config["n_routed_experts"]),
        "held_start": int(config.get("held_experts_start", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared_width": int(config["moe_shared_expert_intermediate_size"]),
        "score_correction": str(config.get("e_score_correction_bias", "zeros")),
        "eps": float(config["norm_eps"]),
    }


def init_params(seed, sizes: dict) -> dict:
    """Every leaf drawn from the seed. N(0, 0.02) matrices; with
    ``rescale_prenorm_residual`` every mixer's output matrix scaled by
    ``1/sqrt(2 * depth_published)``; the Mamba-2 leaves from the config: ``A``
    uniform in [1, 16], the step ``dt`` log-uniform in [dt_min, dt_max] floored
    at dt_floor and stored as ``softplus^-1(dt)``, ``D = 1``. Norm weights and the
    convolution's bias get small random offsets so that no term is multiplied by
    an exact 1 or 0."""
    h, v = sizes["hidden"], sizes["vocab"]
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    pattern = sizes["pattern"]
    resid = 0.02 / (2.0 * sizes["depth_published"]) ** 0.5

    def normal(k, shape, std):
        return std * jax.random.normal(k, shape, jnp.float32)

    def norm(k, width=h):
        return {"scale": 1.0 + normal(k, (width,), 0.02)}

    top = jax.random.split(key, len(pattern) + 3)
    params = {
        "embed": {"embedding": normal(top[0], (v, h), 0.02)},
        "lm_head": {"kernel": normal(top[1], (h, v), 0.02)},
        "norm_f": norm(top[2]),
    }
    for i, kind in enumerate(pattern):
        k = jax.random.split(top[3 + i], 10)
        if kind == "M":
            nh, p = sizes["m_heads"], sizes["m_head_dim"]
            d_in, gn = nh * p, sizes["groups"] * sizes["state"]
            dt = jnp.exp(
                jax.random.uniform(k[4], (nh,)) * (jnp.log(sizes["dt_max"]) - jnp.log(sizes["dt_min"]))
                + jnp.log(sizes["dt_min"])
            )
            dt = jnp.maximum(dt, sizes["dt_floor"])
            mixer = {
                "in_proj": normal(k[1], (h, 2 * d_in + 2 * gn + nh), 0.02),
                "conv_kernel": jax.random.uniform(
                    k[2], (sizes["conv"], d_in + 2 * gn), jnp.float32, -0.5, 0.5),
                "conv_bias": normal(k[3], (d_in + 2 * gn,), 0.02),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(k[5], (nh,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((nh,), jnp.float32),
                "gate_norm": 1.0 + normal(k[6], (d_in,), 0.02),
                "out_proj": normal(k[7], (d_in, h), resid),
            }
        elif kind == "*":
            d_q = sizes["heads"] * sizes["head_dim"]
            d_kv = sizes["kv_heads"] * sizes["head_dim"]
            mixer = {
                "q": normal(k[1], (h, d_q), 0.02),
                "k": normal(k[2], (h, d_kv), 0.02),
                "v": normal(k[3], (h, d_kv), 0.02),
                "o": normal(k[4], (d_q, h), resid),
            }
        elif kind == "E":
            e, f, fs = sizes["held"], sizes["expert_width"], sizes["shared_width"]
            mixer = {
                "router": normal(k[1], (h, sizes["experts"]), 0.02),
                "w1": normal(k[2], (e, h, f), 0.02),
                "w2": normal(k[3], (e, f, h), resid),
                "shared_w1": normal(k[4], (h, fs), 0.02),
                "shared_w2": normal(k[5], (fs, h), resid),
            }
        else:
            raise ValueError(f"unknown block kind {kind!r} in pattern {pattern!r}")
        params[f"h_{i}"] = {"norm": norm(k[0]), "mixer": mixer}
    return params


# -- the three mixers ---------------------------------------------------------


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def recurrence(x, dt, a, bm, cm, keep):
    """The token-by-token state-space recurrence. ``x`` (B, T, H, P), ``dt`` and
    ``a`` (B, T, H), ``bm`` and ``cm`` (B, T, H, N) (already spread from groups
    to heads), ``keep`` (T,) of ones (zero where a fault resets the state).
    ``S_t = keep_t a_t S_{t-1} + dt_t x_t (x) B_t``; returns ``S_t C_t`` (B, T, H, P)."""
    b, t, h, p = x.shape
    n = bm.shape[-1]

    def step(state, inp):
        x_t, dt_t, a_t, b_t, c_t, keep_t = inp
        state = (keep_t * a_t)[..., None, None] * state + (
            (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision="highest")

    time_major = [jnp.moveaxis(v, 1, 0) for v in (x, dt, a, bm, cm)] + [keep]
    state0 = jnp.zeros((b, h, p, n), jnp.float32)
    if t % STRETCH or t <= STRETCH:
        _, y = jax.lax.scan(step, state0, time_major)
    else:  # the same scan, a stretch at a time, each stretch recomputed in the backward pass
        stretches = [v.reshape((t // STRETCH, STRETCH) + v.shape[1:]) for v in time_major]
        _, y = jax.lax.scan(
            jax.checkpoint(lambda s, inp: jax.lax.scan(step, s, inp)), state0, stretches
        )
        y = y.reshape((t,) + y.shape[2:])
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(u, p, sizes, precision="f32", faults=()):
    b, t, _ = u.shape
    nh, hp, g, n = sizes["m_heads"], sizes["m_head_dim"], sizes["groups"], sizes["state"]
    d_in, gn, kw = nh * hp, g * n, sizes["conv"]
    proj = _mm("bth,hk->btk", u, p["in_proj"], precision, -1, 0)
    z, xbc, dt = proj[..., :d_in], proj[..., d_in : 2 * d_in + 2 * gn], proj[..., 2 * d_in + 2 * gn :]
    padded = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
    conv = sum(padded[:, j : j + t] * p["conv_kernel"][j] for j in range(kw)) + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_in].reshape(b, t, nh, hp)
    bm = jnp.repeat(xbc[..., d_in : d_in + gn].reshape(b, t, g, n), nh // g, axis=2)
    cm = jnp.repeat(xbc[..., d_in + gn :].reshape(b, t, g, n), nh // g, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(dt * -jnp.exp(p["A_log"]))
    keep = jnp.ones((t,), jnp.float32)
    if "no_state_carry" in faults:
        keep = (jnp.arange(t) % sizes["chunk"] != 0).astype(jnp.float32)
    y = recurrence(x, dt, a, bm, cm, keep) + p["D"][:, None] * x
    scan_rms = jnp.sqrt(jnp.mean(jnp.square(y), axis=(1, 3)))  # (B, heads): what the scan put out
    y = y.reshape(b, t, d_in) * jax.nn.silu(z)
    y = rms_norm(y.reshape(b, t, g, d_in // g), 1.0, sizes["eps"]).reshape(b, t, d_in) * p["gate_norm"]
    return _mm("btk,kh->bth", y, p["out_proj"], precision, -1, 0), scan_rms


def attention_mixer(u, p, sizes, precision="f32"):
    b, t, _ = u.shape
    nh, kvh, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    q = _mm("bth,hk->btk", u, p["q"], precision, -1, 0).reshape(b, t, nh, d)
    k = _mm("bth,hk->btk", u, p["k"], precision, -1, 0).reshape(b, t, kvh, d)
    v = _mm("bth,hk->btk", u, p["v"], precision, -1, 0).reshape(b, t, kvh, d)
    k = jnp.repeat(k, nh // kvh, axis=2)  # KV head j serves query heads j*rep .. (j+1)*rep - 1
    v = jnp.repeat(v, nh // kvh, axis=2)
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def block(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        scores = _mm("bsnd,btnd->bnst", qs, k, precision, -1, -1) / d**0.5
        causal = (start + jnp.arange(qb))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        return _mm("bnst,btnd->bsnd", probs, v, precision, -1, 1)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(0, t, qb))  # (blocks, B, qb, heads, d)
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, nh * d)
    return _mm("btk,kh->bth", out, p["o"], precision, -1, 0)


def route(u, p, sizes, faults=()):
    """The router: chosen experts (B, T, k) and their weights. float32 at
    ``highest`` whatever the precision (the router is float32 in the model)."""
    k = sizes["top_k"] - (1 if "top5" in faults else 0)
    scores = jax.nn.sigmoid(jnp.einsum("bth,he->bte", u, p["router"], precision="highest"))
    choice = scores
    if sizes.get("score_correction", "zeros") == "centred":
        choice = scores - jnp.mean(scores, axis=(0, 1), keepdims=True)
    idx = jax.lax.top_k(choice, k)[1]
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    if "renorm_over_held" in faults:
        here = (idx >= sizes["held_start"]) & (idx < sizes["held_start"] + sizes["held"])
        total = jnp.sum(jnp.where(here, picked, 0.0), axis=-1, keepdims=True)
    else:
        total = jnp.sum(picked, axis=-1, keepdims=True)
    return idx, picked / (total + 1e-20) * sizes["route_scale"]


def _relu2_mlp(x, w1, w2, precision):
    hid = jnp.square(jax.nn.relu(_mm("bth,hf->btf", x, w1, precision, -1, 0)))
    return _mm("btf,fh->bth", hid, w2, precision, -1, 0)


def experts_mixer(u, p, sizes, precision="f32", faults=()):
    """(the layer's output, the chosen experts): a loop over the held experts,
    each run over every token and masked by its weight."""
    idx, weights = route(u, p, sizes, faults)
    out = _relu2_mlp(u, p["shared_w1"], p["shared_w2"], precision)
    for e in range(sizes["held"]):
        w_e = jnp.sum(jnp.where(idx == sizes["held_start"] + e, weights, 0.0), axis=-1)
        out = out + w_e[..., None] * _relu2_mlp(u, p["w1"][e], p["w2"][e], precision)
    return out, idx


# -- the model ----------------------------------------------------------------


def hidden_states(params, ids, sizes, precision="f32", faults=()):
    """(final-norm states (B, T, H), what the blocks showed on the way:
    ``routes`` the experts each ``E`` block chose (B, T, k), ``scan_rms`` the
    root mean square per head of each ``M`` block's scan output (B, heads))."""
    x = params["embed"]["embedding"][ids]
    seen = {"routes": [], "scan_rms": []}
    for i, kind in enumerate(sizes["pattern"]):

        def block(x, p, kind=kind):
            u = rms_norm(x, p["norm"]["scale"], sizes["eps"])
            if kind == "*":
                return x + attention_mixer(u, p["mixer"], sizes, precision), None
            mixer = mamba_mixer if kind == "M" else experts_mixer
            y, shown = mixer(u, p["mixer"], sizes, precision, faults)
            return x + y, shown

        x, shown = jax.checkpoint(block)(x, params[f"h_{i}"])
        if shown is not None:
            seen["scan_rms" if kind == "M" else "routes"].append(shown)
    hidden = rms_norm(x, params["norm_f"]["scale"], sizes["eps"])
    return hidden, {k: tuple(v) for k, v in seen.items()}


def logits_of(params, hidden, precision="f32"):
    return _mm("...h,hv->...v", hidden, params["lm_head"]["kernel"], precision, -1, 0)


def lm_loss(params, ids, sizes, precision="f32", faults=()):
    """Mean next-token cross-entropy over every position of every row (over
    the first half of each row's positions with the fault ``half_batch``)."""
    hidden, _ = hidden_states(params, ids, sizes, precision, faults)
    logits = logits_of(params, hidden[:, :-1], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    if "half_batch" in faults:
        picked = picked[:, : picked.shape[1] // 2]
    return -jnp.mean(picked)
