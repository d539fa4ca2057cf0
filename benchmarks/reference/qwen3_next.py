"""Plain Qwen3-Next decoder layer stack: weights from a seed, forward, loss.

The benchmark's yardstick for the ``qwen3_next`` configurations. Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels, no chunks,
no triangular inverse, no sorting, nothing imported from the program. The weights
are made here, from the seed, and handed to the program.

Layer ``i`` (0-based) is two sub-blocks, each behind its own zero-centred RMSNorm
``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`` and a residual:
``x <- x + Mixer_i(N(x))``, then ``x <- x + MoE_i(N(x))``; the mixer is gated
softmax attention when ``(i + 1) % full_attention_interval == 0``, else Gated
DeltaNet. After the last layer ``logits = W_head N_f(x)``; embedding and head are
untied; no projection has a bias. The program's decoder counts each sub-block as
a block of its pattern string (``GEGEGEAE`` a period), so the parameters are
``h_<2i>`` (the mixer's) and ``h_<2i+1>`` (the expert layer's).

``G``  ``[q, k, v, z] = W_qkvz u`` laid out per key head ``[q dk | k dk | v r dv |
       z r dv]`` (``r`` value heads a key head), ``[b, a] = W_ba u`` per key head
       ``[b r | a r]``; ``[q | k | v]``, each flattened over its heads, goes through
       a causal depthwise convolution (``conv[t] = sum_j w[j] x[t - (K-1) + j]``, no
       bias) and ``silu``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
       dt_bias)``; ``q``, ``k`` repeated to the value heads, l2-normalised (``x /
       sqrt(sum x^2 + 1e-6)``), ``q`` scaled by ``dk^-1/2``. Per value head the
       **token-by-token gated delta rule**, ``S_0 = 0``: ``S <- exp(g_t) S``;
       ``S <- S + k_t (x) beta_t (v_t - S^T k_t)``; ``o_t = S^T q_t`` (a
       ``lax.scan`` over time). Then ``y = RMSNorm(o) w silu(z)`` over each head's
       ``dv`` (plain weight, norm first, gate second) and ``out = W_o y``.
``A``  ``[q | gate] = W_q u`` per head ``[q d | gate d]``; ``k``, ``v`` on the KV
       heads; ``q <- N(q)``, ``k <- N(k)`` over ``d``; rotary (rotate-half: dimension
       ``i`` pairs with ``i + rot/2``) on the first ``rot = partial_rotary_factor x d``
       dimensions at ``rope_theta``, positions ``0 .. T-1``; ``softmax(q k^T /
       sqrt(d)) v``, causal, KV head ``j`` serving query heads ``j rep .. (j+1) rep -
       1``; ``out = W_o (attn * sigmoid(gate))``.
``E``  ``p = softmax(W_r x)`` over all ``experts``; the ``top_k`` experts ``I`` with
       the largest ``p + b``, ``b`` zeros or (``score_correction`` ``"centred"``)
       minus each expert's mean probability over the step's tokens, for the choice
       alone; ``w_e = p_e / sum_{j in I} p_j`` — normalised over the chosen experts,
       held here or not; ``f_e(x) = W2_e (silu(W1_e x) * W3_e x)``; ``out = sum_{e in
       I and held} w_e f_e(x) + sigmoid(x . w_s) f_shared(x)``. **The share**: of
       ``experts`` routed experts only ``held`` live here (``held_start`` onwards);
       what the absent ones would add is left out. Experts are a plain loop over the
       held ones with a mask; no token is dropped.

Memory, so that one 8,192-token row fits at the published widths: every sub-block
is under ``jax.checkpoint``; the recurrence is a scan over 128-step stretches of
the same token-by-token scan, each stretch under ``jax.checkpoint``; attention is
dense over all keys for 256 queries at a time.

``precision``: ``"f32"`` is the reference; ``"fp8"`` rounds both operands of every
matrix product (``reference.gpt2._mm``). ``faults`` plants a fault in the reference
put in the program's place: ``"top9"`` (one expert fewer a token),
``"renorm_over_held"`` (weights normalised over the chosen experts that are held
here), ``"no_state_carry"`` (the delta rule's state zeroed every ``chunk`` tokens),
``"no_delta"`` (the update ``S + k (x) beta v``: gated linear attention, the
``S^T k`` correction left out), ``"no_attn_gate"`` (attention's output gate left
out), ``"half_batch"`` (the loss over the first half of each row).
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
        "layers": int(config["num_hidden_layers"]),
        "interval": int(config["full_attention_interval"]),
        "key_heads": int(config["linear_num_key_heads"]),
        "value_heads": int(config["linear_num_value_heads"]),
        "key_dim": int(config["linear_key_head_dim"]),
        "value_dim": int(config["linear_value_head_dim"]),
        "conv": int(config["linear_conv_kernel_dim"]),
        # the source's kernels' chunk: where the fault ``no_state_carry`` loses the state
        "chunk": int(config.get("linear_chunk_size", 64)),
        "dt_min": float(config.get("linear_time_step_min", 0.001)),
        "dt_max": float(config.get("linear_time_step_max", 0.1)),
        "dt_floor": float(config.get("linear_time_step_floor", 1e-4)),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "rotary_dim": int(round(float(config["partial_rotary_factor"]) * int(config["head_dim"]))),
        "rope_theta": float(config["rope_theta"]),
        "experts": int(config["num_experts_published"]),
        "held": int(config["num_experts"]),
        "held_start": int(config.get("held_experts_start", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared_width": int(config["shared_expert_intermediate_size"]),
        "score_correction": str(config.get("score_correction", "zeros")),
        "eps": float(config["rms_norm_eps"]),
    }


def pattern_of(sizes: dict) -> str:
    """The program's pattern string: a character a sub-block."""
    return "".join(
        ("A" if (i + 1) % sizes["interval"] == 0 else "G") + "E" for i in range(sizes["layers"])
    )


def init_params(seed, sizes: dict) -> dict:
    """Every leaf drawn from the seed. N(0, 0.02) matrices (the source's
    ``initializer_range``, output matrices too); ``A_log`` the log of uniform
    [1, 16]; the delta rule's step ``dt`` log-uniform in [dt_min, dt_max] floored
    at dt_floor and stored in ``dt_bias`` as ``softplus^-1(dt)`` (the Gated
    DeltaNet authors' initialisation, Mamba-2's: with ``dt_bias`` 1, the value
    the source's modelling code holds before it loads a checkpoint, every head
    forgets within two tokens and the state carries nothing); the convolution's
    kernel uniform in [-0.5, 0.5].
    Norm weights get N(0, 0.02) offsets (zero-centred norms about 0, the delta
    rule's plain norm about 1) so that no term is multiplied by an exact 1 or 0."""
    h, v = sizes["hidden"], sizes["vocab"]
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    pattern = pattern_of(sizes)

    def normal(k, shape, std=0.02):
        return std * jax.random.normal(k, shape, jnp.float32)

    top = jax.random.split(key, len(pattern) + 3)
    params = {
        "embed": {"embedding": normal(top[0], (v, h))},
        "lm_head": {"kernel": normal(top[1], (h, v))},
        "norm_f": {"scale": normal(top[2], (h,))},
    }
    for i, kind in enumerate(pattern):
        k = jax.random.split(top[3 + i], 10)
        if kind == "G":
            kh, vh, dk, dv = (sizes[n] for n in ("key_heads", "value_heads", "key_dim", "value_dim"))
            dt = jnp.exp(
                jax.random.uniform(k[7], (vh,)) * (jnp.log(sizes["dt_max"]) - jnp.log(sizes["dt_min"]))
                + jnp.log(sizes["dt_min"])
            )
            dt = jnp.maximum(dt, sizes["dt_floor"])
            mixer = {
                "in_proj_qkvz": normal(k[1], (h, 2 * kh * dk + 2 * vh * dv)),
                "in_proj_ba": normal(k[2], (h, 2 * vh)),
                "conv_kernel": jax.random.uniform(
                    k[3], (sizes["conv"], 2 * kh * dk + vh * dv), jnp.float32, -0.5, 0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(k[4], (vh,), jnp.float32, 1.0, 16.0)),
                "norm": 1.0 + normal(k[5], (dv,)),
                "out_proj": normal(k[6], (vh * dv, h)),
            }
        elif kind == "A":
            nh, kvh, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
            mixer = {
                "q": normal(k[1], (h, 2 * nh * d)),
                "k": normal(k[2], (h, kvh * d)),
                "v": normal(k[3], (h, kvh * d)),
                "o": normal(k[4], (nh * d, h)),
                "q_norm": normal(k[5], (d,)),
                "k_norm": normal(k[6], (d,)),
            }
        else:
            e, f, fs = sizes["held"], sizes["expert_width"], sizes["shared_width"]
            mixer = {
                "router": normal(k[1], (h, sizes["experts"])),
                "w1": normal(k[2], (e, h, f)),
                "w3": normal(k[3], (e, h, f)),
                "w2": normal(k[4], (e, f, h)),
                "shared_w1": normal(k[5], (h, fs)),
                "shared_w3": normal(k[6], (h, fs)),
                "shared_w2": normal(k[7], (fs, h)),
                "shared_gate": normal(k[8], (h,)),
            }
        params[f"h_{i}"] = {"norm": {"scale": normal(k[0], (h,))}, "mixer": mixer}
    return params


# -- the three sub-blocks -----------------------------------------------------


def rms_norm0(x, w, eps):
    """Zero-centred RMSNorm: the stored weight is the offset from 1."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def delta_rule(q, k, v, g, beta, keep, delta=True):
    """The token-by-token gated delta rule. ``q``, ``k`` (B, T, H, dk), ``v`` (B,
    T, H, dv), ``g`` and ``beta`` (B, T, H), ``keep`` (T,) of ones (zero where a
    fault loses the state). ``S <- keep_t exp(g_t) S; S <- S + k_t (x) beta_t (v_t -
    S^T k_t)``; returns ``S^T q_t`` (B, T, H, dv). ``delta`` False leaves the
    ``S^T k_t`` correction out."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, inp):
        q_t, k_t, v_t, g_t, beta_t, keep_t = inp
        state = (keep_t * jnp.exp(g_t))[..., None, None] * state
        if delta:
            v_t = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision="highest")
        state = state + k_t[..., :, None] * (beta_t[..., None] * v_t)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision="highest")

    time_major = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)] + [keep]
    state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    if t % STRETCH or t <= STRETCH:
        _, o = jax.lax.scan(step, state0, time_major)
    else:  # the same scan, a stretch at a time, each stretch recomputed in the backward pass
        stretches = [x.reshape((t // STRETCH, STRETCH) + x.shape[1:]) for x in time_major]
        _, o = jax.lax.scan(
            jax.checkpoint(lambda s, inp: jax.lax.scan(step, s, inp)), state0, stretches
        )
        o = o.reshape((t,) + o.shape[2:])
    return jnp.moveaxis(o, 0, 1)


def delta_mixer(u, p, sizes, precision="f32", faults=()):
    b, t, _ = u.shape
    kh, vh, dk, dv = (sizes[n] for n in ("key_heads", "value_heads", "key_dim", "value_dim"))
    r, kw = vh // kh, sizes["conv"]
    qkvz = _mm("bth,hk->btk", u, p["in_proj_qkvz"], precision, -1, 0).reshape(b, t, kh, 2 * dk + 2 * r * dv)
    ba = _mm("bth,hk->btk", u, p["in_proj_ba"], precision, -1, 0).reshape(b, t, kh, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk : 2 * dk]
    v = qkvz[..., 2 * dk : 2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv :].reshape(b, t, vh, dv)
    beta = jax.nn.sigmoid(ba[..., :r]).reshape(b, t, vh)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., r:].reshape(b, t, vh) + p["dt_bias"])
    mixed = jnp.concatenate(
        [q.reshape(b, t, kh * dk), k.reshape(b, t, kh * dk), v.reshape(b, t, vh * dv)], axis=-1)
    padded = jnp.pad(mixed, ((0, 0), (kw - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j : j + t] * p["conv_kernel"][j] for j in range(kw)))
    q = mixed[..., : kh * dk].reshape(b, t, kh, dk)
    k = mixed[..., kh * dk : 2 * kh * dk].reshape(b, t, kh, dk)
    v = mixed[..., 2 * kh * dk :].reshape(b, t, vh, dv)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) * dk**-0.5, r, axis=2)
    k = jnp.repeat(unit(k), r, axis=2)
    keep = jnp.ones((t,), jnp.float32)
    if "no_state_carry" in faults:
        keep = (jnp.arange(t) % sizes["chunk"] != 0).astype(jnp.float32)
    o = delta_rule(q, k, v, g, beta, keep, delta="no_delta" not in faults)
    out_rms = jnp.sqrt(jnp.mean(jnp.square(o), axis=(1, 3)))  # (B, value heads): what the rule put out
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + sizes["eps"])
    y = (o * p["norm"] * jax.nn.silu(z)).reshape(b, t, vh * dv)
    return _mm("btk,kh->bth", y, p["out_proj"], precision, -1, 0), out_rms


def rotary(x, rot: int, theta: float):
    """Rotate-half rotary embedding on the first ``rot`` of the last axis's
    dimensions of ``x`` (B, T, H, D), positions ``0 .. T-1``."""
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # (T, rot/2)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    turned, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-turned[..., rot // 2 :], turned[..., : rot // 2]], axis=-1)
    return jnp.concatenate([turned * cos + half * sin, rest], axis=-1)


def attention_mixer(u, p, sizes, precision="f32", faults=()):
    b, t, _ = u.shape
    nh, kvh, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    qg = _mm("bth,hk->btk", u, p["q"], precision, -1, 0).reshape(b, t, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _mm("bth,hk->btk", u, p["k"], precision, -1, 0).reshape(b, t, kvh, d)
    v = _mm("bth,hk->btk", u, p["v"], precision, -1, 0).reshape(b, t, kvh, d)
    q = rotary(rms_norm0(q, p["q_norm"], sizes["eps"]), sizes["rotary_dim"], sizes["rope_theta"])
    k = rotary(rms_norm0(k, p["k_norm"], sizes["eps"]), sizes["rotary_dim"], sizes["rope_theta"])
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
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, nh, d)
    if "no_attn_gate" not in faults:
        out = out * jax.nn.sigmoid(gate)
    return _mm("btk,kh->bth", out.reshape(b, t, nh * d), p["o"], precision, -1, 0)


def route(u, p, sizes, faults=()):
    """The router: chosen experts (B, T, k) and their weights. float32 at
    ``highest`` whatever the precision (the router is float32 in the model)."""
    k = sizes["top_k"] - (1 if "top9" in faults else 0)
    probs = jax.nn.softmax(jnp.einsum("bth,he->bte", u, p["router"], precision="highest"), axis=-1)
    choice = probs
    if sizes.get("score_correction", "zeros") == "centred":
        choice = probs - jnp.mean(probs, axis=(0, 1), keepdims=True)
    idx = jax.lax.top_k(choice, k)[1]
    picked = jnp.take_along_axis(probs, idx, axis=-1)
    if "renorm_over_held" in faults:
        here = (idx >= sizes["held_start"]) & (idx < sizes["held_start"] + sizes["held"])
        total = jnp.sum(jnp.where(here, picked, 0.0), axis=-1, keepdims=True)
    else:
        total = jnp.sum(picked, axis=-1, keepdims=True)
    return idx, picked / (total + 1e-20)


def _swiglu_mlp(x, w1, w3, w2, precision):
    hid = jax.nn.silu(_mm("bth,hf->btf", x, w1, precision, -1, 0)) * _mm("bth,hf->btf", x, w3, precision, -1, 0)
    return _mm("btf,fh->bth", hid, w2, precision, -1, 0)


def experts_mixer(u, p, sizes, precision="f32", faults=()):
    """(the layer's output, the chosen experts): a loop over the held experts
    (a ``lax.scan``: one body for the compiler, whatever their number), each
    run over every token and masked by its weight."""
    idx, weights = route(u, p, sizes, faults)
    gate = jax.nn.sigmoid(jnp.einsum("bth,h->bt", u, p["shared_gate"], precision="highest"))
    out = gate[..., None] * _swiglu_mlp(u, p["shared_w1"], p["shared_w3"], p["shared_w2"], precision)

    def add_expert(out, held):
        e, w1, w3, w2 = held
        w_e = jnp.sum(jnp.where(idx == sizes["held_start"] + e, weights, 0.0), axis=-1)
        return out + w_e[..., None] * _swiglu_mlp(u, w1, w3, w2, precision), None

    out, _ = jax.lax.scan(
        jax.checkpoint(add_expert), out, (jnp.arange(sizes["held"]), p["w1"], p["w3"], p["w2"]))
    return out, idx


# -- the model ----------------------------------------------------------------


def hidden_states(params, ids, sizes, precision="f32", faults=()):
    """(final-norm states (B, T, H), what the sub-blocks showed on the way:
    ``routes`` the experts each expert layer chose (B, T, k), ``gdn_rms`` the
    root mean square per value head of each delta rule's output (B, heads))."""
    x = params["embed"]["embedding"][ids]
    seen = {"routes": [], "gdn_rms": []}
    for i, kind in enumerate(pattern_of(sizes)):

        def block(x, p, kind=kind):
            u = rms_norm0(x, p["norm"]["scale"], sizes["eps"])
            if kind == "A":
                return x + attention_mixer(u, p["mixer"], sizes, precision, faults), None
            mixer = delta_mixer if kind == "G" else experts_mixer
            y, shown = mixer(u, p["mixer"], sizes, precision, faults)
            return x + y, shown

        x, shown = jax.checkpoint(block)(x, params[f"h_{i}"])
        if shown is not None:
            seen["gdn_rms" if kind == "G" else "routes"].append(shown)
    hidden = rms_norm0(x, params["norm_f"]["scale"], sizes["eps"])
    return hidden, {k: tuple(v) for k, v in seen.items()}


def logits_of(params, hidden, precision="f32"):
    return _mm("...h,hv->...v", hidden, params["lm_head"]["kernel"], precision, -1, 0)


def lm_loss(params, ids, sizes, precision="f32", faults=(), with_shown=False):
    """Mean next-token cross-entropy over every position of every row (over
    the first half of each row's positions with the fault ``half_batch``);
    ``with_shown``: ``(loss, what the sub-blocks showed)``, for a gradient that
    hands both out of one program."""
    hidden, shown = hidden_states(params, ids, sizes, precision, faults)
    logits = logits_of(params, hidden[:, :-1], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    if "half_batch" in faults:
        picked = picked[:, : picked.shape[1] // 2]
    loss = -jnp.mean(picked)
    return (loss, shown) if with_shown else loss
