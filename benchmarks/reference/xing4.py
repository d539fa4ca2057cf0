"""Plain Xing4.0 decoder layer stack: weights from a seed, forward, two losses.

The benchmark's yardstick for the ``xing4`` configurations. Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels, no sorting,
nothing imported from the program. The weights are made here, from the seed, and
handed to the program.

**Streams.** A token's residual is ``X`` in R^{n x hidden}, ``n = hc_mult``; after
the embedding every row of ``X`` is the embedding. Layer ``i`` is two sub-blocks,
attention then FFN (``first_k_dense_replace`` leading layers have a dense FFN, the
rest an expert layer); the program's decoder counts each sub-block as a block of
its pattern string (``LDLELELELE``), so the parameters are ``h_<2i>`` and
``h_<2i+1>``. Sub-block ``F`` with its RMSNorm ``N(x) = x / sqrt(mean(x^2) + eps) w``
and its maps ``hc`` (``phi`` (n, hidden, 2n + n^2) read as (n hidden, ·), columns
``[pre | post | res]``; ``bias`` likewise; ``gate = (a_pre, a_post, a_res)``):

    x~     = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)
    H_pre  = sigmoid(a_pre (x~ Phi_pre) + b_pre)         H_post = 2 sigmoid(a_post (x~ Phi_post) + b_post)
    M_0    = exp(clip(a_res mat(x~ Phi_res) + B_res, lo, hi))
    M_t    = cols(rows(M_{t-1})): every row divided by (its sum + hc_eps), then every column; H_res = M_20
    u = H_pre X        y = F(N(u))        X' = H_res X + H_post^T y

After the last layer ``h = sum of the streams``, ``logits = W_head N_f(h)``.

``L``  ``c_q = N_q(u W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` per head; ``[c_kv | k_r] =
       u W_kva``; ``[k_nope | v] = N_kv(c_kv) W_kvb`` per head; rotary on ``q_rope``
       and on the ONE ``k_r`` a token, which every head shares: pairs ``(2i, 2i+1)``,
       yarn's frequencies (:func:`yarn_frequencies`), cos and sin unscaled;
       ``softmax(causal(q k^T) (nope + rope)^-1/2 mscale^2) v`` with ``mscale = 0.1
       mscale_all_dim ln(factor) + 1``; ``y = concat_heads(o) W_o``.
``D``  ``W2 (silu(W1 u) * W3 u)``.
``E``  ``s = sigmoid(W_r u)`` over all ``experts``; the ``top_k`` experts ``I`` with the
       largest ``s + b``, ``b`` zeros or (``score_correction`` ``"centred"``) minus each
       expert's mean score over the step's tokens, for the choice alone; ``w_e =
       route_scale s_e / sum_{j in I} s_j``; ``out = sum_{e in I and held} w_e f_e(u) +
       f_shared(u)``, ``f`` a SwiGLU. **The share**: of ``experts`` routed experts only
       ``held`` live here (``held_start`` onwards); what the absent ones would add is
       left out. A plain loop (a ``lax.scan``) over the held ones with a mask.

**Multi-token prediction** (depth 1). With ``h_i`` the summed streams BEFORE ``N_f``:
``h'_i = [N_e(Emb(t_{i+1})) | N_h(h_i)] W_eh``, one more layer (``L`` then ``E``,
hyper-connected, its own weights: ``mtp/h_0``, ``mtp/h_1``) from ``h'`` replicated
into the streams, their sum ``g``, and ``loss = CE(head(N_f(h_i)), t_{i+1}) + lambda
CE(head(N_mtp(g_i)), t_{i+2})``. The module runs over every position (``t_{S}`` read
as ``t_0``, as the program does); its loss is over positions ``0 .. S-3``.

``precision``: ``"f32"`` is the reference; ``"fp8"`` rounds both operands of every
matrix product (``reference.gpt2._mm``; the maps' and the router's products stay
float32, as they are in the model). ``faults`` plants a fault in the reference put
in the program's place: ``"top3"`` (one expert fewer a token), ``"renorm_over_held"``
(weights normalised over the chosen experts that are held here), ``"no_mtp"``
(lambda 0), ``"sinkhorn_1"`` (one iteration), ``"one_stream"`` (``H_res = I``, ``H_pre =
1/n``, ``H_post = 1``: a plain residual under this model's name), ``"no_rope_key"``
(the shared rotary key left out), ``"no_yarn_scale"`` (the score scale without
``mscale^2``), ``"half_batch"`` (both losses over the first half of each row).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.gpt2 import _mm

QUERY_BLOCK = 256


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, by the published config's key names
    (scalars and strings only: the dict is a cache key)."""
    yarn = config["rope_scaling"]
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope_dim": int(config["qk_nope_head_dim"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "rope_theta": float(config["rope_theta"]),
        "rope_factor": float(yarn["factor"]),
        "beta_fast": float(yarn["beta_fast"]),
        "beta_slow": float(yarn["beta_slow"]),
        "original_max_len": int(yarn["original_max_position_embeddings"]),
        "mscale_all_dim": float(yarn["mscale_all_dim"]),
        "dense_width": int(config["intermediate_size"]),
        "experts": int(config["n_routed_experts_published"]),
        "held": int(config["n_routed_experts"]),
        "held_start": int(config.get("held_experts_start", 0)),
        "top_k": int(config["num_experts_per_tok"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared_width": int(config["n_shared_experts"]) * int(config["moe_intermediate_size"]),
        "score_correction": str(config.get("score_correction", "zeros")),
        "eps": float(config["rms_norm_eps"]),
        "streams": int(config["hc_mult"]),
        "sinkhorn": int(config["hc_sinkhorn_iters"]),
        "hc_eps": float(config["hc_eps"]),
        "clamp_min": float(config["mhc_h_res_clamp_min"]),
        "clamp_max": float(config["mhc_h_res_clamp_max"]),
        "mtp_layers": int(config["num_nextn_predict_layers"]),
        "mtp_lambda": float(config["train"]["mtp_lambda"]),
    }


def pattern_of(sizes: dict) -> str:
    """The program's pattern string: a character a sub-block."""
    return "LD" * sizes["dense_layers"] + "LE" * (sizes["layers"] - sizes["dense_layers"])


def init_params(seed, sizes: dict) -> dict:
    """Every leaf drawn from the seed. N(0, 0.02) matrices (output matrices
    too); norm weights 1 + N(0, 0.02), so that no term is multiplied by an
    exact 1. The maps: ``phi`` N(0, 0.02), ``b_pre`` and ``b_post`` N(0, 1),
    ``B_res`` N(0, 1) + 2 I, the three gates 1: with ``B_res`` = 0 and small
    gates ``exp(0)`` is already doubly stochastic and Sinkhorn has nothing to
    do, so that a program that skipped it could not fail."""
    h, v, n = sizes["hidden"], sizes["vocab"], sizes["streams"]
    key = jax.random.key(jnp.asarray(seed, jnp.uint32))
    pattern = pattern_of(sizes)

    def normal(k, shape, std=0.02):
        return std * jax.random.normal(k, shape, jnp.float32)

    def norm(k, width=h):
        return {"scale": 1.0 + normal(k, (width,))}

    def block(key, kind):
        k = jax.random.split(key, 12)
        bias = jax.random.normal(k[2], (2 * n + n * n,), jnp.float32)
        bias = bias.at[2 * n :].add(2.0 * jnp.eye(n).reshape(-1))
        hc = {"phi": normal(k[1], (n, h, 2 * n + n * n)), "bias": bias, "gate": jnp.ones((3,), jnp.float32)}
        if kind == "L":
            nh, qr, kr = sizes["heads"], sizes["q_rank"], sizes["kv_rank"]
            dn, dr, dv = sizes["nope_dim"], sizes["rope_dim"], sizes["v_dim"]
            mixer = {
                "q_a": normal(k[3], (h, qr)),
                "q_a_norm": 1.0 + normal(k[4], (qr,)),
                "q_b": normal(k[5], (qr, nh * (dn + dr))),
                "kv_a": normal(k[6], (h, kr + dr)),
                "kv_a_norm": 1.0 + normal(k[7], (kr,)),
                "kv_b": normal(k[8], (kr, nh * (dn + dv))),
                "o": normal(k[9], (nh * dv, h)),
            }
        elif kind == "D":
            f = sizes["dense_width"]
            mixer = {"w1": normal(k[3], (h, f)), "w3": normal(k[4], (h, f)), "w2": normal(k[5], (f, h))}
        else:
            e, f, fs = sizes["held"], sizes["expert_width"], sizes["shared_width"]
            mixer = {
                "router": normal(k[3], (h, sizes["experts"])),
                "w1": normal(k[4], (e, h, f)),
                "w3": normal(k[5], (e, h, f)),
                "w2": normal(k[6], (e, f, h)),
                "shared_w1": normal(k[7], (h, fs)),
                "shared_w3": normal(k[8], (h, fs)),
                "shared_w2": normal(k[9], (fs, h)),
            }
        return {"norm": norm(k[0]), "hc": hc, "mixer": mixer}

    top = jax.random.split(key, len(pattern) + 4)
    params = {
        "embed": {"embedding": normal(top[0], (v, h))},
        "lm_head": {"kernel": normal(top[1], (h, v))},
        "norm_f": norm(top[2]),
    }
    for i, kind in enumerate(pattern):
        params[f"h_{i}"] = block(top[4 + i], kind)
    if sizes["mtp_layers"]:
        k = jax.random.split(top[3], 6)
        params["mtp"] = {
            "enorm": norm(k[0]), "hnorm": norm(k[1]), "norm": norm(k[2]),
            "eh_proj": normal(k[3], (2 * h, h)),
            "h_0": block(k[4], "L"), "h_1": block(k[5], "E"),
        }
    return params


# -- the residual path ----------------------------------------------------------


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def hyper_maps(x, p, sizes, faults=()):
    """``x`` (B, T, n, hidden) -> ``H_pre`` (B, T, n), ``H_post`` (B, T, n),
    ``H_res`` (B, T, n, n), float32 at ``highest`` whatever the precision."""
    b, t, n, h = x.shape
    eps = sizes["hc_eps"]
    flat = x.reshape(b, t, n * h)
    flat = flat / jnp.sqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)
    raw = jnp.einsum("btk,km->btm", flat, p["phi"].reshape(n * h, -1), precision="highest")
    a_pre, a_post, a_res = p["gate"]
    bias = p["bias"]
    h_pre = jax.nn.sigmoid(a_pre * raw[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(a_post * raw[..., n : 2 * n] + bias[n : 2 * n])
    m = a_res * raw[..., 2 * n :].reshape(b, t, n, n) + bias[2 * n :].reshape(n, n)
    m = jnp.exp(jnp.clip(m, sizes["clamp_min"], sizes["clamp_max"]))
    for _ in range(1 if "sinkhorn_1" in faults else sizes["sinkhorn"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)  # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)  # columns
    if "one_stream" in faults:
        h_pre = jnp.full_like(h_pre, 1.0 / n)
        h_post = jnp.ones_like(h_post)
        m = jnp.broadcast_to(jnp.eye(n, dtype=m.dtype), m.shape)
    return h_pre, h_post, m


# -- the three sub-blocks -------------------------------------------------------


def yarn_frequencies(sizes: dict):
    """The rotary pairs' frequencies: ``f_j = theta^(-2j/d)``; pair ``j`` turns
    ``f_j L / 2 pi`` times in the original ``L`` positions. Pairs that turn more
    than ``beta_fast`` times keep ``f_j``, pairs that turn less than ``beta_slow``
    times get ``f_j / factor``, a linear ramp over the pairs between."""
    d, theta, length = sizes["rope_dim"], sizes["rope_theta"], sizes["original_max_len"]
    f = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    if sizes["rope_factor"] == 1.0:
        return f
    pair = lambda turns: d * math.log(length / (2 * math.pi * turns)) / (2 * math.log(theta))
    lo = max(math.floor(pair(sizes["beta_fast"])), 0)
    hi = min(math.ceil(pair(sizes["beta_slow"])), d // 2 - 1)
    keep = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f * keep + (f / sizes["rope_factor"]) * (1.0 - keep)


def rotary(x, freqs):
    """Interleaved rotary embedding over the whole last axis of ``x`` (B, T, H,
    D): dimension ``2i`` turns with ``2i + 1``, positions ``0 .. T-1``."""
    t = x.shape[1]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]  # (T, D/2)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape)


def score_scale(sizes: dict, faults=()) -> float:
    scale = (sizes["nope_dim"] + sizes["rope_dim"]) ** -0.5
    if sizes["rope_factor"] > 1.0 and "no_yarn_scale" not in faults:
        scale *= (0.1 * sizes["mscale_all_dim"] * math.log(sizes["rope_factor"]) + 1.0) ** 2
    return scale


def latent_attention(u, p, sizes, precision="f32", faults=()):
    """(the sub-block's output, the root mean square of attention's output per
    (row, head))."""
    b, t, _ = u.shape
    nh, dn, dr, dv = (sizes[k] for k in ("heads", "nope_dim", "rope_dim", "v_dim"))
    eps = sizes["eps"]
    c_q = rms_norm(_mm("bth,hk->btk", u, p["q_a"], precision, -1, 0), p["q_a_norm"], eps)
    q = _mm("btk,kd->btd", c_q, p["q_b"], precision, -1, 0).reshape(b, t, nh, dn + dr)
    latent = _mm("bth,hk->btk", u, p["kv_a"], precision, -1, 0)
    c_kv, k_r = latent[..., : sizes["kv_rank"]], latent[..., sizes["kv_rank"] :]
    kv = _mm("btk,kd->btd", rms_norm(c_kv, p["kv_a_norm"], eps), p["kv_b"], precision, -1, 0)
    kv = kv.reshape(b, t, nh, dn + dv)
    freqs = yarn_frequencies(sizes)
    k_r = rotary(k_r[:, :, None, :], freqs)
    if "no_rope_key" in faults:
        k_r = jnp.zeros_like(k_r)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], freqs)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (b, t, nh, dr))], axis=-1)
    v = kv[..., dn:]
    scale = score_scale(sizes, faults)
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def block(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        scores = _mm("bsnd,btnd->bnst", qs, k, precision, -1, -1) * scale
        causal = (start + jnp.arange(qb))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        return _mm("bnst,btnd->bsnd", probs, v, precision, -1, 1)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(0, t, qb))  # (blocks, B, qb, heads, dv)
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, nh, dv)
    out_rms = jnp.sqrt(jnp.mean(jnp.square(out), axis=(1, 3)))
    return _mm("btk,kh->bth", out.reshape(b, t, nh * dv), p["o"], precision, -1, 0), out_rms


def _swiglu_mlp(x, w1, w3, w2, precision):
    hid = jax.nn.silu(_mm("bth,hf->btf", x, w1, precision, -1, 0)) * _mm("bth,hf->btf", x, w3, precision, -1, 0)
    return _mm("btf,fh->bth", hid, w2, precision, -1, 0)


def route(u, p, sizes, faults=()):
    """The router: chosen experts (B, T, k) and their weights. float32 at
    ``highest`` whatever the precision (the router is float32 in the model)."""
    k = sizes["top_k"] - (1 if "top3" in faults else 0)
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
    return idx, sizes["route_scale"] * picked / (total + 1e-20)


def experts_mixer(u, p, sizes, precision="f32", faults=()):
    """(the layer's output, the chosen experts): a loop over the held experts
    (a ``lax.scan``: one body for the compiler, whatever their number), each
    run over every token and masked by its weight; the shared expert ungated."""
    idx, weights = route(u, p, sizes, faults)
    out = _swiglu_mlp(u, p["shared_w1"], p["shared_w3"], p["shared_w2"], precision)

    def add_expert(out, held):
        e, w1, w3, w2 = held
        w_e = jnp.sum(jnp.where(idx == sizes["held_start"] + e, weights, 0.0), axis=-1)
        return out + w_e[..., None] * _swiglu_mlp(u, w1, w3, w2, precision), None

    out, _ = jax.lax.scan(
        jax.checkpoint(add_expert), out, (jnp.arange(sizes["held"]), p["w1"], p["w3"], p["w2"]))
    return out, idx


# -- the model ------------------------------------------------------------------


def sub_block(x, p, kind, sizes, precision="f32", faults=()):
    """One hyper-connected sub-block on the streams ``x`` (B, T, n, hidden):
    ``(X', what it showed)``: the streams' root mean square (B, n) always, the
    experts chosen (``E``) or the size of attention's output (``L``)."""
    h_pre, h_post, h_res = hyper_maps(x, p["hc"], sizes, faults)
    u = rms_norm(jnp.sum(h_pre[..., None] * x, axis=2), p["norm"]["scale"], sizes["eps"])
    shown = {}
    if kind == "L":
        y, shown["mla_rms"] = latent_attention(u, p["mixer"], sizes, precision, faults)
    elif kind == "D":
        m = p["mixer"]
        y = _swiglu_mlp(u, m["w1"], m["w3"], m["w2"], precision)
    else:
        y, shown["routes"] = experts_mixer(u, p["mixer"], sizes, precision, faults)
    x = jnp.einsum("btij,btjh->btih", h_res, x, precision="highest") + h_post[..., None] * y[:, :, None, :]
    shown["stream_rms"] = jnp.sqrt(jnp.mean(jnp.square(x), axis=(1, 3)))
    return x, shown


def _layers(x, blocks, sizes, precision, faults, seen):
    """``x`` (B, T, hidden) replicated into the streams, through ``blocks`` =
    [(kind, parameters)], the streams summed; what the blocks showed is
    appended to ``seen``'s lists."""
    x = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (sizes["streams"],) + x.shape[2:])
    for kind, p in blocks:
        x, shown = jax.checkpoint(
            lambda x, p, kind=kind: sub_block(x, p, kind, sizes, precision, faults))(x, p)
        for name, value in shown.items():
            seen[name].append(value)
    return jnp.sum(x, axis=2)


def hidden_states(params, ids, sizes, precision="f32", faults=()):
    """(final-norm states (B, T, H), the multi-token-prediction module's normed
    states (B, T, H) or None, what the sub-blocks showed on the way, the
    module's last: ``routes`` the experts each expert layer chose (B, T, k),
    ``mla_rms`` (B, heads) a latent attention, ``stream_rms`` (B, n) a sub-block)."""
    seen = {"routes": [], "mla_rms": [], "stream_rms": []}
    pattern = pattern_of(sizes)
    embedding = params["embed"]["embedding"]
    h = _layers(embedding[ids], [(kind, params[f"h_{i}"]) for i, kind in enumerate(pattern)],
                sizes, precision, faults, seen)
    ahead = None
    if sizes["mtp_layers"]:
        m = params["mtp"]
        both = jnp.concatenate([
            rms_norm(embedding[jnp.roll(ids, -1, axis=1)], m["enorm"]["scale"], sizes["eps"]),
            rms_norm(h, m["hnorm"]["scale"], sizes["eps"]),
        ], axis=-1)
        g = _layers(_mm("btk,kh->bth", both, m["eh_proj"], precision, -1, 0),
                    [("L", m["h_0"]), ("E", m["h_1"])], sizes, precision, faults, seen)
        ahead = rms_norm(g, m["norm"]["scale"], sizes["eps"])
    hidden = rms_norm(h, params["norm_f"]["scale"], sizes["eps"])
    return hidden, ahead, {k: tuple(v) for k, v in seen.items()}


def logits_of(params, hidden, precision="f32"):
    return _mm("...h,hv->...v", hidden, params["lm_head"]["kernel"], precision, -1, 0)


def _cross_entropy(params, hidden, labels, precision, faults):
    """Mean over ``labels``' positions (their first half with ``half_batch``)."""
    logp = jax.nn.log_softmax(logits_of(params, hidden, precision), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if "half_batch" in faults:
        picked = picked[:, : picked.shape[1] // 2]
    return -jnp.mean(picked)


def lm_loss(params, ids, sizes, precision="f32", faults=(), with_shown=False):
    """``next-token + lambda x two-ahead`` cross-entropy, each a mean over its
    positions of every row; ``with_shown``: ``(loss, (what the sub-blocks
    showed, the two-ahead loss))``, for a gradient that hands all out of one
    program."""
    hidden, ahead, shown = hidden_states(params, ids, sizes, precision, faults)
    loss = _cross_entropy(params, hidden[:, :-1], ids[:, 1:], precision, faults)
    mtp_loss = jnp.zeros((), jnp.float32)
    if ahead is not None:
        mtp_loss = _cross_entropy(params, ahead[:, :-2], ids[:, 2:], precision, faults)
        loss = loss + (0.0 if "no_mtp" in faults else sizes["mtp_lambda"]) * mtp_loss
    return (loss, (shown, mtp_loss)) if with_shown else loss
