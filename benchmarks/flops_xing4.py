"""Operations and bytes of the ``xing4`` decoder's work, from shapes and from the
routed-row count.

What the algorithm needs, not what a compiler emitted: a multiply-add is two
operations, recomputation (per-block remat, the backward kernels' second look at
the scores) does not count, and bytes are the least a kernel must move through
HBM. ``sizes`` is ``reference.xing4.sizes_of(config)``. Every function here is
checked against a hand count in ``tests/test_flops_xing4.py``.
"""

from __future__ import annotations

# the same work under the same keys (hidden, expert_width, held): the grouped products of the held
# experts, the (group, row tile) pairs they visit, a causal row's (query, key) pairs
from flops_qwen3_next import causal_pairs, gmm_tile_pairs, routed_bytes, routed_flops  # noqa: F401


def kinds(sizes: dict) -> dict:
    """How many sub-blocks of each kind run a step: ``L`` latent attentions (one
    a layer), ``D`` dense MLPs, ``E`` expert layers; the multi-token-prediction
    module adds one ``L`` and one ``E`` a depth."""
    mtp = sizes["mtp_layers"]
    return {
        "L": sizes["layers"] + mtp,
        "D": sizes["dense_layers"],
        "E": sizes["layers"] - sizes["dense_layers"] + mtp,
    }


def sub_blocks(sizes: dict) -> int:
    return sum(kinds(sizes).values())


def latent_params(sizes: dict) -> int:
    """One latent attention's five matrices and two latent norms."""
    h, nh = sizes["hidden"], sizes["heads"]
    dn, dr, dv, qr, kr = (sizes[k] for k in ("nope_dim", "rope_dim", "v_dim", "q_rank", "kv_rank"))
    return h * qr + qr * nh * (dn + dr) + h * (kr + dr) + kr * nh * (dn + dv) + nh * dv * h + qr + kr


def hyper_params(sizes: dict) -> int:
    """One sub-block's maps: ``phi``, the biases and the three gates."""
    n = sizes["streams"]
    return (n * sizes["hidden"] + 1) * (2 * n + n * n) + 3


def expert_params(sizes: dict) -> int:
    """One SwiGLU expert of the routed width (the shared expert's, at its)."""
    return 3 * sizes["hidden"] * sizes["expert_width"]


def total_params(sizes: dict) -> int:
    """Every parameter the share holds (the program's ``init`` and the
    reference's count the same)."""
    h, n = sizes["hidden"], kinds(sizes)
    per_block = hyper_params(sizes) + h  # its maps and its norm
    layer_l = latent_params(sizes) + per_block
    layer_d = 3 * h * sizes["dense_width"] + per_block
    layer_e = (sizes["held"] * expert_params(sizes) + 3 * h * sizes["shared_width"]
               + h * sizes["experts"] + per_block)
    module = sizes["mtp_layers"] * (2 * h * h + 3 * h)
    return (n["L"] * layer_l + n["D"] * layer_d + n["E"] * layer_e + module
            + 2 * h * sizes["vocab"] + h)


def dense_params(sizes: dict) -> int:
    """Weights that multiply EVERY token: each latent attention's five
    matrices, the dense MLP's three, each expert layer's router and shared
    expert, every sub-block's maps ``phi``, the module's ``eh_proj`` and the
    head, once for each loss it serves (the embedding lookup multiplies
    nothing). The routed experts multiply only the rows routed to them:
    :func:`routed_flops`."""
    h, n, s = sizes["hidden"], kinds(sizes), sizes["streams"]
    latent = latent_params(sizes) - sizes["q_rank"] - sizes["kv_rank"]
    dense = 3 * h * sizes["dense_width"]
    experts = h * sizes["experts"] + 3 * h * sizes["shared_width"]
    maps = s * h * (2 * s + s * s)
    mtp = sizes["mtp_layers"]
    return (n["L"] * latent + n["D"] * dense + n["E"] * experts + sub_blocks(sizes) * maps
            + mtp * 2 * h * h + (1 + mtp) * h * sizes["vocab"])


def mixing_flops(sizes: dict, tokens: int) -> float:
    """The streams' elementwise mixing of ONE sub-block, forward: ``H_pre X``
    (n multiply-adds a hidden element), ``H_res X`` (n^2) and ``H_post^T y`` (n)."""
    n = sizes["streams"]
    return 2.0 * tokens * sizes["hidden"] * (n * n + 2 * n)


def attention_flops(sizes: dict, rows: int, seq: int, backward: bool) -> float:
    """One latent attention's causal attention over ``rows`` rows, keys ``d_k =
    nope + rope`` wide and values ``d_v``: forward ``q k^T`` and ``p v``, 2 (d_k +
    d_v) operations a (query, key) pair and head; backward the scores again,
    dV, dP, dQ and dK: 2 (3 d_k + 2 d_v)."""
    d_k, d_v = sizes["nope_dim"] + sizes["rope_dim"], sizes["v_dim"]
    per_pair = 2.0 * (3 * d_k + 2 * d_v) if backward else 2.0 * (d_k + d_v)
    return per_pair * sizes["heads"] * rows * causal_pairs(seq)


def attention_bytes(sizes: dict, rows: int, seq: int, itemsize: int, backward: bool) -> float:
    """Least HBM traffic of one latent attention's kernels, K and V as the
    kernels see them (per head): forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv."""
    d_k, d_v = sizes["nope_dim"] + sizes["rope_dim"], sizes["v_dim"]
    widths = 4 * d_k + 4 * d_v if backward else 2 * d_k + 2 * d_v
    return float(rows * seq * sizes["heads"] * widths * itemsize)


def residual_bytes(sizes: dict, tokens: int, itemsize: int) -> float:
    """Least HBM traffic of the residual path over ONE step: each sub-block
    reads the streams ``X`` and writes ``X'`` once forward, and reads ``dX'`` and
    writes ``dX`` once backward; the maps and ``y`` are a 24th and a 4th of
    that and are left out. ``itemsize`` is the streams'."""
    return 4.0 * sub_blocks(sizes) * tokens * sizes["streams"] * sizes["hidden"] * itemsize


def forward_flops(sizes: dict, tokens: int, context_sum: int, routed_rows: float) -> float:
    """Forward operations for ``tokens`` positions that between them attend to
    ``context_sum`` (query, key) pairs in each latent attention and send
    ``routed_rows`` (token, choice) pairs through experts held here."""
    d_k, d_v = sizes["nope_dim"] + sizes["rope_dim"], sizes["v_dim"]
    dense = 2.0 * dense_params(sizes) * tokens
    attention = 2.0 * (d_k + d_v) * kinds(sizes)["L"] * sizes["heads"] * context_sum
    mixing = sub_blocks(sizes) * mixing_flops(sizes, tokens)
    return dense + attention + mixing + routed_flops(sizes, routed_rows, backward=False)


def train_flops(sizes: dict, rows: int, seq: int, routed_rows: float) -> float:
    """Forward + backward (= 3 x forward) of one step of ``rows`` causal rows of
    ``seq`` tokens whose expert layers routed ``routed_rows`` pairs in all."""
    return 3.0 * forward_flops(sizes, rows * seq, rows * causal_pairs(seq), routed_rows)
