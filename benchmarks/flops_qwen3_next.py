"""Operations and bytes of the ``qwen3_next`` decoder's work, from shapes and from
the routed-row count.

What the algorithm needs, not what a compiler emitted: a multiply-add is two
operations, recomputation (per-block remat) does not count, and bytes are the
least a kernel must move through HBM. ``sizes`` is
``reference.qwen3_next.sizes_of(config)``. Every function here is checked
against a hand count in ``tests/test_flops_qwen3_next.py``.
"""

from __future__ import annotations


def kinds(sizes: dict) -> dict:
    """How many sub-blocks of each kind the layers hold: ``G`` Gated DeltaNet
    mixers, ``A`` attention mixers, ``E`` expert layers (one a layer)."""
    attention = sum(1 for i in range(sizes["layers"]) if (i + 1) % sizes["interval"] == 0)
    return {"G": sizes["layers"] - attention, "A": attention, "E": sizes["layers"]}


def dense_params(sizes: dict) -> int:
    """Weights that multiply EVERY token: the delta-rule mixers' three
    projections, attention's four (q twice as wide: the gate), each expert
    layer's router, shared expert (three matrices) and its gate, and the
    output head (the embedding lookup multiplies nothing; the convolution is
    counted with the scan). The routed experts multiply only the rows routed to
    them: :func:`routed_flops`."""
    h, n = sizes["hidden"], kinds(sizes)
    d_k = sizes["key_heads"] * sizes["key_dim"]
    d_v = sizes["value_heads"] * sizes["value_dim"]
    delta = h * (2 * d_k + 2 * d_v + 2 * sizes["value_heads"]) + d_v * h
    d_q, d_kv = sizes["heads"] * sizes["head_dim"], sizes["kv_heads"] * sizes["head_dim"]
    attention = h * (2 * d_q + 2 * d_kv) + d_q * h
    experts = h * sizes["experts"] + 3 * h * sizes["shared_width"] + h
    return n["G"] * delta + n["A"] * attention + n["E"] * experts + h * sizes["vocab"]


def gdn_scan_flops(sizes: dict, tokens: int) -> float:
    """The gated delta rule of ONE mixer over ``tokens`` tokens, forward, as the
    token-by-token recurrence counts it: per state element ``S <- exp(g) S`` is
    a multiply, ``S^T k`` a multiply-add, the rank-one update a multiply-add
    and ``S^T q`` a multiply-add: seven operations on each of value heads x
    key width x value width elements; and the depthwise convolution, two
    operations a tap and channel. (The chunked form the program runs spends
    about 2.4 x as many, on the MXU: the triangular inverse.)"""
    state = sizes["value_heads"] * sizes["key_dim"] * sizes["value_dim"]
    channels = 2 * sizes["key_heads"] * sizes["key_dim"] + sizes["value_heads"] * sizes["value_dim"]
    return tokens * (7.0 * state + 2.0 * sizes["conv"] * channels)


def gdn_scan_bytes(sizes: dict, tokens: int, itemsize: int) -> float:
    """Least HBM traffic of one mixer's delta rule forward: read q, k (key
    heads), v, write o (value heads), each once, and the two float32 vectors
    ``g`` and ``beta``; the state stays on the chip."""
    d_k = sizes["key_heads"] * sizes["key_dim"]
    d_v = sizes["value_heads"] * sizes["value_dim"]
    return tokens * ((2 * d_k + 2 * d_v) * itemsize + 2 * sizes["value_heads"] * 4)


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def routed_flops(sizes: dict, rows: float, backward: bool = True) -> float:
    """The routed experts' three matrix products over ``rows`` (token, choice)
    pairs: forward 3 x 2 x rows x hidden x width, three times that with the
    backward pass (each product's two gradients)."""
    forward = 3.0 * 2.0 * rows * sizes["hidden"] * sizes["expert_width"]
    return forward * (3.0 if backward else 1.0)


def routed_bytes(sizes: dict, rows: float, layer_steps: int, itemsize: int) -> float:
    """Least HBM traffic of the grouped products over ``layer_steps`` (expert
    layer, step) pairs that between them route ``rows`` pairs: nine kernel
    passes a pair (three forward, three for the rows' gradients, three for the
    weights'), each touching its stack of held weights once and moving the
    rows in and out (hidden wide on one side, the expert width on the other)."""
    h, f = sizes["hidden"], sizes["expert_width"]
    weights = sizes["held"] * h * f * itemsize
    return 9.0 * (layer_steps * weights + rows * (h + f) * itemsize)


def gmm_tile_pairs(rows_per_group, tile: int) -> int:
    """How many (group, row tile) pairs a grouped product over sorted rows
    visits: group ``i``'s rows follow group ``i - 1``'s, and it meets every
    tile of ``tile`` rows that holds one of them."""
    pairs, start = 0, 0.0
    for n in rows_per_group:
        if n > 0:
            pairs += -int(-(start + n) // tile) - int(start // tile)
        start += n
    return pairs


def forward_flops(sizes: dict, tokens: int, context_sum: int, routed_rows: float) -> float:
    """Forward operations for ``tokens`` positions that between them attend to
    ``context_sum`` (query, key) pairs in each attention mixer and send
    ``routed_rows`` (token, choice) pairs through experts held here."""
    n = kinds(sizes)
    dense = 2.0 * dense_params(sizes) * tokens
    scan = n["G"] * gdn_scan_flops(sizes, tokens)
    attention = 4.0 * n["A"] * sizes["heads"] * sizes["head_dim"] * context_sum
    return dense + scan + attention + routed_flops(sizes, routed_rows, backward=False)


def train_flops(sizes: dict, rows: int, seq: int, routed_rows: float) -> float:
    """Forward + backward (= 3 x forward) of one step of ``rows`` causal rows of
    ``seq`` tokens whose expert layers routed ``routed_rows`` pairs in all."""
    return 3.0 * forward_flops(sizes, rows * seq, rows * causal_pairs(seq), routed_rows)


def attention_flops(sizes: dict, rows: int, seq: int, backward: bool) -> float:
    """One attention mixer's causal attention over ``rows`` rows: forward
    4 x heads x head_dim operations a pair; the backward pass needs 2.5 x the
    forward's (dQ, dK, dV, and dP through P)."""
    forward = 4.0 * sizes["heads"] * sizes["head_dim"] * rows * causal_pairs(seq)
    return forward * (2.5 if backward else 1.0)


def attention_bytes(sizes: dict, rows: int, seq: int, itemsize: int, backward: bool) -> float:
    """Least HBM traffic of one mixer's attention kernels, K and V as the
    kernels see them (repeated to the query heads): read q, k, v and write o
    forward; backward reads q, k, v, o, do and writes dq, dk, dv."""
    tensor = rows * seq * sizes["heads"] * sizes["head_dim"] * itemsize
    return tensor * (8 if backward else 4)
