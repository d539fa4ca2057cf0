"""Operations and bytes of the hybrid (``nemotron_h``) decoder's work, from
shapes and from the routed-row count.

What the algorithm needs, not what a compiler emitted: a multiply-add is two
operations, recomputation (per-block remat) does not count, and bytes are the
least a kernel must move through HBM. ``sizes`` is
``reference.nemotron_h.sizes_of(config)``. Every function here is checked
against a hand count in ``tests/test_flops_nemotron_h.py``.
"""

from __future__ import annotations


def kinds(sizes: dict) -> dict:
    """How many blocks of each kind the pattern holds."""
    return {kind: sizes["pattern"].count(kind) for kind in "ME*"}


def dense_params(sizes: dict) -> int:
    """Weights that multiply EVERY token: the Mamba-2 blocks' two projections,
    attention's four, each expert block's router and shared expert, and the
    output head (the embedding lookup multiplies nothing). The routed experts
    multiply only the rows routed to them: :func:`routed_flops`."""
    h, n = sizes["hidden"], kinds(sizes)
    d_in = sizes["m_heads"] * sizes["m_head_dim"]
    gn = sizes["groups"] * sizes["state"]
    mamba = h * (2 * d_in + 2 * gn + sizes["m_heads"]) + d_in * h
    d_q, d_kv = sizes["heads"] * sizes["head_dim"], sizes["kv_heads"] * sizes["head_dim"]
    attention = h * (d_q + 2 * d_kv) + d_q * h
    experts = h * sizes["experts"] + 2 * h * sizes["shared_width"]
    return n["M"] * mamba + n["*"] * attention + n["E"] * experts + h * sizes["vocab"]


def scan_flops_per_token(sizes: dict) -> float:
    """The state-space recurrence of one Mamba-2 block for one token, forward:
    per state element ``S = a S + (dt x) B`` is a multiply, a multiply and an
    add, and ``y = S C`` a multiply-add: five operations on each of
    heads x head_dim x state elements. (The chunked dual form the program runs
    spends about as many, on the MXU.)"""
    return 5.0 * sizes["m_heads"] * sizes["m_head_dim"] * sizes["state"]


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def routed_flops(sizes: dict, rows: float, backward: bool = True) -> float:
    """The routed experts' two matrix products over ``rows`` (token, choice)
    pairs: forward 2 x 2 x rows x hidden x width, three times that with the
    backward pass (each product's two gradients)."""
    forward = 2.0 * 2.0 * rows * sizes["hidden"] * sizes["expert_width"]
    return forward * (3.0 if backward else 1.0)


def routed_bytes(sizes: dict, rows: float, layer_steps: int, itemsize: int) -> float:
    """Least HBM traffic of the grouped products over ``layer_steps`` (expert
    block, step) pairs that between them route ``rows`` pairs: six kernel passes
    a pair (two forward, two for the rows' gradients, two for the weights'),
    each touching its stack of held weights once and moving the rows in and
    out (hidden wide on one side, the expert width on the other)."""
    h, f = sizes["hidden"], sizes["expert_width"]
    weights = sizes["held"] * h * f * itemsize
    return 6.0 * (layer_steps * weights + rows * (h + f) * itemsize)


def forward_flops(sizes: dict, tokens: int, context_sum: int, routed_rows: float) -> float:
    """Forward operations for ``tokens`` positions that between them attend to
    ``context_sum`` (query, key) pairs in each attention block and send
    ``routed_rows`` (token, choice) pairs through experts held here."""
    n = kinds(sizes)
    dense = 2.0 * dense_params(sizes) * tokens
    scan = n["M"] * scan_flops_per_token(sizes) * tokens
    attention = 4.0 * n["*"] * sizes["heads"] * sizes["head_dim"] * context_sum
    return dense + scan + attention + routed_flops(sizes, routed_rows, backward=False)


def train_flops(sizes: dict, rows: int, seq: int, routed_rows: float) -> float:
    """Forward + backward (= 3 x forward) of one step of ``rows`` causal rows of
    ``seq`` tokens whose expert blocks routed ``routed_rows`` pairs in all."""
    return 3.0 * forward_flops(sizes, rows * seq, rows * causal_pairs(seq), routed_rows)


def attention_flops(sizes: dict, rows: int, seq: int, backward: bool) -> float:
    """One attention block's causal attention over ``rows`` rows: forward
    4 x heads x head_dim operations a pair; the backward pass needs 2.5 x the
    forward's (dQ, dK, dV, and dP through P)."""
    forward = 4.0 * sizes["heads"] * sizes["head_dim"] * rows * causal_pairs(seq)
    return forward * (2.5 if backward else 1.0)


def attention_bytes(sizes: dict, rows: int, seq: int, itemsize: int, backward: bool) -> float:
    """Least HBM traffic of one block's attention kernels, K and V as the
    kernels see them (repeated to the query heads): read q, k, v and write o
    forward; backward reads q, k, v, o, do and writes dq, dk, dv."""
    tensor = rows * seq * sizes["heads"] * sizes["head_dim"] * itemsize
    return tensor * (8 if backward else 4)
