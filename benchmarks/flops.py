"""Operations and bytes of GPT-2's work, from shapes alone.

What the algorithm needs, not what a compiler emitted: a multiply-add is two
operations, recomputation does not count, and bytes are the least a kernel
must move through HBM. ``sizes`` is ``reference.gpt2.sizes_of(config)``.
Every function here is checked against a hand count in ``tests/``.
"""

from __future__ import annotations


def matmul_params(sizes: dict) -> int:
    """Weights that multiply every token: the blocks' four matrices and the
    tied output head (the embedding lookups multiply nothing)."""
    h, m, n, v = sizes["hidden"], sizes["mlp"], sizes["layers"], sizes["vocab"]
    return n * (3 * h * h + h * h + 2 * h * m) + v * h


def forward_flops(sizes: dict, tokens: int, context_sum: int) -> float:
    """Forward operations for ``tokens`` positions that between them attend to
    ``context_sum`` (query, key) pairs; every position goes through the head."""
    h, n = sizes["hidden"], sizes["layers"]
    dense = 2.0 * matmul_params(sizes) * tokens
    attention = 4.0 * n * h * context_sum  # QK^T and PV, 2*h operations a pair each
    return dense + attention


def causal_pairs(seq: int) -> int:
    """(query, key) pairs of one causal sequence of ``seq`` tokens."""
    return seq * (seq + 1) // 2


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward + backward (= 3 x forward) for one token of a ``seq``-token
    causal row, the attention at its causal half."""
    return 3.0 * forward_flops(sizes, seq, causal_pairs(seq)) / seq


def attention_flops(sizes: dict, rows: int, seq: int, backward: bool) -> float:
    """One layer's causal attention over ``rows`` rows: forward 4*h operations
    a pair; the backward pass recomputes nothing that counts and needs
    2.5 x the forward's (dQ, dK, dV, and dP through P)."""
    pairs = rows * causal_pairs(seq)
    forward = 4.0 * sizes["hidden"] * pairs
    return forward * (2.5 if backward else 1.0)


def attention_bytes(sizes: dict, rows: int, seq: int, itemsize: int, backward: bool) -> float:
    """Least HBM traffic of one layer's attention kernel: read q, k, v and
    write o forward; backward reads q, k, v, o, do and writes dq, dk, dv."""
    tensor = rows * seq * sizes["hidden"] * itemsize
    return tensor * (8 if backward else 4)


def codec_bytes(params: int, chunk: int, k: int) -> float:
    """Chunked top-k + int8 encode and scatter decode over ``params`` f32
    values: read the residual once to select, write k of every ``chunk`` as
    int8 with an index and a scale per chunk, read them back and
    read-modify-write the tracked copy at the kept places."""
    chunks = params / chunk
    kept = chunks * k
    encode = 4.0 * params + kept * (1 + 4) + 4.0 * chunks
    decode = kept * (1 + 4) + 4.0 * chunks + 2 * 4.0 * params
    return encode + decode
