"""The one traffic generator: a traffic file's parameters -> the work of a run.

A traffic mix is data (``traffic/<cell>.json``); this module is the only code
that turns it into work. One kind so far:

``train_rounds`` (training) — token batches for the rounds of a window, rows
from a first-order Markov chain over the vocabulary so that every row differs;
round ``r``'s rows are a pure function of (``--seed``, ``r``).

A serving kind (open-loop arrivals) comes with the first serving cell.
Nothing here imports JAX or the program.
"""

from __future__ import annotations

import numpy as np

SEED_MASK = 0xFFFFFFFF


def markov_rows(rng, succ: np.ndarray, rows: int, seq: int) -> np.ndarray:
    """(rows, seq) int32 tokens: each row its own walk over the successor
    table ``succ`` (vocab, 4), so rows all differ and there is something to
    learn."""
    out = np.empty((rows, seq), np.int32)
    out[:, 0] = rng.integers(0, succ.shape[0], size=rows)
    pick = rng.integers(0, succ.shape[1], size=(rows, seq))
    for t in range(1, seq):
        out[:, t] = succ[out[:, t - 1], pick[:, t]]
    return out


def successor_table(seed: int, vocab: int) -> np.ndarray:
    """The seed's Markov chain: four successors for each token."""
    return np.random.default_rng(int(seed) & SEED_MASK).integers(0, vocab, size=(vocab, 4))


def train_round(seed: int, succ: np.ndarray, rnd: int, shape: tuple) -> np.ndarray:
    """Round ``rnd``'s tokens, ``shape`` = (workers, h, batch, seq): a pure
    function of (seed, round), so the reference reads the rows the program
    was fed."""
    rng = np.random.default_rng([int(seed) & SEED_MASK, int(rnd) + 1])
    workers, h, batch, seq = shape
    return markov_rows(rng, succ, workers * h * batch, seq).reshape(shape)
