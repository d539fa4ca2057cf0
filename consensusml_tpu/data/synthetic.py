"""Procedural datasets with MNIST/CIFAR shapes.

Class-prototype + noise classification: class k's images cluster around a
fixed random prototype, so a small model reaches high accuracy quickly —
ideal for convergence smoke tests (the reference's MNIST role, SURVEY.md
§4) while requiring zero network access.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SyntheticClassification", "round_batches", "SyntheticLM", "lm_round_batches"]


@dataclasses.dataclass
class SyntheticClassification:
    """Deterministic synthetic classification dataset, sharded by worker."""

    # the u8-wire quant affine for this data family: prototypes+noise are
    # ~N(0,1)-scale, so u8 = clip((x + 4) * 32) covers [-4, 4). The ONE
    # source of truth for every u8 consumer of synthetic images (configs'
    # native closures, train.py's device-side dequant).
    U8_QSCALE = 32.0
    U8_QOFF = 4.0

    n: int = 8192
    image_shape: tuple[int, ...] = (28, 28, 1)
    classes: int = 10
    noise: float = 0.35
    seed: int = 0
    # None => samples come from the prototype rng stream (training split).
    # An int selects an independent sample stream over the SAME prototypes
    # — a held-out split of the same task (see holdout()).
    sample_seed: int | None = None

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self.prototypes = rng.normal(size=(self.classes, *self.image_shape)).astype(
            np.float32
        )
        if self.sample_seed is not None:
            rng = np.random.default_rng((self.seed, self.sample_seed))
        self.labels = rng.integers(0, self.classes, size=self.n).astype(np.int32)
        self.images = (
            self.prototypes[self.labels]
            + self.noise * rng.normal(size=(self.n, *self.image_shape))
        ).astype(np.float32)

    def holdout(self, n: int | None = None) -> "SyntheticClassification":
        """Held-out split: same class prototypes, disjoint sample stream."""
        return dataclasses.replace(
            self, n=n or self.n, sample_seed=(self.sample_seed or 0) + 1
        )

    def worker_shard(self, rank: int, world_size: int) -> tuple[np.ndarray, np.ndarray]:
        """Disjoint contiguous shard for one worker (reference-style DP
        partitioning)."""
        per = self.n // world_size
        lo = rank * per
        return self.images[lo : lo + per], self.labels[lo : lo + per]

    def eval_batch(self, size: int = 1024) -> dict[str, jnp.ndarray]:
        return {
            "image": jnp.asarray(self.images[:size]),
            "label": jnp.asarray(self.labels[:size]),
        }


@dataclasses.dataclass
class SyntheticLM:
    """Procedural token streams with learnable structure.

    Sequences follow a fixed random Markov chain over the vocab (worker-
    sharded by seeding), so causal/masked LMs can demonstrably reduce loss
    without any downloaded corpus.
    """

    vocab_size: int = 256
    seq_len: int = 128
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        # sparse-ish transition table: each token has 4 likely successors.
        # The last vocab id is RESERVED (never emitted by the chain) so it
        # can serve as an unambiguous [MASK] token for MLM corruption.
        succ = rng.integers(0, self.vocab_size - 1, size=(self.vocab_size, 4))
        self.successors = succ.astype(np.int32)

    @property
    def mask_token(self) -> int:
        """Reserved id never produced by the chain."""
        return self.vocab_size - 1

    def sample(self, rng: np.random.Generator, shape: tuple[int, ...]) -> np.ndarray:
        """Sample token id sequences of shape (*shape, seq_len)."""
        n = int(np.prod(shape))
        out = np.empty((n, self.seq_len), np.int32)
        state = rng.integers(0, self.vocab_size - 1, size=n)
        for t in range(self.seq_len):
            out[:, t] = state
            choice = rng.integers(0, 4, size=n)
            state = self.successors[state, choice]
        return out.reshape(*shape, self.seq_len)


def mlm_corrupt(
    ids: np.ndarray,
    dataset: SyntheticLM,
    seed: int,
    r: int,
    mlm_rate: float,
    mask_token: int | None = None,
) -> dict:
    """BERT-style corruption of a round's token block, keyed (seed, round).

    Shared by the Python and native loader paths so the two streams stay
    bit-identical for the same (seed, round)."""
    rng = np.random.default_rng((seed, r, 10**6))
    mask = rng.random(ids.shape) < mlm_rate
    mtok = dataset.mask_token if mask_token is None else mask_token
    corrupted = np.where(mask, mtok, ids)
    return {
        "input_ids": jnp.asarray(corrupted, jnp.int32),
        "labels": jnp.asarray(ids, jnp.int32),
        "mlm_mask": jnp.asarray(mask, jnp.float32),
    }


def lm_round_batches(
    dataset: SyntheticLM,
    world_size: int,
    h: int,
    batch: int,
    rounds: int,
    seed: int = 0,
    mlm_rate: float = 0.0,
    mask_token: int | None = None,
    start: int = 0,
):
    """Stacked (W, H, B, S) LM round batches; ``mlm_rate > 0`` yields
    BERT-style corrupted inputs + labels + mlm_mask.

    Batches are keyed by (seed, absolute round, rank), so resuming with
    ``start=N`` continues the EXACT stream a fresh run would have produced
    at round N (checkpoint/resume correctness)."""
    for r in range(start, start + rounds):
        per_worker = []
        for rank in range(world_size):
            rng = np.random.default_rng((seed, r, rank))
            per_worker.append(dataset.sample(rng, (h, batch)))
        ids = np.stack(per_worker)  # (W, H, B, S)
        if mlm_rate <= 0:
            yield {"input_ids": jnp.asarray(ids)}
        else:
            yield mlm_corrupt(ids, dataset, seed, r, mlm_rate, mask_token)


def round_batches(
    dataset: SyntheticClassification,
    world_size: int,
    h: int,
    batch: int,
    rounds: int,
    seed: int = 0,
    start: int = 0,
) -> Iterator[dict[str, jnp.ndarray]]:
    """Yield ``rounds`` stacked round-batches of shape ``(W, H, B, ...)``.

    Every worker samples uniformly (with replacement) from its OWN shard —
    workers see disjoint data, which is what makes their replicas drift and
    gives the consensus step something to do. Batches are keyed by
    (seed, absolute round), so ``start=N`` resumes the exact stream.
    """
    shards = [dataset.worker_shard(r, world_size) for r in range(world_size)]
    for rnd in range(start, start + rounds):
        rng = np.random.default_rng((seed, rnd))
        imgs = np.empty(
            (world_size, h, batch, *dataset.image_shape), np.float32
        )
        labs = np.empty((world_size, h, batch), np.int32)
        for r, (x, y) in enumerate(shards):
            idx = rng.integers(0, len(x), size=(h, batch))
            imgs[r] = x[idx]
            labs[r] = y[idx]
        yield {"image": jnp.asarray(imgs), "label": jnp.asarray(labs)}
