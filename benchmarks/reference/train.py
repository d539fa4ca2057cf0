"""Plain local-SGD rounds for the training cells: Adam, chunked top-k + int8.

What one worker of the gossip recipe does, written down without the program:
``h`` Adam steps on the round's rows, then the CHOCO tracking update
``xhat += C(x - xhat)`` with ``C`` = keep the 8 largest of every 512, int8 the
kept values. With one worker the mix ``x += gamma * (s - xhat)`` is zero
(``s = xhat``), so the parameters follow Adam alone; the tracked copy is what
the codec leaves. Gradients are taken in blocks of rows so that float32 at
``highest`` fits beside the optimizer state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import gpt2


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def make_grad_fn(sizes: dict, precision: str):
    return _grad_fn(tuple(sorted(sizes.items())), precision)


@functools.lru_cache(maxsize=None)
def _grad_fn(sizes_items: tuple, precision: str):
    """One jitted function per (sizes, precision), so that following several
    seeds in one process traces each once."""
    sizes = dict(sizes_items)

    def loss_and_grad(params, rows):
        return jax.value_and_grad(lambda p: gpt2.lm_loss(p, rows, sizes, precision))(params)

    return jax.jit(loss_and_grad)


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _tree_div(tree, n):
    return jax.tree.map(lambda g: g / n, tree)


def batch_grad(grad_fn, params, rows, block_rows: int):
    """Mean loss and gradient over ``rows`` (B, S), ``block_rows`` at a time."""
    blocks = range(0, rows.shape[0], block_rows)
    total_loss, total = 0.0, None
    for start in blocks:
        loss, grads = grad_fn(params, rows[start : start + block_rows])
        total_loss += float(loss)
        total = grads if total is None else _tree_add(total, grads)
    n = len(blocks)
    return total_loss / n, _tree_div(total, jnp.float32(n))


@jax.jit
def adam_step(params, grads, mu, nu, count, lr, b1, b2, eps):
    """Adam as published (Kingma & Ba 2015), bias-corrected, no weight decay."""
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), params, mu, nu
    )
    return params, mu, nu, count


@functools.partial(jax.jit, static_argnames=("chunk", "k", "top"))
def topk_int8_norm(tree, chunk: int, k: int, top: float = 127.0):
    """Norm of C(x): each tensor flattened and padded to a multiple of
    ``chunk``, the ``k`` largest magnitudes of every ``chunk`` kept, the kept
    values rounded to int8 with one scale per ``chunk`` of them. ``top`` = 7
    rounds to int4 instead: the codec's lower-precision control."""
    kept = []
    for x in jax.tree.leaves(tree):
        flat = x.reshape(-1).astype(jnp.float32)
        flat = jnp.pad(flat, (0, (-flat.shape[0]) % chunk))
        kept.append(jax.lax.top_k(jnp.abs(flat).reshape(-1, chunk), k)[0].reshape(-1))
    kept = jnp.concatenate(kept)
    groups = jnp.pad(kept, (0, (-kept.shape[0]) % chunk)).reshape(-1, chunk)
    scale = jnp.max(groups, axis=1, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.sqrt(jnp.sum(jnp.square(jnp.round(groups / scale) * scale)))


def by_reference_leaves(tree):
    """The tree with every fused ``qkv`` tensor split into its q, k and v
    thirds (the last axis is per head [q|k|v]). Norms are compared by these
    leaves: a key's bias has no gradient under softmax, and inside the fused
    tensor it would hide in the norm of the other two."""
    out = {}
    for name, sub in tree.items():
        if name == "qkv":
            for leaf, x in sub.items():
                d = x.shape[-1] // 3
                for i, part in enumerate("qkv"):
                    out[f"{part}_{leaf}"] = x[..., i * d : (i + 1) * d]
        elif isinstance(sub, dict):
            out[name] = by_reference_leaves(sub)
        else:
            out[name] = sub
    return out


def leaf_norms_split(tree):
    return leaf_norms(by_reference_leaves(tree))


def follow(params, rounds_rows, sizes: dict, recipe: dict, precision: str = "f32",
           block_rows: int = 2, faults: tuple = ()):
    """Follow ``len(rounds_rows)`` rounds; each entry is (h, B, S) int32 rows.

    Returns per-round mean losses, the leaf norms of the first gradient,
    Adam's first moment after round 1 and its leaf norms, the norm of C(x)
    after round 1, and the leaf norms of the parameters' change after the last
    round. ``faults``
    plants step 3's faults in the reference put in the program's place:
    ``"half_batch"`` (the mean over the first half of the rows only);
    ``"codec_int4"`` reads the int4 norm beside the int8 one.
    """
    grad_fn = make_grad_fn(sizes, precision)
    lr, b1, b2, eps = (recipe[k] for k in ("learning_rate", "adam_b1", "adam_b2", "adam_eps"))
    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    out = {"loss": []}
    for r, rows_h in enumerate(rounds_rows):
        losses = []
        for i in range(rows_h.shape[0]):
            rows = jnp.asarray(rows_h[i])
            if "half_batch" in faults:
                rows = rows[: rows.shape[0] // 2]
            loss, grads = batch_grad(grad_fn, params, rows, block_rows)
            if r == 0 and i == 0:
                out["grad_norms"] = leaf_norms_split(grads)
            params, mu, nu, count = adam_step(params, grads, mu, nu, count, lr, b1, b2, eps)
            losses.append(loss)
        out["loss"].append(sum(losses) / len(losses))
        if r == 0:
            out["mu"] = jax.device_get(mu)  # on the host: the later rounds need the device's room
            out["mu_norms"] = leaf_norms_split(mu)
            chunk, k = recipe["codec_chunk"], recipe["codec_k"]
            out["track_norm"] = float(topk_int8_norm(params, chunk, k))
            if "codec_int4" in faults:  # read beside, it changes nothing else
                out["track_norm_int4"] = float(topk_int8_norm(params, chunk, k, top=7.0))
    out["delta_norms"] = leaf_norms_split(jax.tree.map(jnp.subtract, params, start))
    return out


@jax.jit
def leaf_diff_norms(program, reference):
    """Per leaf ‖program - reference‖, the tensors themselves compared."""
    return leaf_norms_split(jax.tree.map(jnp.subtract, program, reference))


def worst_leaf_share(diff_norms, reference_norms) -> float:
    """The worst leaf's ‖program - reference‖ over max(‖reference‖ of the
    leaf, of the median leaf)."""
    import numpy as np

    diff = np.asarray([float(x) for x in jax.tree.leaves(diff_norms)])
    ref = np.asarray([float(x) for x in jax.tree.leaves(reference_norms)])
    return float((diff / np.maximum(ref, np.median(ref))).max())


def leaf_gaps(program, reference, keep=None):
    """Per leaf |‖program‖ - ‖reference‖| over max(‖reference‖ of the leaf, of
    the median leaf); ``keep`` masks leaves out."""
    import numpy as np

    prog = np.asarray([float(x) for x in jax.tree.leaves(program)])
    ref = np.asarray([float(x) for x in jax.tree.leaves(reference)])
    gap = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
    return gap if keep is None else gap[np.asarray(keep)]


def worst_leaf_gap(program, reference, keep=None) -> float:
    return float(leaf_gaps(program, reference, keep).max())


def mean_leaf_gap(program, reference, keep=None) -> float:
    """The same gaps, averaged: steady from seed to seed where the worst leaf
    is one small tensor's noise."""
    return float(leaf_gaps(program, reference, keep).mean())
