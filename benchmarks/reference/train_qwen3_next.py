"""Plain local-SGD rounds for the ``qwen3_next`` training cells: Adam, exact
gossip.

What one worker of the recipe does, written down without the program: ``h`` Adam
steps on the round's rows, the step size warmed up linearly over the recipe's
``warmup_steps`` (step ``k``, counted from 0, takes ``learning_rate * k / warmup_steps``). With one worker exact gossip is the identity mix
(``W = [1]``), so the parameters follow Adam alone and there is no tracking
state to compare. ``reference/train_nemotron_h.py``'s ``follow`` in this
model's name (that file's is bound to its own model module and may not be
edited here); the Adam step, norms and leaf comparisons are
``reference/train.py``'s, the routes' comparison ``train_nemotron_h.py``'s. A
row here is 8,192 tokens and a step one row, so gradients are taken over the
whole step at once; Adam's step donates the state it replaces, and the second
moment waits on the host while a gradient is taken (626M parameters in float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import qwen3_next as model
from reference.train import adam_step, leaf_norms
from reference.train_nemotron_h import routing_disagreement  # noqa: F401 (the driver reads it here)

# the same arithmetic, the parameters and both moments updated in place
_adam_step = jax.jit(adam_step.__wrapped__, donate_argnums=(0, 2, 3))


@functools.lru_cache(maxsize=None)
def _grad_fn(sizes_items: tuple, precision: str, faults: tuple):
    """One jitted ((loss, routes and delta-rule outputs), gradient) function per
    (sizes, precision, faults): several seeds in one process trace it once, and
    what the first step shows rides out of the gradient's own program (the
    reference at this size takes a minute and a half to compile)."""
    sizes = dict(sizes_items)
    return jax.jit(jax.value_and_grad(
        lambda p, rows: model.lm_loss(p, rows, sizes, precision, faults, with_shown=True),
        has_aux=True))


def follow(params, rounds_rows, sizes: dict, recipe: dict, precision: str = "f32",
           faults: tuple = ()):
    """Follow ``len(rounds_rows)`` rounds; each entry is (h, B, S) int32 rows.
    ``params`` IS consumed: Adam's first step updates it in place, so the
    caller makes it for this call and keeps no other use of it.

    Returns per-round mean losses, the leaf norms of the first gradient, the
    experts every expert layer chose for the first step's tokens (``routes``) and
    the per-head root mean square of every delta rule's output there
    (``gdn_rms``), Adam's first
    moment after round 1 (on the host) and its leaf norms, and the leaf norms of
    the parameters' change after the last round. ``faults`` (``half_batch``,
    ``top9``, ``renorm_over_held``, ``no_state_carry``, ``no_delta``,
    ``no_attn_gate``) are the model's: ``reference/qwen3_next.py``."""
    faults = tuple(sorted(faults))
    grad_fn = _grad_fn(tuple(sorted(sizes.items())), precision, faults)
    peak, b1, b2, eps = (recipe[k] for k in ("learning_rate", "adam_b1", "adam_b2", "adam_eps"))
    warmup = int(recipe.get("warmup_steps", 0))
    steps = 0  # Adam steps taken: the step size warms up linearly, the first step's is 0
    start = jax.device_get(params)  # on the host: the device holds one generation
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    out = {"loss": []}
    for r, rows_h in enumerate(rounds_rows):
        losses = []
        for i in range(rows_h.shape[0]):
            rows = jnp.asarray(rows_h[i])
            nu = jax.device_get(nu)  # on the host while the gradient's workspace is live
            (loss, shown), grads = grad_fn(params, rows)
            if r == 0 and i == 0:
                out.update(jax.tree.map(np.asarray, shown))
                out["grad_norms"] = leaf_norms(grads)
            del shown
            lr = peak * min(steps, warmup) / warmup if warmup else peak
            params, mu, nu, count = _adam_step(params, grads, mu, nu, count, lr, b1, b2, eps)
            steps += 1
            del grads
            losses.append(float(loss))
        out["loss"].append(sum(losses) / len(losses))
        if r == 0:
            out["mu"] = jax.device_get(mu)
            out["mu_norms"] = leaf_norms(mu)
    del mu, nu
    out["delta_norms"] = jax.jit(
        lambda now, then: leaf_norms(jax.tree.map(jnp.subtract, now, then))
    )(params, start)
    return out


def gdn_rms_gap(program, reference) -> float:
    """Worst (mixer, row, value head) relative gap of the root mean square of
    the delta rule's output. Rounding averages out of it; a rule that loses the
    state between chunks, or leaves the ``S^T k`` correction out, reads whole
    tenths."""
    return float(max(
        np.max(np.abs(np.asarray(p) - np.asarray(r)) / np.asarray(r))
        for p, r in zip(program, reference)
    ))
