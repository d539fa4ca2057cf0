"""Plain local-SGD rounds for the ``xing4`` training cells: Adam, exact gossip.

What one worker of the recipe does, written down without the program: ``h`` Adam
steps on the round's rows, the step size warmed up linearly over the recipe's
``warmup_steps`` (step ``k``, counted from 0, takes ``learning_rate * k /
warmup_steps``). With one worker exact gossip is the identity mix (``W = [1]``),
so the parameters follow Adam alone and there is no tracking state to compare.
``reference/train_qwen3_next.py``'s ``follow`` in this model's name, with two
differences that its size forces (913M parameters in float32 are 3.65 GB, and
so is a gradient): BOTH of Adam's moments wait on the host while a gradient is
taken, and Adam's step is taken one top-level subtree of the parameters at a
time (the same arithmetic, ``reference/train.py``'s), so that the device never
holds more than the parameters, a gradient and one subtree's moments. The
norms and leaf comparisons are ``reference/train.py``'s, the routes' comparison
``train_nemotron_h.py``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import xing4 as model
from reference.train import adam_step, leaf_norms
# the driver reads both here: the routes' comparison, and the worst relative gap of a root mean
# square that the first step showed (every sub-block, row, head or stream), the hybrid's scan's measure
from reference.train_nemotron_h import routing_disagreement  # noqa: F401
from reference.train_nemotron_h import scan_rms_gap as rms_gap  # noqa: F401

# the same arithmetic on one subtree, its parameters and both moments updated in place
_adam_step = jax.jit(adam_step.__wrapped__, donate_argnums=(0, 2, 3))


@functools.lru_cache(maxsize=None)
def _grad_fn(sizes_items: tuple, precision: str, faults: tuple):
    """One jitted ((loss, (what the sub-blocks showed, the two-ahead loss)),
    gradient) function per (sizes, precision, faults): several seeds in one
    process trace it once."""
    sizes = dict(sizes_items)
    return jax.jit(jax.value_and_grad(
        lambda p, rows: model.lm_loss(p, rows, sizes, precision, faults, with_shown=True),
        has_aux=True))


def follow(params, rounds_rows, sizes: dict, recipe: dict, precision: str = "f32",
           faults: tuple = (), start=None):
    """Follow ``len(rounds_rows)`` rounds; each entry is (h, B, S) int32 rows.
    ``params`` IS consumed: Adam's first step updates it in place, so the
    caller makes it for this call and keeps no other use of it. ``start``: a
    function that makes the first parameters again, for the change's norms at
    the end; without it a copy waits on the host (3.65 GB at the full size).

    Returns per-round mean losses (``loss``: the weighted sum; ``mtp_loss``: the
    two-ahead loss alone), the leaf norms of the first gradient, what the first
    step showed (``routes``, ``mla_rms``, ``stream_rms``), Adam's first moment
    after round 1 (on the host) and its leaf norms, and the leaf norms of the
    parameters' change after the last round. ``faults`` are the model's:
    ``reference/xing4.py``."""
    faults = tuple(sorted(faults))
    grad_fn = _grad_fn(tuple(sorted(sizes.items())), precision, faults)
    peak, b1, b2, eps = (recipe[k] for k in ("learning_rate", "adam_b1", "adam_b2", "adam_eps"))
    warmup = int(recipe.get("warmup_steps", 0))
    steps = 0  # Adam steps taken: the step size warms up linearly, the first step's is 0
    kept = None if start else jax.device_get(params)  # on the host: the device holds one generation
    zeros = lambda: jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
    mu, nu = zeros(), zeros()  # both moments live on the host
    count = jnp.zeros((), jnp.int32)
    out = {"loss": [], "mtp_loss": []}
    for r, rows_h in enumerate(rounds_rows):
        losses, ahead = [], []
        for i in range(rows_h.shape[0]):
            rows = jnp.asarray(rows_h[i])
            (loss, (shown, mtp_loss)), grads = grad_fn(params, rows)
            if r == 0 and i == 0:
                out.update(jax.tree.map(np.asarray, shown))
                out["grad_norms"] = leaf_norms(grads)
            del shown
            lr = peak * min(steps, warmup) / warmup if warmup else peak
            for name in sorted(params):  # a subtree at a time: its moments come up and go back
                params[name], mu_k, nu_k, stepped = _adam_step(
                    params[name], grads.pop(name), mu[name], nu[name], count, lr, b1, b2, eps)
                mu[name], nu[name] = jax.device_get((mu_k, nu_k))
                del mu_k, nu_k
            count = stepped
            steps += 1
            losses.append(float(loss))
            ahead.append(float(mtp_loss))
        out["loss"].append(sum(losses) / len(losses))
        out["mtp_loss"].append(sum(ahead) / len(ahead))
        if r == 0:
            out["mu"] = jax.tree.map(lambda x: x, mu)  # a tree of its own: later steps replace leaves, never write into them
            out["mu_norms"] = leaf_norms(mu)
    del mu, nu
    out["delta_norms"] = jax.jit(
        lambda now, then: leaf_norms(jax.tree.map(jnp.subtract, now, then))
    )(params, start() if start else kept)
    return out
