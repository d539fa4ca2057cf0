"""Plain local-SGD rounds for the hybrid (``nemotron_h``) training cells: Adam,
exact gossip.

What one worker of the recipe does, written down without the program: ``h`` Adam
steps on the round's rows, the step size warmed up linearly over the recipe's
``warmup_steps`` (step ``k``, counted from 0, takes ``learning_rate * k / warmup_steps``). With one worker exact gossip is the identity mix
(``W = [1]``), so the parameters follow Adam alone and there is no tracking
state to compare. ``reference/train.py`` cannot be reused unedited (its
``follow`` is written to GPT-2's loss and the top-k codec); its Adam step, norms
and leaf comparisons are imported from it. A row here is 8,192 tokens and a step
one row, so gradients are taken over the whole step at once; Adam's step donates
the state it replaces, and the second moment waits on the host while a gradient
is taken (667M parameters in float32: the gradient's 10.4 GB of arguments,
output and workspace beside both moments would leave the chip 1 GB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import nemotron_h as model
from reference.train import adam_step, leaf_norms

# the same arithmetic, the parameters and both moments updated in place
_adam_step = jax.jit(adam_step.__wrapped__, donate_argnums=(0, 2, 3))


@functools.lru_cache(maxsize=None)
def _fns(sizes_items: tuple, precision: str, faults: tuple):
    """One jitted (loss and gradient, routes and scan outputs) pair per (sizes,
    precision, faults): several seeds in one process trace each once."""
    sizes = dict(sizes_items)
    grad = jax.jit(jax.value_and_grad(
        lambda p, rows: model.lm_loss(p, rows, sizes, precision, faults)))
    shown = jax.jit(lambda p, rows: model.hidden_states(p, rows, sizes, precision, faults)[1])
    return grad, shown


def follow(params, rounds_rows, sizes: dict, recipe: dict, precision: str = "f32",
           faults: tuple = ()):
    """Follow ``len(rounds_rows)`` rounds; each entry is (h, B, S) int32 rows.
    ``params`` IS consumed: Adam's first step updates it in place, so the
    caller makes it for this call and keeps no other use of it.

    Returns per-round mean losses, the leaf norms of the first gradient, the
    experts every ``E`` block chose for the first step's tokens (``routes``) and
    the per-head root mean square of every ``M`` block's scan output there
    (``scan_rms``), Adam's first
    moment after round 1 (on the host) and its leaf norms, and the leaf norms of
    the parameters' change after the last round. ``faults`` (``half_batch``,
    ``top5``, ``renorm_over_held``, ``no_state_carry``) are the model's:
    ``reference/nemotron_h.py``."""
    faults = tuple(sorted(faults))
    grad_fn, shown_fn = _fns(tuple(sorted(sizes.items())), precision, faults)
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
            if r == 0 and i == 0:
                out.update(jax.tree.map(np.asarray, shown_fn(params, rows)))
            nu = jax.device_get(nu)  # on the host while the gradient's workspace is live
            loss, grads = grad_fn(params, rows)
            if r == 0 and i == 0:
                out["grad_norms"] = leaf_norms(grads)
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


def routing_disagreement(program: list, reference: list) -> float:
    """Share of a token's chosen experts that the other side did not choose,
    over every token of every ``E`` block: ``1 - |I_program & I_reference| /
    max(|I_program|, |I_reference|)`` averaged. Ties apart, a lower precision
    moves it a little; one expert fewer a token reads at least ``1/k``."""
    shares = []
    for prog, ref in zip(program, reference):
        prog = np.asarray(prog).reshape(-1, prog.shape[-1])
        ref = np.asarray(ref).reshape(-1, ref.shape[-1])
        common = (prog[:, :, None] == ref[:, None, :]).any(axis=2).sum(axis=1)
        shares.append(1.0 - common / max(prog.shape[1], ref.shape[1]))
    return float(np.mean(np.concatenate(shares)))


def scan_rms_gap(program, reference) -> float:
    """Worst (block, row, head) relative gap of the scan output's root mean
    square. Rounding averages out of it; a scan that loses the state between
    chunks reads whole tenths on the heads that remember longest."""
    return float(max(
        np.max(np.abs(np.asarray(p) - np.asarray(r)) / np.asarray(r))
        for p, r in zip(program, reference)
    ))
