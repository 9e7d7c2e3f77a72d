"""Plain float32 training steps: mean next-token loss, global-norm clip,
AdamW with the warmup-cosine schedule, as the configuration states.

``reference_steps`` follows the program's first steps from the same
weights and batches and returns what the comparison reads: each step's
loss, the norm of each leaf of the first (clipped) gradient, and the
norm of each leaf's change over all the steps.
"""
from __future__ import annotations

import math
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp

from bench.reference.model import loss_sum


def learning_rate(hp: Mapping, step):
    """Warmup then cosine decay to ``final_lr_fraction`` of the peak;
    ``step`` counts updates already made (0 for the first)."""
    peak, warm = hp["learning_rate"], hp["warmup_steps"]
    step = jnp.asarray(step, jnp.float32)
    t = jnp.clip((step - warm) / max(hp["total_steps"] - warm, 1), 0.0, 1.0)
    ff = hp["final_lr_fraction"]
    cos = peak * (ff + (1 - ff) * 0.5 * (1 + jnp.cos(math.pi * t)))
    return jnp.where(step < warm, peak * step / max(warm, 1), cos)


def adamw(params, grads, mu, nu, t, lr, hp):
    """One AdamW update; ``t`` is the 1-based step count."""
    b1, b2 = hp["beta1"], hp["beta2"]
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        new_m[k] = b1 * mu[k] + (1 - b1) * g
        new_v[k] = b2 * nu[k] + (1 - b2) * g * g
        upd = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + hp["eps"])
        new_p[k] = p - lr * (upd + hp["weight_decay"] * p)
    return new_p, new_m, new_v


def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return {k: g * scale for k, g in grads.items()}


def loss_and_grads(params, m, tokens, rows_per_micro: int,
                   lowp: Optional[str]):
    """Mean loss over all labelled positions and its gradient, summed
    over micro-batches of ``rows_per_micro`` rows so that it fits.
    Micro-batch i takes rows i, i + B/r, i + 2B/r, ...: with the rows
    split in r contiguous blocks over r devices, each holds one row."""
    B, S = tokens.shape
    micro = tokens.reshape(rows_per_micro, B // rows_per_micro, S)
    micro = micro.swapaxes(0, 1)
    vg = jax.value_and_grad(lambda p, t: loss_sum(p, m, t, lowp))

    def body(acc, t):
        l, g = vg(params, t)
        return jax.tree.map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(jnp.zeros_like, params))
    (total, gsum), _ = jax.lax.scan(body, zero, micro)
    n = B * (S - 1)
    return total / n, jax.tree.map(lambda g: g / n, gsum)


def reference_steps(params0: Dict[str, jax.Array], m: Mapping, hp: Mapping,
                    batches, rows_per_micro: int = 1,
                    lowp: Optional[str] = None):
    """batches: int32 [steps, B, S]. Returns (losses [steps],
    {leaf: |first clipped gradient|}, {leaf: |params_end - params0|})."""
    params = dict(params0)
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = dict(mu)
    losses, grad_norms = [], None
    for i in range(batches.shape[0]):
        loss, grads = loss_and_grads(params, m, batches[i], rows_per_micro,
                                     lowp)
        grads = clip_by_global_norm(grads, hp["grad_clip"])
        if grad_norms is None:
            grad_norms = {k: jnp.linalg.norm(g) for k, g in grads.items()}
        params, mu, nu = adamw(params, grads, mu, nu, i + 1,
                               learning_rate(hp, i), hp)
        losses.append(loss)
    change = {k: jnp.linalg.norm(params[k] - params0[k]) for k in params}
    return jnp.stack(losses), grad_norms, change
