"""Plain AdamW with decoupled weight decay and global-norm clipping."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-30))
    return jax.tree.map(lambda g: g * scale, grads)


def step(params, grads, m, v, t: int, *, lr, b1, b2, eps, weight_decay, clip):
    """One update at step ``t`` (1-based) -> (params, m, v, clipped grads)."""
    grads = clip_by_global_norm(grads, clip)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1**t, 1 - b2**t

    def update(p, m, v):
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p)

    return jax.tree.map(update, params, m, v), m, v, grads
