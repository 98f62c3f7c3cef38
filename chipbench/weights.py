"""Seeded weights for each configuration, made by the benchmark itself.

The program and the plain reference are both given these weights, so the
reference takes nothing the program made. The tree is laid out as the
program's ``Model.init`` lays out its parameters (the harness checks the two
layouts agree, leaf by leaf, before a run), with stacked leaves of shape
``[layers, ...]`` for the scanned layer program. The whole tree is made in
one jitted call, on the device, in float32: the type the cells train and
serve in.

Each kind of model lays out its own tree (``kinds/<kind>.py``), following
the published initialisations where they matter for the arithmetic
(Mamba-2's A and dt ranges; Qwen3's initializer_range for its linear
layers), with a 10% spread on every norm scale, so that a scale that is
dropped or applied twice shows in the comparison.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bench


class _Leaves:
    """Hands out one key per leaf, in a fixed order."""

    def __init__(self, key):
        self.key = key
        self.n = 0

    def _next(self):
        self.n += 1
        return jax.random.fold_in(self.key, self.n)

    def normal(self, shape, std):
        return jax.random.normal(self._next(), shape, jnp.float32) * std

    def uniform(self, shape, lo, hi):
        return jax.random.uniform(self._next(), shape, jnp.float32, lo, hi)

    def scale(self, shape):
        return 1.0 + 0.1 * jax.random.normal(self._next(), shape, jnp.float32)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def init(cfg: dict, key):
    """The configuration's parameters (float32), made from ``key`` by its kind."""
    return bench.kind(cfg["kind"]).init(cfg, _Leaves(key))


def make(cfg: dict, seed: int):
    """All parameters on the default device, in one jitted call."""
    return jax.jit(lambda k: init(cfg, k))(seed_key(seed))


def layout_mismatch(ours, program) -> str | None:
    """The first difference between two shape trees, or None."""
    a, ta = jax.tree_util.tree_flatten_with_path(ours)
    b, tb = jax.tree_util.tree_flatten_with_path(program)
    if ta != tb:
        return f"tree structure differs:\n  benchmark {ta}\n  program   {tb}"
    for (path, x), (_, y) in zip(a, b):
        if x.shape != y.shape or x.dtype != y.dtype:
            return (f"{jax.tree_util.keystr(path)}: benchmark {x.dtype}{list(x.shape)}, "
                    f"program {y.dtype}{list(y.shape)}")
    return None


def leaf_slices(tree):
    """[(name, array)] with each stacked ``blocks`` leaf cut into its layers.

    The comparisons judge parameters layer by layer, so that a fault in one
    layer is not averaged away over the whole stack.
    """
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if name.startswith("['blocks']"):
            out.extend((f"{name}[{i}]", x[i]) for i in range(x.shape[0]))
        else:
            out.append((name, x))
    return out
