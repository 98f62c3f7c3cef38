"""Mamba-2 (arXiv:2405.21060): seeded weights, reference, and the operations
one trained token requires.

An operation is a multiply or an add (a multiply-accumulate counts 2). Only
the work the model requires is counted: no recomputation, no padding, and
the causal half of every triangular product.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from chipbench.reference import mamba2 as reference
from chipbench.reference.mamba2 import dims

__all__ = ["dims", "forward_flops_per_token", "init", "reference", "train_flops_per_token"]


def _inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def init(c: dict, w):
    """Parameters from the leaf source ``w`` (``chipbench.weights``), stacked
    per layer. dt ~ log-uniform on [1e-3, 1e-1], floored at 1e-4, and
    A ~ U[1, 16], as Mamba-2 initialises them."""
    d = dims(c)
    D, L, H = d["D"], d["L"], d["H"]
    dt = jnp.maximum(jnp.exp(w.uniform((L, H), math.log(1e-3), math.log(1e-1))), 1e-4)
    mamba = {
        "in_proj": w.normal((L, D, d["proj"]), 1.0 / math.sqrt(D)),
        "conv_w": w.uniform((L, d["K"], d["conv"]), -0.5, 0.5),
        "conv_b": w.uniform((L, d["conv"]), -0.5, 0.5),
        "A_log": jnp.log(w.uniform((L, H), 1.0, 16.0)),
        "D": w.scale((L, H)),
        "dt_bias": _inv_softplus(dt),
        "norm": w.scale((L, d["di"])),
        # Mamba-2 rescales the residual branch's output by 1/sqrt(layers).
        "out_proj": w.normal((L, d["di"], D), 1.0 / math.sqrt(d["di"] * L)),
    }
    return {
        "embed": {"tok": w.normal((d["V"], D), 0.02)},
        "blocks": [{"l0": {"ln1": {"scale": w.scale((L, D))}, "mamba": mamba}}],
        "final_norm": {"scale": w.scale((D,))},
    }


def forward_flops_per_token(c: dict) -> float:
    d = dims(c)
    D, H, P, G, N = d["D"], d["H"], d["P"], d["G"], d["N"]
    half_chunk = c["chunk_size"] / 2   # causal: a position sees half a chunk on average
    per_layer = (
        2 * D * d["proj"]                          # in_proj
        + 2 * d["K"] * d["conv"]                   # depthwise conv
        + 2 * half_chunk * (G * N + H * P)         # C.B within the chunk, then its product with x
        + 2 * H * P * N                            # the token's share of its chunk's state
        + 2 * H * P * N                            # C . entering state
        + 2 * d["di"] * D                          # out_proj
    )
    return d["L"] * per_layer + 2 * D * d["V"]     # + tied head


def train_flops_per_token(c: dict) -> float:
    """Forward plus backward (twice the forward)."""
    return 3 * forward_flops_per_token(c)
