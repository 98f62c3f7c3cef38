"""Fused RMSNorm Pallas kernel: one VMEM pass per row block.

Unfused XLA does (square -> mean -> rsqrt -> mul -> mul) as separate HBM
round-trips when fusion fails across reshapes; the kernel reads each row
once and writes once (2x d_model bytes per row, the HBM floor).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool | None = None):
    """x [..., D], scale [D] -> same shape/dtype as x."""
    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    br = max(br, 1)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, scale)
    return out.reshape(orig_shape)
