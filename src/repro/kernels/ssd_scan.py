"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid is (batch*heads, chunks) with the chunk axis "arbitrary": the running
[P, N] SSM state lives in VMEM scratch and is carried across chunk steps,
while each step does the intra-chunk quadratic work on the MXU:

    cum   = cumsum(dA)                       [c]   (computed by the wrapper)
    y     = (C B^T  *  exp(cum_i - cum_j) tril) @ (x*dt)   intra-chunk
          + exp(cum) * (C @ state^T)                        inter-chunk
    state = state * exp(cum[-1]) + (x*dt)^T @ (B * exp(cum[-1]-cum))

The wrapper pre-computes xdt = x*dt and cum, the running sum of dA = dt*A[h]
within each chunk (Mosaic has no cumsum), so the kernel streams only
[c,P]/[c,N] tiles plus cum twice: as a [1,c] row and a [c,1] column, the two
layouts the pairwise decay exp(cum_i - cum_j) broadcasts from.  Each block
keeps its last two dimensions tile-aligned or full, as the TPU lowering
requires (chunk a multiple of 128 on the chip).  Groups are broadcast to
heads via the B/C index map (no duplication in VMEM).  Oracle:
ref.ssd_reference (sequential recurrence).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret


def _ssd_kernel(xdt_ref, cum_row_ref, cum_col_ref, b_ref, c_ref, y_ref, state_scr,
                *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[0].astype(jnp.float32)       # [c, P]
    cum_row = cum_row_ref[0]                   # [1, c]
    cum_col = cum_col_ref[0]                   # [c, 1]
    bm = b_ref[0].astype(jnp.float32)          # [c, N]
    cm = c_ref[0].astype(jnp.float32)          # [c, N]

    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)      # [c, c]
    CB = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [c, c]
    y = jax.lax.dot_general(CB * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [c, P]

    state = state_scr[...]                     # [P, N]
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # [c, N] @ [N, P]^T -> [c, P]

    # cum at the chunk's end, as [1, 1]: a masked lane sum, because a slice
    # at lane c-1 gives a layout Mosaic cannot broadcast.
    lane = jax.lax.broadcasted_iota(jnp.int32, cum_row.shape, 1)
    last = jnp.sum(jnp.where(lane == chunk - 1, cum_row, 0.0), axis=1, keepdims=True)
    decay_to_end = jnp.exp(last - cum_col)     # [c, 1]
    new_state = state * jnp.exp(last) + jax.lax.dot_general(
        xdt, bm * decay_to_end, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # [P, N]
    state_scr[...] = new_state
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 64, interpret: bool | None = None):
    """x [b,s,h,p], dt [b,s,h] (post-softplus), A [h] (<0), Bm/Cm [b,s,g,n].

    Returns y [b,s,h,p].  s % chunk == 0 required (ops.py guards)."""
    interpret = resolve_interpret(interpret)
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    nc = s // chunk
    assert nc * chunk == s, (s, chunk)

    xdt = (x * dt[..., None]).transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dA = (dt * A).astype(jnp.float32).transpose(0, 2, 1).reshape(b * h, nc, chunk)
    cum = jnp.cumsum(dA, axis=-1)
    bm = Bm.transpose(0, 2, 1, 3).reshape(b * g, s, n)
    cm = Cm.transpose(0, 2, 1, 3).reshape(b * g, s, n)

    grid = (b * h, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, c: (i, 0, c)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: ((i // rep) if rep > 1 else i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: ((i // rep) if rep > 1 else i, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
    )(xdt, cum.reshape(b * h, 1, s), cum.reshape(b * h, s, 1), bm, cm)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
