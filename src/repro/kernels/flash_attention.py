"""Flash attention (forward) as a Pallas TPU kernel.

TPU-native adaptation of blockwise attention: q/k/v tiles live in VMEM via
BlockSpec, the MXU consumes (block_q x head_dim) @ (head_dim x block_k)
tiles, and the online-softmax running state (m, l, acc) persists in VMEM
scratch across the k-block grid dimension (the "arbitrary" innermost axis).

Features needed by the assigned architectures:
  * causal masking with whole-block skipping (upper-triangle blocks never
    enter the MXU — true FLOP savings, not masking),
  * sliding-window attention with both-side block skipping (gemma2/3, hymba),
  * logit softcap (gemma2),
  * GQA via the kv-head index map (no K/V duplication in VMEM).

Block sizes default to 512x512 (bq*hd + 2*bk*hd + bq*bk fp32 tiles fit
comfortably in ~16 MiB VMEM for hd <= 256; MXU dims are multiples of 128).

Validated against ref.mha_reference under interpret=True (CPU) over shape/
dtype/flag sweeps in tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None, softcap: float | None,
    block_q: int, block_k: int, num_k_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # ---- whole-block skip predicates (computed on grid indices) ----
    q_lo = iq * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_hi              # block not entirely in the future
    if window is not None:
        live &= q_lo - k_hi < window      # block not entirely out of window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # [bq, bk]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                           # [bq, 128] (lane-bcast)
        m_cur = jnp.max(s, axis=1, keepdims=True)     # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev - m_new)                # [bq, 128]
        p = jnp.exp(s - m_new[:, :1])                 # [bq, bk]
        l_scr[...] = l_scr[...] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_scr.shape
        )
        acc_scr[...] = acc_scr[...] * corr[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention(
    q, k, v,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """q [B,Sq,H,hd], k/v [B,Sk,KV,hd] -> [B,Sq,H,hd].

    Requires Sq % block_q == 0 and Sk % block_k == 0 (ops.py picks divisors
    or falls back to the reference).
    """
    interpret = resolve_interpret(interpret)
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    G = H // KV
    scale = scale if scale is not None else hd**-0.5

    qt = q.transpose(0, 2, 1, 3)  # [B,H,Sq,hd]
    kt = k.transpose(0, 2, 1, 3)  # [B,KV,Sk,hd]
    vt = v.transpose(0, 2, 1, 3)
    nq, nk = Sq // block_q, Sk // block_k
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G if G > 1 else h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G if G > 1 else h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
