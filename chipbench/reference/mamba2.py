"""Plain float32 Mamba-2 language model (arXiv:2405.21060): loss and gradients.

One layer: RMSNorm, in-projection to (z, x, B, C, dt), a depthwise causal
convolution with SiLU over (x, B, C), the SSD recurrence

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t B_t^T,   y_t = h_t C_t + D x_t,

a gated RMSNorm of y * SiLU(z), and the out-projection added to the residual
stream. The recurrence is evaluated exactly, chunk by chunk: within a chunk
in its quadratic form, across chunks as a sequential scan over the chunk
states. The head is tied to the embedding; the loss is the mean next-token
cross-entropy. Everything is float32 (the published model keeps its residual
stream in float32 too).

Parameters are the tree of ``chipbench/weights.py`` (stacked per layer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import REFERENCE, rmsnorm


def dims(c: dict) -> dict:
    """The model's sizes from its configuration."""
    D = c["d_model"]
    di = c["expand"] * D
    H = di // c["headdim"]
    G, N = c["ngroups"], c["d_state"]
    return dict(D=D, L=c["n_layer"], V=c["vocab_size"], di=di, H=H, P=c["headdim"],
                G=G, N=N, K=c["d_conv"], conv=di + 2 * G * N, proj=2 * di + 2 * G * N + H)


def causal_conv(x, w, b):
    """Depthwise causal convolution. x [b,s,c], w [k,c], b [c]."""
    k, c = w.shape
    out = jax.lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(k - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=c,
        precision=jax.lax.Precision.HIGHEST,
    )
    return out + b


def ssd(x, dt, A, B, C, chunk, mm):
    """y_t = sum_{j<=t} C_t . B_j exp(sum_{j<k<=t} dt_k A) dt_j x_j.

    x [b,s,h,p], dt [b,s,h], A [h], B/C [b,s,g,n] -> y [b,s,h,p].
    """
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of the chunk {chunk}")
    nc = s // chunk
    B = jnp.repeat(B, h // g, axis=2)
    C = jnp.repeat(C, h // g, axis=2)
    split = lambda t: t.reshape((b, nc, chunk) + t.shape[2:])
    x, dt, B, C = split(x), split(dt), split(B), split(C)
    cum = mm.act(jnp.cumsum(mm.act(dt * A), axis=2))          # [b,c,l,h]
    xdt = mm.act(x * dt[..., None])

    # Within a chunk: the decay from step j to step i >= j is exp(cum_i - cum_j).
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]       # [b,c,i,j,h]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    y = mm("bcijh,bcjhp->bcihp", mm("bcihn,bcjhn->bcijh", C, B) * decay, xdt)

    # Across chunks: the state each chunk leaves, carried by a sequential scan.
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)                 # [b,c,l,h]
    left = mm("bcjhn,bcjhp->bchpn", B * to_end[..., None], xdt)
    whole = jnp.exp(cum[:, :, -1, :])                         # [b,c,h]

    def carry(state, inp):
        w, s_c = inp
        return state * w[..., None, None] + s_c, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((b, h, p, n), jnp.float32),
        (whole.swapaxes(0, 1), left.swapaxes(0, 1)),
    )
    entering = entering.swapaxes(0, 1)                        # [b,c,h,p,n]
    y = y + mm("bcihn,bchpn->bcihp", C * jnp.exp(cum)[..., None], entering)
    return y.reshape(b, s, h, p)


def layer(p, x, cfg: dict, mm=REFERENCE):
    d = dims(cfg)
    di, H, P, G, N = d["di"], d["H"], d["P"], d["G"], d["N"]
    eps = cfg["norm_epsilon"]
    b, s, _ = x.shape
    m = p["mamba"]
    zxbcdt = mm.act(mm("bsd,dk->bsk", mm.act(rmsnorm(x, p["ln1"]["scale"], eps)), m["in_proj"]))
    z = zxbcdt[..., :di]
    xbc = mm.act(jax.nn.silu(causal_conv(zxbcdt[..., di:di + d["conv"]], m["conv_w"], m["conv_b"])))
    dt = mm.act(jax.nn.softplus(zxbcdt[..., di + d["conv"]:] + m["dt_bias"]))
    xs = xbc[..., :di].reshape(b, s, H, P)
    Bm = xbc[..., di:di + G * N].reshape(b, s, G, N)
    Cm = xbc[..., di + G * N:].reshape(b, s, G, N)
    y = mm.act(ssd(xs, dt, mm.act(-jnp.exp(m["A_log"])), Bm, Cm, cfg["chunk_size"], mm))
    y = mm.act((y + m["D"][:, None] * xs).reshape(b, s, di))
    y = mm.act(rmsnorm(mm.act(y * jax.nn.silu(z)), m["norm"], eps))
    return mm.act(x + mm.act(mm("bsk,kd->bsd", y, m["out_proj"])))


def loss_sum(params, ids, cfg: dict, mm=REFERENCE):
    """Summed next-token cross-entropy over ids [b, s] (s - 1 targets a row)."""
    x = mm.act(params["embed"]["tok"][ids])
    body = jax.checkpoint(lambda x, p: (layer(p, x, cfg, mm), None))
    x, _ = jax.lax.scan(body, x, params["blocks"][0]["l0"])
    x = mm.act(rmsnorm(x, params["final_norm"]["scale"], cfg["norm_epsilon"]))
    logits = mm("bsd,vd->bsv", x[:, :-1], params["embed"]["tok"])
    target = ids[:, 1:]
    picked = jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss_and_grad(params, ids, cfg: dict, rows: int, mm=REFERENCE):
    """Mean loss over ids [B, s] and its gradient, ``rows`` rows at a time."""
    B, s = ids.shape
    if B % rows:
        raise ValueError(f"batch {B} is not a multiple of {rows} rows")
    blocks = ids.reshape(B // rows, rows, s)
    vg = jax.value_and_grad(lambda p, i: loss_sum(p, i, cfg, mm))

    def add(acc, blk):
        l, g = vg(params, blk)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (total, grads), _ = jax.lax.scan(add, zero, blocks)
    count = B * (s - 1)
    return total / count, jax.tree.map(lambda g: g / count, grads)
