"""Mamba-2 (SSD, state-space duality) mixer — chunked parallel scan.

Training/prefill uses the SSD block decomposition (arXiv:2405.21060 §6):
intra-chunk quadratic attention-like term + inter-chunk state recurrence,
with the cross-chunk scan done by ``lax.associative_scan`` (log-depth on
TPU).  Decode keeps a constant-size recurrent state: [B, H, P, N] SSM state
plus a [B, conv_dim, K-1] convolution tail — this is what makes the
``long_500k`` cell linear-cost for SSM models.

Layout: d_inner = expand*d_model, heads H = d_inner/headdim (P=headdim),
state N = ssm_state, G groups share B/C across H/G heads.

The token mixer of ``mamba`` layers (``transformer.MIXERS``): it reads the
layer's ``mamba`` params and writes its ``ssm`` cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from .layers import CacheView, rmsnorm

SCOPE = "mamba"


def _dims(cfg: ModelConfig):
    di = cfg.d_inner
    H = cfg.ssm_heads
    P = cfg.ssm_headdim
    N = cfg.ssm_state
    G = cfg.ssm_groups
    conv_dim = di + 2 * G * N
    return di, H, P, N, G, conv_dim


def init(keys, cfg: ModelConfig, spec: LayerSpec):
    """The layer's ``mamba`` params, made from the first of its mixer ``keys``."""
    d = cfg.d_model
    di, H, P, N, G, conv_dim = _dims(cfg)
    ks = jax.random.split(keys[0], 4)
    s = 1.0 / np.sqrt(d)
    proj_out = 2 * di + 2 * G * N + H  # z, x, B, C, dt
    return {"mamba": {
        "in_proj": jax.random.normal(ks[0], (d, proj_out), jnp.float32) * s,
        "conv_w": jax.random.normal(ks[1], (cfg.conv_kernel, conv_dim), jnp.float32) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.ones((di,), jnp.float32),
        "out_proj": jax.random.normal(ks[2], (di, d), jnp.float32) / np.sqrt(di),
    }}


def _split_proj(zxbcdt, cfg: ModelConfig):
    di, H, P, N, G, _ = _dims(cfg)
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di : 2 * di + 2 * G * N]
    dt = zxbcdt[..., 2 * di + 2 * G * N :]
    return z, xBC, dt


def _split_xbc(xBC, cfg: ModelConfig):
    di, H, P, N, G, _ = _dims(cfg)
    x = xBC[..., :di]
    Bm = xBC[..., di : di + G * N]
    Cm = xBC[..., di + G * N :]
    B_, S = x.shape[:2]
    return (
        x.reshape(B_, S, H, P),
        Bm.reshape(B_, S, G, N),
        Cm.reshape(B_, S, G, N),
    )


def _causal_conv(xBC, w, b):
    """Depthwise causal conv over the sequence axis. xBC [B,S,C], w [K,C]."""
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC)
    for i in range(K):  # K is 4: unrolled taps beat a conv op for this shape
        out = out + pad[:, i : i + xBC.shape[1], :] * w[i]
    return jax.nn.silu(out + b)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int):
    """SSD over chunks.  x [b,s,h,p] (pre-scaled by nothing), dt [b,s,h] >0,
    A [h] < 0, Bm/Cm [b,s,g,n].  Returns y [b,s,h,p]."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    nc = s // chunk
    assert nc * chunk == s, (s, chunk)

    xc = (x * dt[..., None]).reshape(b, nc, chunk, h, p)     # input contribution
    dA = (dt * A).reshape(b, nc, chunk, h)                   # negative increments
    Bc = Bm.reshape(b, nc, chunk, g, n)
    Cc = Cm.reshape(b, nc, chunk, g, n)

    cum = jnp.cumsum(dA, axis=2)                             # [b,nc,c,h]
    # --- intra-chunk (quadratic, attention-like) ---
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # [b,nc,c,c,h]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    # Mask BEFORE exp: upper-triangular diffs are positive and would overflow,
    # poisoning gradients through the where (NaN * 0). exp(-inf) == 0 is safe.
    diff = jnp.where(tri[None, None, :, :, None], diff, -jnp.inf)
    L = jnp.exp(diff).astype(x.dtype)
    CB = jnp.einsum("bzcgn,bzdgn->bzcdg", Cc, Bc)            # [b,nc,c,c,g]
    CB = jnp.repeat(CB, rep, axis=-1)                        # -> heads
    y_diag = jnp.einsum("bzcdh,bzcdh,bzdhp->bzchp", CB, L, xc)

    # --- chunk states ---
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum).astype(x.dtype)   # [b,nc,c,h]
    Bh = jnp.repeat(Bc, rep, axis=3)                         # [b,nc,c,h,n]
    states = jnp.einsum("bzchn,bzch,bzchp->bzhpn", Bh, decay_to_end, xc)

    # --- inter-chunk recurrence (associative scan over chunks) ---
    chunk_decay = jnp.exp(cum[:, :, -1, :]).astype(x.dtype)  # [b,nc,h]

    def combine(a, c):
        d1, s1 = a
        d2, s2 = c
        return d1 * d2, s1 * d2[..., None, None] + s2

    run_decay, run_state = jax.lax.associative_scan(
        combine, (chunk_decay, states), axis=1
    )
    # state entering chunk z is the running state after chunk z-1
    prev = jnp.concatenate(
        [jnp.zeros_like(run_state[:, :1]), run_state[:, :-1]], axis=1
    )
    state_decay_in = jnp.exp(cum).astype(x.dtype)            # decay from chunk start
    Ch = jnp.repeat(Cc, rep, axis=3)                         # [b,nc,c,h,n]
    y_off = jnp.einsum("bzchn,bzhpn,bzch->bzchp", Ch, prev, state_decay_in)

    y = (y_diag + y_off).reshape(b, s, h, p)
    final_state = run_state[:, -1].astype(jnp.float32)       # [b,h,p,n]
    return y, final_state


def _mamba(p, x_in, cfg: ModelConfig):
    """x_in [B,S,D] -> (out [B,S,D], final SSM state, conv input [B,S,conv])."""
    dt_ = x_in.dtype
    B_, S = x_in.shape[:2]
    zxbcdt = jnp.einsum("bsd,dk->bsk", x_in, p["in_proj"].astype(dt_))
    z, xBC_raw, dt_raw = _split_proj(zxbcdt, cfg)
    xBC = _causal_conv(xBC_raw, p["conv_w"].astype(dt_), p["conv_b"].astype(dt_))
    x, Bm, Cm = _split_xbc(xBC, cfg)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    # Pad the sequence to a chunk multiple; padded steps get dt == 0, which
    # makes them exact no-ops in the recurrence (no decay, no contribution).
    pad = (-S) % cfg.ssm_chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    A = -jnp.exp(p["A_log"])
    y, final_state = ssd_chunked(x, dt.astype(dt_), A.astype(dt_), Bm, Cm, cfg.ssm_chunk)
    y = y + p["D"].astype(dt_)[:, None] * x
    y = y[:, :S]
    x = x[:, :S]
    y = y.reshape(B_, S, -1)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(dt_))
    return out, final_state, xBC_raw


def forward(p, x, cfg: ModelConfig, spec: LayerSpec, angles, causal: bool = True):
    """Training: x [B,S,D] -> [B,S,D]."""
    return _mamba(p["mamba"], x, cfg)[0]


def prefill(p, x, cfg: ModelConfig, spec: LayerSpec, angles, max_seq: int):
    """The forward pass that also emits the ``ssm`` cache decode goes on
    from: the final SSM state and the conv tail."""
    out, final_state, xBC_raw = _mamba(p["mamba"], x, cfg)
    S, K = x.shape[1], cfg.conv_kernel
    return out, {"ssm": {"state": final_state, "conv": xBC_raw[:, S - (K - 1) :, :]}}


# ------------------------------------------------------------------ decode
def init_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int, dtype):
    """The layer's zeroed ``ssm`` cache, the same size at any ``max_seq``."""
    di, H, P, N, G, conv_dim = _dims(cfg)
    return {"ssm": {
        "state": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, conv_dim), dtype),
    }}


def decode(p, x_in, cache, index, pos, cfg: ModelConfig, spec: LayerSpec, angles):
    """One-token recurrent step. x_in [B,1,D] -> ([B,1,D], its ``ssm``);
    ``cache`` holds the layer's ``ssm`` (with ``index``, stacked; see
    ``CacheView``), its ``state`` and ``conv`` tail both written whole."""
    p = p["mamba"]
    view = CacheView(cache["ssm"], index)
    dt_ = x_in.dtype
    di, H, P, N, G, conv_dim = _dims(cfg)
    zxbcdt = jnp.einsum("bsd,dk->bsk", x_in, p["in_proj"].astype(dt_))
    z, xBC_new, dt_raw = _split_proj(zxbcdt, cfg)

    # conv over [cached K-1 tail, new column]
    window = jnp.concatenate([view.read("conv"), xBC_new], axis=1)     # [B,K,conv]
    conv_out = (window * p["conv_w"].astype(dt_)[None]).sum(1, keepdims=True)
    xBC = jax.nn.silu(conv_out + p["conv_b"].astype(dt_))
    new_conv = window[:, 1:]

    x, Bm, Cm = _split_xbc(xBC, cfg)                                # S == 1
    x, Bm, Cm = x[:, 0], Bm[:, 0], Cm[:, 0]                         # [B,H,P],[B,G,N]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1)                                # [B,H,N]
    Ch = jnp.repeat(Cm, rep, axis=1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                            # [B,H]

    state = view.read("state") * dA[..., None, None] + jnp.einsum(
        "bhp,bhn,bh->bhpn", x.astype(jnp.float32), Bh.astype(jnp.float32), dt
    )
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch.astype(jnp.float32)).astype(dt_)
    y = y + p["D"].astype(dt_)[:, None] * x
    y = y.reshape(x_in.shape[0], 1, di)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(dt_))
    view = view.write("state", state, (0, 0, 0, 0)).write("conv", new_conv, (0, 0, 0))
    return out, {"ssm": view.tree}
