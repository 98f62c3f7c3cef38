"""Shared layer primitives: norms, FFNs, embeddings, the decode cache view.

Everything is functional: ``init_*`` builds a param pytree, ``apply``-style
functions consume it.  Params default to float32 masters; activations run in
``cfg.dtype`` (bf16 on TPU) with f32 softmax/log-softmax.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------- norms
def init_norm(cfg: ModelConfig, width: int | None = None):
    d = width or cfg.d_model
    if cfg.norm_type == "layer":
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_norm(p, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layer":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:
        var = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]
    return y.astype(x.dtype)


def rmsnorm(scale, x, eps: float = 1e-6):
    """Bare RMSNorm used for qk-norm and hybrid branch norms."""
    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# ------------------------------------------------------------------ FFN
def init_dense_ffn(key, cfg: ModelConfig, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d)
    scale_out = 1.0 / np.sqrt(f)
    if cfg.ffn_act == "swiglu":
        return {
            "w_gate": jax.random.normal(k1, (d, f), jnp.float32) * scale_in,
            "w_up": jax.random.normal(k2, (d, f), jnp.float32) * scale_in,
            "w_down": jax.random.normal(k3, (f, d), jnp.float32) * scale_out,
        }
    return {
        "w_up": jax.random.normal(k1, (d, f), jnp.float32) * scale_in,
        "b_up": jnp.zeros((f,), jnp.float32),
        "w_down": jax.random.normal(k2, (f, d), jnp.float32) * scale_out,
        "b_down": jnp.zeros((cfg.d_model,), jnp.float32),
    }


def apply_dense_ffn(p, x, cfg: ModelConfig):
    dt = x.dtype
    if "w_gate" in p:
        g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(dt))
        u = jnp.einsum("...d,df->...f", x, p["w_up"].astype(dt))
        h = jax.nn.silu(g) * u
        return jnp.einsum("...f,fd->...d", h, p["w_down"].astype(dt))
    h = jnp.einsum("...d,df->...f", x, p["w_up"].astype(dt)) + p["b_up"].astype(dt)
    h = jax.nn.gelu(h)
    return jnp.einsum("...f,fd->...d", h, p["w_down"].astype(dt)) + p["b_down"].astype(dt)


# ------------------------------------------------------------ embeddings
def init_embedding(key, cfg: ModelConfig):
    p = {"tok": jax.random.normal(key, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["head"] = jax.random.normal(k2, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02
    return p


def embed_tokens(p, tokens, cfg: ModelConfig):
    return p["tok"][tokens].astype(dtype_of(cfg))


def lm_logits(p, x, cfg: ModelConfig):
    table = p.get("head", p["tok"])
    logits = jnp.einsum("...d,vd->...v", x, table.astype(x.dtype))
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap:
        c = cfg.final_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def cross_entropy(logits, labels, ignore_index: int = -1):
    """Mean CE over non-ignored positions.  logits f32 [..., V], labels int."""
    mask = labels != ignore_index
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    total = jnp.maximum(mask.sum(), 1)
    return -(ll * mask).sum() / total


def chunked_softmax_xent(
    x, params, labels, cfg: ModelConfig, chunk: int = 256, ignore_index: int = -1
):
    """CE without ever materializing [B, S, V] logits.

    Scans over token chunks; each chunk's logits are computed, reduced to
    (sum CE, count), and *rematerialized* in backward (jax.checkpoint), so
    live logits are [B, chunk, V] — at gemma3's 262k vocab this is the
    difference between ~4 TB and ~0.3 GB per device.  x is pre-final-norm
    hidden states aligned so position i predicts labels[i] (callers shift).
    """
    from repro.distributed.sharding import shard as _shard

    table = params.get("head", params["tok"])
    B, S, D = x.shape
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=ignore_index)
    nc = (S + pad) // c
    xs = x.reshape(B, nc, c, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nc, c).transpose(1, 0, 2)

    def body(acc, inp):
        xc, lc = inp
        logits = jnp.einsum("bcd,vd->bcv", xc, table.astype(xc.dtype))
        logits = logits.astype(jnp.float32)
        if cfg.final_softcap:
            cap = cfg.final_softcap
            logits = cap * jnp.tanh(logits / cap)
        logits = _shard(logits, "batch_pd", None, "vocab")
        mask = lc != ignore_index
        safe = jnp.where(mask, lc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        ce_sum = ((logz - ll) * mask).sum()
        return (acc[0] + ce_sum, acc[1] + mask.sum()), None

    body = jax.checkpoint(body)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (xs, ls))
    return tot / jnp.maximum(cnt, 1).astype(jnp.float32)


# ------------------------------------------------------------ decode cache
@dataclass(frozen=True)
class CacheView:
    """One layer's entry of the decode cache that the layer loop carries.

    ``tree`` maps names to the buffers the loop carries: the layer's own
    entry, or, with ``index``, the entries of a segment's repeats stacked on
    a leading axis, the layer's at ``index``. ``read`` takes the layer's entry
    out; ``write`` puts an update in place at ``at`` along the entry's axes,
    so a decode step writes only what it changed: one token's slot of a KV
    cache, a recurrent state whole.
    """

    tree: Any
    index: Any = None

    def shape(self, name) -> tuple[int, ...]:
        s = self.tree[name].shape
        return s if self.index is None else s[1:]

    def read(self, name):
        a = self.tree[name]
        if self.index is None:
            return a
        return jax.lax.dynamic_index_in_dim(a, self.index, keepdims=False)

    def write(self, name, update, at) -> "CacheView":
        if self.index is not None:
            update, at = update[None], (self.index, *at)
        a = jax.lax.dynamic_update_slice(self.tree[name], update, tuple(at))
        return CacheView({**self.tree, name: a}, self.index)
