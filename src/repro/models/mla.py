"""Multi-head Latent Attention (DeepSeek-V2) with compressed KV cache.

Train/prefill: decompress c_kv into per-head K_nope/V and run standard MHA.
Decode: the *absorbed* formulation — W_uk folds into the query and W_uv into
the output so attention runs directly against the [B, S, kv_lora] compressed
cache plus the shared [B, S, qk_rope] rope key.  Cache bytes per token:
(kv_lora + qk_rope) vs 2*H*head_dim for vanilla GQA — the 512+64 vs 4096
compression that makes 32k decode cheap.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.sharding import shard_pick
from .layers import CacheView, rmsnorm
from .rope import apply_rope


def init_mla(key, cfg: ModelConfig, spec: LayerSpec):
    d, H = cfg.d_model, cfg.num_heads
    nope, rope_d, vh, lora = (
        cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim,
        cfg.v_head_dim,
        cfg.kv_lora_rank,
    )
    ks = jax.random.split(key, 6)
    s = 1.0 / np.sqrt(d)
    sl = 1.0 / np.sqrt(lora)
    return {
        "wq": jax.random.normal(ks[0], (d, H, nope + rope_d), jnp.float32) * s,
        "w_dkv": jax.random.normal(ks[1], (d, lora), jnp.float32) * s,
        "kv_norm": jnp.ones((lora,), jnp.float32),
        "w_kr": jax.random.normal(ks[2], (d, rope_d), jnp.float32) * s,
        "w_uk": jax.random.normal(ks[3], (lora, H, nope), jnp.float32) * sl,
        "w_uv": jax.random.normal(ks[4], (lora, H, vh), jnp.float32) * sl,
        "wo": jax.random.normal(ks[5], (H, vh, d), jnp.float32) / np.sqrt(H * vh),
    }


def _mla_scale(cfg: ModelConfig) -> float:
    return 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _project_q(p, x, cfg: ModelConfig, angles):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim :], angles)
    return q_nope, q_rope


def _compress_kv(p, x, cfg: ModelConfig, angles):
    dt = x.dtype
    c_kv = jnp.einsum("bsd,dl->bsl", x, p["w_dkv"].astype(dt))
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dk->bsk", x, p["w_kr"].astype(dt))
    k_rope = apply_rope(k_rope, angles)
    return c_kv, k_rope


def apply_mla(p, x, cfg: ModelConfig, spec: LayerSpec, angles, *, causal=True):
    """Training/prefill MLA (decompressed). x [B,S,D] -> [B,S,D]."""
    dt = x.dtype
    B, S, _ = x.shape
    q_nope, q_rope = _project_q(p, x, cfg, angles)
    c_kv, k_rope = _compress_kv(p, x, cfg, angles)
    k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uk"].astype(dt))
    v = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uv"].astype(dt))

    scores = (
        jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope)
        + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope)
    ) * _mla_scale(cfg)
    scores = scores.astype(jnp.float32)
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
    scores = shard_pick(
        scores,
        ("batch", "heads", None, None),
        ("batch", None, "seq_model", None),
        ("batch", None, None, "seq_model"),
    )
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bhqs,bshk->bqhk", w, v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"].astype(dt))


def prefill_mla(p, x, cfg: ModelConfig, spec: LayerSpec, angles, max_seq: int):
    """MLA prefill emitting the compressed cache."""
    out = apply_mla(p, x, cfg, spec, angles, causal=True)
    c_kv, k_rope = _compress_kv(p, x, cfg, angles)
    S = x.shape[1]
    pad = [(0, 0), (0, max_seq - S), (0, 0)]
    return out, {"c_kv": jnp.pad(c_kv, pad), "k_rope": jnp.pad(k_rope, pad)}


def init_mla_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dtype),
    }


def decode_mla(p, x, cache: CacheView, pos, cfg: ModelConfig, spec: LayerSpec, angles):
    """Absorbed one-token decode against the compressed cache; ``cache`` views
    the layer's ``c_kv`` and ``k_rope``, written in place at slot ``pos``."""
    dt = x.dtype
    q_nope, q_rope = _project_q(p, x, cfg, angles)          # [B,1,H,*]
    c_new, kr_new = _compress_kv(p, x, cfg, angles)         # [B,1,lora], [B,1,rope]
    with jax.named_scope("kv_write"):
        cache = cache.write("c_kv", c_new, (0, pos, 0)).write("k_rope", kr_new, (0, pos, 0))
        c_kv, k_rope = cache.read("c_kv"), cache.read("k_rope")

    # Absorb W_uk into q: score_nope = (q_nope W_uk^T) . c_kv
    q_abs = jnp.einsum("bqhk,lhk->bqhl", q_nope, p["w_uk"].astype(dt))  # [B,1,H,lora]
    scores = (
        jnp.einsum("bqhl,bsl->bhqs", q_abs, c_kv)
        + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope)
    ) * _mla_scale(cfg)
    mask = jnp.arange(c_kv.shape[1]) <= pos
    scores = jnp.where(mask[None, None, None], scores.astype(jnp.float32), -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    ctx = jnp.einsum("bhqs,bsl->bqhl", w, c_kv)             # [B,1,H,lora]
    out = jnp.einsum("bqhl,lhk->bqhk", ctx, p["w_uv"].astype(dt))  # absorb W_uv
    out = jnp.einsum("bqhk,hkd->bqd", out, p["wo"].astype(dt))
    return out, cache
