"""Multi-head Latent Attention (DeepSeek-V2) with compressed KV cache.

Train/prefill: decompress c_kv into per-head K_nope/V and run standard MHA,
in blocks of queries from the same length as GQA (``attention.
CHUNKED_THRESHOLD``, ``Q_BLOCK``), so that a 16k prompt never holds its
whole [H, S, S] scores.
Decode: the *absorbed* formulation — W_uk folds into the query and W_uv into
the output so attention runs directly against the cached latent: the
compressed c_kv and the shared rope key side by side, [S, B, kv_lora +
qk_rope].  Cache bytes per token: (kv_lora + qk_rope) vs 2*H*head_dim for
vanilla GQA — the 512+64 vs 4096 compression that makes 32k decode cheap.

Scopes as in GQA: ``qkv`` (projections, compression, rope key, W_uk),
``kv_write``, ``sdpa`` (scores, mask, softmax, context), ``out_proj``.

The token mixer of ``mla`` layers (``transformer.MIXERS``): it reads the
layer's ``attn`` params and writes its ``kv`` cache, the latent.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.sharding import shard_pick
from .attention import CHUNKED_THRESHOLD, Q_BLOCK
from .layers import CacheView, rmsnorm
from .rope import apply_rope, yarn_mscale, yarn_rope_factor

SCOPE = "mla"


def init(keys, cfg: ModelConfig, spec: LayerSpec):
    """The layer's ``attn`` params, made from the first of its mixer ``keys``."""
    d, H = cfg.d_model, cfg.num_heads
    nope, rope_d, vh, lora = (
        cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim,
        cfg.v_head_dim,
        cfg.kv_lora_rank,
    )
    ks = jax.random.split(keys[0], 6)
    s = 1.0 / np.sqrt(d)
    sl = 1.0 / np.sqrt(lora)
    return {"attn": {
        "wq": jax.random.normal(ks[0], (d, H, nope + rope_d), jnp.float32) * s,
        "w_dkv": jax.random.normal(ks[1], (d, lora), jnp.float32) * s,
        "kv_norm": jnp.ones((lora,), jnp.float32),
        "w_kr": jax.random.normal(ks[2], (d, rope_d), jnp.float32) * s,
        "w_uk": jax.random.normal(ks[3], (lora, H, nope), jnp.float32) * sl,
        "w_uv": jax.random.normal(ks[4], (lora, H, vh), jnp.float32) * sl,
        "wo": jax.random.normal(ks[5], (H, vh, d), jnp.float32) / np.sqrt(H * vh),
    }}


def _mla_scale(cfg: ModelConfig) -> float:
    """1/sqrt(q head dim), times YaRN's mscale squared where rope is YaRN."""
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(x, angles, cfg: ModelConfig):
    x = apply_rope(x, angles)
    factor = yarn_rope_factor(cfg.rope_scaling)
    return x if factor == 1.0 else x * jnp.asarray(factor, x.dtype)


def _project_q(p, x, cfg: ModelConfig, angles):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = _rope(q[..., cfg.qk_nope_head_dim :], angles, cfg)
    return q_nope, q_rope


def _compress_kv(p, x, cfg: ModelConfig, angles):
    dt = x.dtype
    c_kv = jnp.einsum("bsd,dl->bsl", x, p["w_dkv"].astype(dt))
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dk->bsk", x, p["w_kr"].astype(dt))
    k_rope = _rope(k_rope, angles, cfg)
    return c_kv, k_rope


def _attend(q_nope, q_rope, k_nope, k_rope, v, q_pos, cfg: ModelConfig, causal: bool):
    """Queries at positions q_pos [Sq] over all S keys -> [B,Sq,H,vh]."""
    dt = q_nope.dtype
    S = k_nope.shape[1]
    scores = (
        jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope)
        + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope)
    ) * _mla_scale(cfg)
    scores = scores.astype(jnp.float32)
    if causal:
        mask = q_pos[:, None] >= jnp.arange(S)[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
    scores = shard_pick(
        scores,
        ("batch", "heads", None, None),
        ("batch", None, "seq_model", None),
        ("batch", None, None, "seq_model"),
    )
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bhqs,bshk->bqhk", w, v)


def _mla(p, x, cfg: ModelConfig, angles, causal: bool):
    """Decompressed MLA over the whole sequence: (out [B,S,D], c_kv, k_rope).
    From ``CHUNKED_THRESHOLD`` positions the queries go in blocks of
    ``Q_BLOCK``, each over every key."""
    dt = x.dtype
    B, S, _ = x.shape
    with jax.named_scope("qkv"):
        q_nope, q_rope = _project_q(p, x, cfg, angles)
        c_kv, k_rope = _compress_kv(p, x, cfg, angles)
        k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uk"].astype(dt))
        v = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uv"].astype(dt))
    with jax.named_scope("sdpa"):
        if S < CHUNKED_THRESHOLD:
            out = _attend(q_nope, q_rope, k_nope, k_rope, v, jnp.arange(S), cfg, causal)
        else:
            nq = S // Q_BLOCK
            assert nq * Q_BLOCK == S, (S, Q_BLOCK)

            def blocks(q):  # [B,S,H,k] -> [nq,B,Q_BLOCK,H,k]
                return q.reshape(B, nq, Q_BLOCK, *q.shape[2:]).swapaxes(0, 1)

            def block(carry, inp):
                i, qn, qr = inp
                q_pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
                return carry, _attend(qn, qr, k_nope, k_rope, v, q_pos, cfg, causal)

            _, out = jax.lax.scan(block, None, (jnp.arange(nq), blocks(q_nope), blocks(q_rope)))
            out = out.swapaxes(0, 1).reshape(B, S, *out.shape[3:])
    with jax.named_scope("out_proj"):
        out = jnp.einsum("bqhk,hkd->bqd", out, p["wo"].astype(dt))
    return out, c_kv, k_rope


def forward(p, x, cfg: ModelConfig, spec: LayerSpec, angles, causal: bool = True):
    """Training MLA (decompressed). x [B,S,D] -> [B,S,D]."""
    return _mla(p["attn"], x, cfg, angles, causal)[0]


def _latent_width(cfg: ModelConfig) -> int:
    """c_kv and the rope key, padded to whole 128-lane tiles."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _pad_to(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _latent(c_kv, k_rope, cfg: ModelConfig):
    """[B,S,kv_lora], [B,S,qk_rope] -> the cache's [S,B,latent width]."""
    return _pad_to(jnp.concatenate([c_kv, k_rope], axis=-1), _latent_width(cfg)).swapaxes(0, 1)


def prefill(p, x, cfg: ModelConfig, spec: LayerSpec, angles, max_seq: int):
    """MLA prefill emitting the latent cache (below)."""
    out, c_kv, k_rope = _mla(p["attn"], x, cfg, angles, causal=True)
    S = x.shape[1]
    with jax.named_scope("kv_write"):
        latent = _latent(c_kv, k_rope, cfg)
        return out, {"kv": {"latent": jnp.pad(latent, [(0, max_seq - S), (0, 0), (0, 0)])}}


def init_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int, dtype):
    """The layer's ``kv``: the latent cache [max_seq, batch, latent width],
    each position's c_kv, then its rope key, then zeros to a whole number of
    128 lanes. So laid out, XLA keeps the donated cache's own layout through
    the decode step. Otherwise it relays the whole stacked cache out and
    back each step: batch-major, since a slot write then touches a partial
    (8, 128) tile of every sequence (4 GiB at batch 32 over 16,640
    positions); 576 lanes wide, or with the 64-wide rope key in an array of
    its own, since it lays those out positions-minor for the absorbed
    products."""
    return {"kv": {"latent": jnp.zeros((max_seq, batch, _latent_width(cfg)), dtype)}}


def decode(p, x, cache, index, pos, cfg: ModelConfig, spec: LayerSpec, angles):
    """Absorbed one-token decode against the latent cache: the layer's
    ``kv`` (with ``index``, stacked; see ``CacheView``), its ``latent``
    written in place at slot ``pos``."""
    p = p["attn"]
    kv = CacheView(cache["kv"], index)
    dt = x.dtype
    with jax.named_scope("qkv"):
        q_nope, q_rope = _project_q(p, x, cfg, angles)          # [B,1,H,*]
        c_new, kr_new = _compress_kv(p, x, cfg, angles)         # [B,1,lora], [B,1,rope]
        # Absorb W_uk into q: score_nope = (q_nope W_uk^T) . c_kv; the rope
        # part scores against the rope key beside it in the latent.
        q_abs = jnp.einsum("bqhk,lhk->bqhl", q_nope, p["w_uk"].astype(dt))  # [B,1,H,lora]
        q_lat = _pad_to(jnp.concatenate([q_abs, q_rope], axis=-1), _latent_width(cfg))
    with jax.named_scope("kv_write"):
        kv = kv.write("latent", _latent(c_new, kr_new, cfg), (pos, 0, 0))
        latent = kv.read("latent")                              # [S,B,latent width]

    with jax.named_scope("sdpa"):
        scores = jnp.einsum("bqhl,sbl->bhqs", q_lat, latent) * _mla_scale(cfg)
        mask = jnp.arange(latent.shape[0]) <= pos
        scores = jnp.where(mask[None, None, None], scores.astype(jnp.float32), -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        # Over the whole latent, the rope key's columns then dropped: one
        # read of the cache, no copy of its c_kv columns.
        ctx = jnp.einsum("bhqs,sbl->bqhl", w, latent)[..., : cfg.kv_lora_rank]  # [B,1,H,lora]
    with jax.named_scope("out_proj"):
        out = jnp.einsum("bqhl,lhk->bqhk", ctx, p["w_uv"].astype(dt))  # absorb W_uv
        out = jnp.einsum("bqhk,hkd->bqd", out, p["wo"].astype(dt))
    return out, {"kv": kv.tree}
