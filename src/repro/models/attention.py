"""Attention family: GQA full/sliding-window, softcap, qk-norm; KV caches.

The token mixer of ``full`` and ``window`` layers (``transformer.MIXERS``):
``init``, ``forward``, ``prefill``, ``init_cache`` and ``decode`` read the
layer's ``attn`` params and write its ``kv`` cache.

Three execution paths share exact semantics:
  * dense   — materialized scores; small sequences (training at 4k).
  * chunked — lax.scan over query blocks with online masking; bounds live
              memory to O(q_block * S) and, for window layers, slices K/V to
              the reachable window only (true FLOP reduction, not just mask).
  * decode  — single-token step against a full or ring KV cache.  The step
              writes the new token's k and v, one slot, in place into the
              cache that the layer loop carries (``layers.CacheView``), then
              reads the layer's cache back for attention, which contracts
              each KV head with its query group; it never copies the cache
              whole nor expands it to the query heads.

Keys are cached post-RoPE.  Ring caches (window layers) store absolute slot
positions implicitly: slot j at decode position p was written at
q = p - ((p - j) mod W), valid iff q >= 0.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.sharding import shard_pick
from .layers import CacheView, rmsnorm
from .rope import apply_rope

SCOPE = "attn"

# Sequences at or above this length use the chunked path in train/prefill.
CHUNKED_THRESHOLD = 8192
Q_BLOCK = 1024


def _window(spec: LayerSpec) -> int | None:
    """The sliding window a layer attends over: None where it sees every position."""
    return spec.window if spec.attn in ("window", "hybrid") else None


# ------------------------------------------------------------------- init
def init(keys, cfg: ModelConfig, spec: LayerSpec):
    """The layer's ``attn`` params, made from the first of its mixer ``keys``."""
    return {"attn": init_projections(keys[0], cfg)}


def init_projections(key, cfg: ModelConfig):
    """The projections of one attention: a layer's own, or its cross-attention's."""
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, H, hd), jnp.float32) * s,
        "wk": jax.random.normal(ks[1], (d, KV, hd), jnp.float32) * s,
        "wv": jax.random.normal(ks[2], (d, KV, hd), jnp.float32) * s,
        "wo": jax.random.normal(ks[3], (H, hd, d), jnp.float32) / np.sqrt(H * hd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


# ---------------------------------------------------------------- scoring
def _scale(cfg: ModelConfig) -> float:
    return cfg.attn_scale if cfg.attn_scale is not None else 1.0 / np.sqrt(cfg.head_dim)


def _softcap(scores, cap):
    if cap:
        return cap * jnp.tanh(scores / cap)
    return scores


def _expand_kv(k, G: int):
    """[B,S,KV,hd] -> [B,S,KV*G,hd] broadcast, for prefill and training only.

    Those paths contract the expanded K and V on the MXU. On a TPU v5e XLA
    materialises this broadcast instead of fusing it into the matmul that
    reads it, which a decode step cannot afford (in a profile of qwen3-4b
    decode at batch 4 over a 16,896-position cache, a ``broadcast_in_dim``
    to ``bf16[4,16896,8,4,128]`` of 10.9 ms a step for each of K and V), so
    one query position takes ``_sdpa_one_query`` instead.
    """
    if G == 1:
        return k
    B, S, KV, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, G, hd)).reshape(B, S, KV * G, hd)


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """q [B,Sq,H,hd], k/v [B,Sk,KV,hd], mask broadcastable to [B,H,Sq,Sk].

    One query position (the decode step) takes ``_sdpa_one_query``; more
    (prefill, training) expand K and V to the H query heads.  Scores are
    [B, H, Sq, Sk] over *fused* q-heads so the partitioner can shard them on
    H; when H doesn't divide the model axis (llama4: 40 heads, hymba: 25),
    shard_pick falls back to query-seq then key-seq sharding
    (context-parallel / split-KV) — otherwise scores replicate at
    O(S^2 * H) per device.
    """
    with jax.named_scope("sdpa"):
        if q.shape[1] == 1:
            return _sdpa_one_query(q, k, v, mask, cfg)
        B, Sq, H, hd = q.shape
        G = H // k.shape[2]
        k, v = _expand_kv(k, G), _expand_kv(v, G)
        scores = jnp.einsum("bqhd,bshd->bhqs", q, k) * _scale(cfg)
        scores = _softcap(scores.astype(jnp.float32), cfg.attn_softcap)
        if mask is not None:
            scores = jnp.where(mask, scores, -1e30)
        scores = shard_pick(
            scores,
            ("batch", "heads", None, None),
            ("batch_full", None, None, None),
            ("batch", None, "seq_model", None),
            ("batch", None, None, "seq_model"),
        )
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", w, v)


def _sdpa_one_query(q, k, v, mask, cfg: ModelConfig):
    """``_sdpa`` for one query position: q [B,1,H,hd], k/v [B,W,KV,hd], mask
    [b,1,1,W] with b in (1, B), or None.

    Each KV head is contracted with its G = H/KV query heads directly, as a
    multiply and a float32 sum over ``hd`` (scores [B,W,KV,G]) and then over
    W (values): XLA fuses the broadcast of q, and of the softmax weights,
    into the reduction that reads the layer's K or V once, in the cache's
    own layout.  The plain grouped einsum (``bqkgd,bskd->bkgqs``) is not
    used: on a TPU v5e it lowers to a convolution that wants another layout
    of the cache, and XLA then copies the whole stacked K and V of every
    layer into that layout before the layer loop and back after it.  The
    products stay in the compute dtype, with only the sums in float32: K or
    V cast to float32 first made XLA relay the cache the same way.
    """
    B, _, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    f32 = jnp.float32
    qg = q.reshape(B, 1, KV, G, hd)
    scores = jnp.sum(k[:, :, :, None, :] * qg, axis=-1, dtype=f32) * _scale(cfg)
    scores = _softcap(scores, cfg.attn_softcap)  # [B,W,KV,G]
    if mask is not None:
        m = mask.reshape(mask.shape[0], mask.shape[-1])[:, :, None, None]
        scores = jnp.where(m, scores, -1e30)
    scores = shard_pick(
        scores,
        ("batch", None, "kv_heads", None),
        ("batch_full", None, None, None),
        ("batch", "seq_model", None, None),
    )
    w = jax.nn.softmax(scores, axis=1).astype(q.dtype)
    out = jnp.sum(w[..., None] * v[:, :, :, None, :], axis=1, dtype=f32)
    return out.astype(q.dtype).reshape(B, 1, H, hd)


def _causal_window_mask(q_pos, k_pos, causal: bool, window: int | None):
    """[Sq, Sk] boolean mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def mha_dense(q, k, v, cfg: ModelConfig, *, causal=True, window=None):
    Sq, Sk = q.shape[1], k.shape[1]
    mask = _causal_window_mask(jnp.arange(Sq), jnp.arange(Sk), causal, window)
    return _sdpa(q, k, v, mask[None, None], cfg)


def mha_chunked(q, k, v, cfg: ModelConfig, *, causal=True, window=None, q_block=Q_BLOCK):
    """Scan over query blocks; window layers slice K/V to the reachable range."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    nq = S // q_block
    assert nq * q_block == S, (S, q_block)
    qb = q.reshape(B, nq, q_block, H, hd).transpose(1, 0, 2, 3, 4)  # [nq,B,qb,H,hd]

    if window is not None and causal:
        # K/V reachable from q block i: [i*qb - (W-1), i*qb + qb)
        span = q_block + _round_up(window, 128)

        def block(carry, inp):
            i, qi = inp
            start = jnp.maximum(i * q_block + q_block - span, 0)
            ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            q_pos = i * q_block + jnp.arange(q_block)
            k_pos = start + jnp.arange(span)
            mask = _causal_window_mask(q_pos, k_pos, causal, window)
            return carry, _sdpa(qi, ks, vs, mask[None, None], cfg)

        _, out = jax.lax.scan(block, None, (jnp.arange(nq), qb))
    else:

        def block(carry, inp):
            i, qi = inp
            q_pos = i * q_block + jnp.arange(q_block)
            k_pos = jnp.arange(k.shape[1])
            mask = _causal_window_mask(q_pos, k_pos, causal, window)
            return carry, _sdpa(qi, k, v, mask[None, None], cfg)

        _, out = jax.lax.scan(block, None, (jnp.arange(nq), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def _round_up(x, m):
    return (x + m - 1) // m * m


# ------------------------------------------------------------ projections
def _project_qkv(p, x, cfg: ModelConfig, angles):
    """x [B,S,D] -> q [B,S,H,hd], k and v [B,S,KV,hd]: projections, qk-norm, RoPE."""
    with jax.named_scope("qkv"):
        dt = x.dtype
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
            k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
        if angles is not None:
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)
        return q, k, v


def _project_out(p, out, dt):
    """[B,S,H,hd] -> [B,S,D]."""
    with jax.named_scope("out_proj"):
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


# ----------------------------------------------------------- train/prefill
def _full_sequence(p, x, cfg: ModelConfig, spec: LayerSpec, angles, causal: bool):
    """Attention over the whole sequence: (out [B,S,D], k, v)."""
    S = x.shape[1]
    q, k, v = _project_qkv(p, x, cfg, angles)
    fn = mha_chunked if S >= CHUNKED_THRESHOLD else mha_dense
    out = fn(q, k, v, cfg, causal=causal, window=_window(spec))
    return _project_out(p, out, x.dtype), k, v


def forward(p, x, cfg: ModelConfig, spec: LayerSpec, angles, causal: bool = True):
    """Training: x [B,S,D] -> [B,S,D]."""
    return _full_sequence(p["attn"], x, cfg, spec, angles, causal)[0]


def apply_cross_attention(p, x, enc_kv, cfg: ModelConfig):
    """Decoder cross-attention; enc_kv = (k, v) precomputed from the encoder."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k, v = enc_kv
    out = _sdpa(q, k, v, None, cfg)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def encode_cross_kv(p, enc_out, cfg: ModelConfig):
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    return k, v


# ------------------------------------------------------------------ cache
def cache_len(cfg: ModelConfig, spec: LayerSpec, max_seq: int) -> int:
    """Slots of a layer's KV cache: a window layer keeps a ring of its window."""
    window = _window(spec)
    return max_seq if window is None else min(max_seq, window)


def prefill(p, x, cfg: ModelConfig, spec: LayerSpec, angles, max_seq: int):
    """Full-sequence attention that also emits the filled ``kv`` cache."""
    dt = x.dtype
    B, S, _ = x.shape
    out, k, v = _full_sequence(p["attn"], x, cfg, spec, angles, causal=True)

    W = cache_len(cfg, spec, max_seq)
    with jax.named_scope("kv_write"):
        if W >= S:
            pad = [(0, 0), (0, W - S), (0, 0), (0, 0)]
            cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
        else:
            # Ring: slots hold the last W positions p in [S-W, S), slot = p % W.
            pos = S - W + jnp.arange(W)
            slots = pos % W
            cache = {
                "k": jnp.zeros((B, W) + k.shape[2:], dt).at[:, slots].set(k[:, pos]),
                "v": jnp.zeros((B, W) + v.shape[2:], dt).at[:, slots].set(v[:, pos]),
            }
    return out, {"kv": cache}


def init_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int, dtype):
    """The layer's zeroed ``kv`` cache of ``cache_len`` slots."""
    shape = (batch, cache_len(cfg, spec, max_seq), cfg.num_kv_heads, cfg.head_dim)
    return {"kv": {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}}


def decode(p, x, cache, index, pos, cfg: ModelConfig, spec: LayerSpec, angles):
    """One-token decode. x [B,1,D]; pos scalar int32; ``cache`` holds the
    layer's ``kv``, ``k`` and ``v`` [B,W,KV,hd] (with ``index``, stacked; see
    ``CacheView``). Returns (out, its ``kv``) with the token's k and v written
    in place at its slot."""
    p = p["attn"]
    kv = CacheView(cache["kv"], index)
    q, k, v = _project_qkv(p, x, cfg, angles)

    # Unified ring-buffer update: full caches are rings of size max_seq, so
    # slot == pos and the validity mask reduces to idx <= pos; window caches
    # wrap and the mask keeps exactly the last `window` positions.
    W = kv.shape("k")[1]
    slot = pos % W
    with jax.named_scope("kv_write"):
        at = (0, slot, 0, 0)
        kv = kv.write("k", k, at).write("v", v, at)
        ck, cv = kv.read("k"), kv.read("v")

    idx = jnp.arange(W)
    written_at = pos - jnp.mod(pos - idx, W)  # last write position of slot idx
    mask = written_at >= 0
    out = _sdpa(q, ck, cv, mask[None, None, None, :], cfg)
    return _project_out(p, out, x.dtype), {"kv": kv.tree}
