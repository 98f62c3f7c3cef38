"""Decoder-only LM assembly: program segments under lax.scan, all families.

One ``Model`` class serves the 8 decoder-only architectures (dense, MoE, MLA,
SSM, hybrid, VLM); ``encdec.py`` wraps it for whisper.  Execution modes:

  loss(params, batch)                      training forward+CE
  prefill(params, batch)                   full forward -> (last logits, cache)
  decode_step(params, cache, tokens, pos)  one token against the cache

Layers are grouped into program segments (configs/base.py); segments with
repeats > 1 run under ``lax.scan`` with stacked params, which keeps compile
time flat in depth and makes remat/offload policies uniform per layer class
(the granularity AutoSwap's planner operates on — see core/offload.py).

Each mode has its own walker over the segments (``apply_program``,
``prefill_program``, ``decode_program``), and every walker runs each layer
through the one ``layer`` body, which takes its token mixer from ``MIXERS``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import LayerSpec, ModelConfig, Segment
from repro.distributed.sharding import shard
from . import attention, hybrid, mla, moe, ssm
from .layers import (
    CacheView,
    apply_dense_ffn,
    apply_norm,
    chunked_softmax_xent,
    dtype_of,
    embed_tokens,
    init_dense_ffn,
    init_embedding,
    init_norm,
    lm_logits,
)
from .rope import mrope_angles, rope_angles

# ---------------------------------------------------------------- layers

# The token mixer of each kind of layer (``LayerSpec.attn``): a module with
# the same entry points, each reading and writing only its own entries of a
# layer's params (``attn``, ``mamba``, ...) and cache (``kv``, ``ssm``):
#
#   SCOPE                                               its named scope
#   init(keys, cfg, spec)                               -> its params
#   forward(p, h, cfg, spec, angles, causal)            -> h  (training)
#   prefill(p, h, cfg, spec, angles, max_seq)           -> (h, its cache)
#   init_cache(cfg, spec, batch, max_seq, dtype)        -> its cache
#   decode(p, h, cache, index, pos, cfg, spec, angles)  -> (h, its cache)
#
# ``decode`` reads and writes through ``CacheView``. A new kind of mixer is
# one such module and its row here.
MIXERS = {"full": attention, "window": attention, "mla": mla, "mamba": ssm, "hybrid": hybrid}


def init_layer(key, cfg: ModelConfig, spec: LayerSpec):
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"ln1": init_norm(cfg)}
    if spec.attn != "none":
        # The mixer's keys are ks[0] and ks[1] (hybrid's Mamba).
        p.update(MIXERS[spec.attn].init(ks[:2], cfg, spec))
        if cfg.sandwich_norms:
            p["ln1_post"] = init_norm(cfg)
    if spec.cross_attn:
        p["ln_cross"] = init_norm(cfg)
        p["cross"] = attention.init_projections(ks[2], cfg)
    if spec.ffn != "none":
        p["ln2"] = init_norm(cfg)
        if spec.ffn == "dense":
            p["ffn"] = init_dense_ffn(ks[3], cfg)
        else:
            p["moe"] = moe.init_moe(ks[4], cfg)
        if cfg.sandwich_norms:
            p["ln2_post"] = init_norm(cfg)
    return p


def layer(p, x, cfg: ModelConfig, spec: LayerSpec, mix, cross_kv, train: bool = False):
    """One layer's blocks, the same in training, prefill and decode: the token
    mixer, cross-attention, then the FFN or MoE, each under its named scope
    with its norm, its sandwich norm and its residual add.

    The mode says what differs. ``mix(mixer, p, h)`` runs its entry of the
    layer's mixer (``MIXERS``) on the normed ``h`` and returns (h, the
    mixer's cache entries); ``cross_kv(p)`` returns the encoder's (k, v) for
    cross-attention and the cache entries that keep them. ``train`` names the
    blocks' outputs for the remat and offload policies (``attn_out``,
    ``ffn_out``; core/offload.py). Returns (x, the layer's cache, MoE aux loss).
    """
    cache: dict[str, Any] = {}
    aux = jnp.zeros((), jnp.float32)

    def residual(x, h, name, post):
        if train:
            h = checkpoint_name(h, name)
        if cfg.sandwich_norms:
            h = apply_norm(p[post], h, cfg)
        return x + h

    if spec.attn != "none":
        mixer = MIXERS[spec.attn]
        with jax.named_scope(mixer.SCOPE):
            h, entries = mix(mixer, p, apply_norm(p["ln1"], x, cfg))
            x = residual(x, h, "attn_out", "ln1_post")
        cache.update(entries)
    if spec.cross_attn:
        with jax.named_scope("cross_attn"):
            h = apply_norm(p["ln_cross"], x, cfg)
            kv, entries = cross_kv(p)
            x = x + attention.apply_cross_attention(p["cross"], h, kv, cfg)
        cache.update(entries)
    if spec.ffn != "none":
        block = "ffn" if spec.ffn == "dense" else "moe"
        with jax.named_scope(block):
            h = apply_norm(p["ln2"], x, cfg)
            if spec.ffn == "dense":
                h = apply_dense_ffn(p["ffn"], h, cfg)
            else:
                h, aux = moe.apply_moe(p["moe"], h, cfg)
            x = residual(x, h, "ffn_out", "ln2_post")
    return x, cache, aux


# --------------------------------------------------------------- program


def init_program(key, cfg: ModelConfig, program: tuple[Segment, ...]):
    """Returns a list of segment params; repeats > 1 get stacked leaves."""
    segs = []
    for si, (unit, reps) in enumerate(program):
        kseg = jax.random.fold_in(key, si)

        def init_unit(k):
            return {
                f"l{i}": init_layer(jax.random.fold_in(k, i), cfg, spec)
                for i, spec in enumerate(unit)
            }

        if reps > 1:
            segs.append(jax.vmap(init_unit)(jax.random.split(kseg, reps)))
        else:
            segs.append(init_unit(kseg))
    return segs


def apply_program(
    segs,
    x,
    cfg: ModelConfig,
    program: tuple[Segment, ...],
    angles,
    enc_out=None,
    causal=True,
    remat: bool = False,
    remat_policy=None,
):
    """Returns (x, total_aux).

    ``remat_policy`` is a jax.checkpoint policy (e.g. the offload policies
    built by core/offload.py); ``remat=True, remat_policy=None`` is full
    per-unit rematerialization.
    """
    total_aux = jnp.zeros((), jnp.float32)
    for (unit, reps), seg_params in zip(program, segs):

        def unit_fn(params, x):
            x = checkpoint_name(x, "block_in")
            aux = jnp.zeros((), jnp.float32)
            for i, spec in enumerate(unit):
                x, _, a = layer(
                    params[f"l{i}"], x, cfg, spec,
                    lambda m, p, h: (m.forward(p, h, cfg, spec, angles, causal), {}),
                    lambda p: (attention.encode_cross_kv(p["cross"], enc_out, cfg), {}),
                    train=True,
                )
                x = shard(x, "batch", "seq", "embed")
                aux = aux + a
            return x, aux

        if remat:
            unit_fn = jax.checkpoint(unit_fn, policy=remat_policy)

        if reps > 1:

            def body(carry, params):
                x, aux = carry
                x, a = unit_fn(params, x)
                return (x, aux + a), None

            (x, total_aux), _ = jax.lax.scan(body, (x, total_aux), seg_params)
        else:
            x, a = unit_fn(seg_params, x)
            total_aux = total_aux + a
    return x, total_aux


def prefill_program(segs, x, cfg, program, angles, max_seq: int, enc_out=None):
    """The prompt through every segment, each layer emitting its decode
    cache. Returns (x, caches), a segment of repeats' caches stacked."""

    def enc_kv(p):
        kv = attention.encode_cross_kv(p["cross"], enc_out, cfg)
        return kv, {"enc_kv": kv}

    caches = []
    for (unit, reps), seg_params in zip(program, segs):

        def unit_fn(params, x):
            cache = {}
            for i, spec in enumerate(unit):
                x, cache[f"l{i}"], _ = layer(
                    params[f"l{i}"], x, cfg, spec,
                    lambda m, p, h: m.prefill(p, h, cfg, spec, angles, max_seq),
                    enc_kv,
                )
                x = shard(x, "batch", "seq", "embed")
            return x, cache

        if reps > 1:

            def body(x, params):
                return unit_fn(params, x)

            x, seg_cache = jax.lax.scan(body, x, seg_params)
        else:
            x, seg_cache = unit_fn(seg_params, x)
        caches.append(seg_cache)
    return x, caches


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int, dtype):
    c = {}
    if spec.attn != "none":
        c = MIXERS[spec.attn].init_cache(cfg, spec, batch, max_seq, dtype)
    if spec.cross_attn:
        # The encoder's k and v, filled at prefill.
        shape = (batch, cfg.enc_seq, cfg.num_kv_heads, cfg.head_dim)
        c["enc_kv"] = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    return c


def init_program_cache(cfg, program, batch, max_seq, dtype):
    caches = []
    for unit, reps in program:
        unit_cache = {
            f"l{i}": init_layer_cache(cfg, spec, batch, max_seq, dtype)
            for i, spec in enumerate(unit)
        }
        if reps > 1:
            unit_cache = jax.tree.map(
                lambda a: jnp.zeros((reps,) + a.shape, a.dtype), unit_cache
            )
        caches.append(unit_cache)
    return caches


def decode_program(segs, caches, x, pos, cfg, program, angles):
    """One token through every segment. Returns (x, caches).

    A segment of repeats carries its stacked cache in the layer loop's carry
    and scans over (layer index, params): each layer reads its own entry by
    that index and writes back only what it changed, one slot-sized
    ``dynamic_update_slice`` in place into the carried buffer (one token's
    k and v, MLA latent or ring slot; a recurrent state whole). A segment of
    one layer writes its entry the same way without the index. The cache is
    never copied whole, so a donated cache is updated in place.
    """
    new_caches = []
    for (unit, reps), seg_params, seg_cache in zip(program, segs, caches):

        def unit_fn(params, cache, x, index):
            cache = dict(cache)
            for i, spec in enumerate(unit):
                c = cache[f"l{i}"]
                x, cache[f"l{i}"], _ = layer(
                    params[f"l{i}"], x, cfg, spec,
                    lambda m, p, h: m.decode(p, h, c, index, pos, cfg, spec, angles),
                    lambda p: _read_enc_kv(c, index),
                )
            return x, cache

        if reps > 1:

            def body(carry, inp):
                x, cache = carry
                index, params = inp
                return unit_fn(params, cache, x, index), None

            (x, seg_cache), _ = jax.lax.scan(
                body, (x, seg_cache), (jnp.arange(reps), seg_params)
            )
        else:
            x, seg_cache = unit_fn(seg_params, seg_cache, x, None)
        new_caches.append(seg_cache)
    return x, new_caches


def _read_enc_kv(cache, index):
    """Decode's cross-attention (k, v): the layer's ``enc_kv``, kept as is."""
    enc = CacheView(cache["enc_kv"], index)
    return (enc.read(0), enc.read(1)), {"enc_kv": cache["enc_kv"]}


def _write_rows(cache, rows, start):
    """``rows``, a group's entry of a cache leaf, into the batch's ``cache`` at
    row ``start`` of its batch axis: the one axis where the two differ."""
    axis = next(i for i, (a, b) in enumerate(zip(cache.shape, rows.shape)) if a != b)
    return jax.lax.dynamic_update_slice_in_dim(cache, rows.astype(cache.dtype), start, axis=axis)


# Prefill takes a batch's rows in groups whose widest feed-forward activation
# stays within this many bytes in bfloat16: a dense layer's [tokens, d_ff]
# intermediate, a MoE layer's [tokens * top_k, d_model] dispatched rows, or
# at least the residual stream.
PREFILL_GROUP_BYTES = 5 << 28


def prefill_group_rows(cfg: ModelConfig, batch: int, seq: int) -> int:
    """Rows of a prompt batch that prefill takes in one pass: the largest
    divisor of ``batch`` whose group keeps within PREFILL_GROUP_BYTES (one row
    at least)."""
    width = max(cfg.d_model, cfg.d_ff, cfg.top_k * cfg.d_model if cfg.num_experts else 0)
    fits = [r for r in range(1, batch + 1)
            if batch % r == 0 and r * seq * width * 2 <= PREFILL_GROUP_BYTES]
    return max(fits, default=1)


# ------------------------------------------------------------------ model
@dataclass
class Model:
    cfg: ModelConfig

    # ---- parameters ----
    def init(self, key):
        cfg = self.cfg
        ke, kp = jax.random.split(key)
        params = {
            "embed": init_embedding(ke, cfg),
            "blocks": init_program(kp, cfg, cfg.program),
            "final_norm": init_norm(cfg),
        }
        return params

    def init_shapes(self):
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    # ---- positions/angles ----
    def _angles(self, positions):
        cfg = self.cfg
        if cfg.num_heads == 0:
            return None
        hd = cfg.qk_rope_head_dim if cfg.kv_lora_rank else cfg.head_dim
        if cfg.mrope_sections is not None:
            return mrope_angles(positions, hd, cfg.rope_theta, cfg.mrope_sections)
        return rope_angles(positions, hd, cfg.rope_theta, cfg.rope_scaling)

    def _embed_inputs(self, params, batch):
        """tokens (+ VLM patch embeds) -> (x [B,S,D], positions)."""
        cfg = self.cfg
        x = embed_tokens(params["embed"], batch["tokens"], cfg)
        if cfg.frontend == "vision_stub" and "patch_embeds" in batch:
            patches = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
        if cfg.scale_embed:
            x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        B, S, _ = x.shape
        if "positions" in batch:
            positions = batch["positions"]
        else:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return x, positions

    # ---- training ----
    def loss(self, params, batch, remat: bool = True, remat_policy=None):
        cfg = self.cfg
        x, positions = self._embed_inputs(params, batch)
        x = shard(x, "batch", "seq", "embed")
        angles = self._angles(positions)
        x, aux = apply_program(
            params["blocks"], x, cfg, cfg.program, angles,
            remat=remat, remat_policy=remat_policy,
        )
        x = apply_norm(params["final_norm"], x, cfg)
        labels = batch["labels"]
        if cfg.frontend == "vision_stub" and "patch_embeds" in batch:
            npatch = batch["patch_embeds"].shape[1]
            pad = jnp.full(labels.shape[:1] + (npatch,), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        # chunked CE: position i predicts labels[i+1]; never materializes BSV
        ce = chunked_softmax_xent(x[:, :-1], params["embed"], labels[:, 1:], cfg)
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}

    # ---- serving ----
    def init_cache(self, batch: int, max_seq: int):
        return init_program_cache(
            self.cfg, self.cfg.program, batch, max_seq, dtype_of(self.cfg)
        )

    def prefill(self, params, batch, max_seq: int | None = None):
        """Forward the prompt, return (last-position logits, filled cache).

        A batch too large for one pass (``prefill_group_rows``) goes through
        in groups of rows, each group writing its rows of the cache in place
        into one cache of the whole batch."""
        cfg = self.cfg
        B, S = batch["tokens"].shape
        rows = prefill_group_rows(cfg, B, S)
        if rows == B:
            return self._prefill(params, batch, max_seq)
        max_seq = max_seq or jax.eval_shape(self._embed_inputs, params, batch)[0].shape[1]

        def group(g, carry):
            logits, cache = carry
            # M-RoPE positions [3, B, S] hold the batch second.
            part = {k: jax.lax.dynamic_slice_in_dim(
                        v, g * rows, rows, axis=v.ndim - 2 if k == "positions" else 0)
                    for k, v in batch.items()}
            part_logits, part_cache = self._prefill(params, part, max_seq)
            logits = jax.lax.dynamic_update_slice_in_dim(logits, part_logits, g * rows, axis=0)
            return logits, jax.tree.map(partial(_write_rows, start=g * rows), cache, part_cache)

        logits = jnp.zeros((B, 1, cfg.vocab_size), jnp.float32)
        return jax.lax.fori_loop(0, B // rows, group, (logits, self.init_cache(B, max_seq)))

    def _prefill(self, params, batch, max_seq: int | None = None):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x, positions = self._embed_inputs(params, batch)
            x = shard(x, "batch", "seq", "embed")
        angles = self._angles(positions)
        S = x.shape[1]
        max_seq = max_seq or S
        with jax.named_scope("layers"):
            x, cache = prefill_program(
                params["blocks"], x, cfg, cfg.program, angles, max_seq
            )
        with jax.named_scope("head"):
            x = apply_norm(params["final_norm"], x, cfg)
            logits = lm_logits(params["embed"], x[:, -1:], cfg)
        return logits, cache

    def decode_step(self, params, cache, tokens, pos):
        """tokens [B,1] int32, pos scalar int32 -> (logits [B,1,V], cache)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = embed_tokens(params["embed"], tokens, cfg)
            if cfg.scale_embed:
                x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        B = x.shape[0]
        positions = jnp.broadcast_to(pos[None, None], (B, 1))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(pos[None, None, None], (3, B, 1))
        angles = self._angles(positions)
        with jax.named_scope("layers"):
            x, new_cache = decode_program(
                params["blocks"], cache, x, pos, cfg, cfg.program, angles
            )
        with jax.named_scope("head"):
            x = apply_norm(params["final_norm"], x, cfg)
            logits = lm_logits(params["embed"], x, cfg)
        return logits, new_cache
