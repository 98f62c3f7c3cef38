"""Plain float32 Qwen3 decoder (Qwen/Qwen3-4B): logits of one sequence.

One layer: RMSNorm; grouped-query attention whose queries and keys get a
per-head RMSNorm and then rotary embeddings (rotate-half, theta from the
configuration), causal softmax over all earlier positions; the output
projection added to the residual; RMSNorm and a SwiGLU feed-forward added
to the residual. A final RMSNorm and the head tied to the embedding give
the logits. Attention is computed in blocks of queries so that a 16k-token
sequence fits the chip; nothing else is approximated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import REFERENCE, rmsnorm


def rope(x, pos, theta):
    """x [s, heads, hd] rotated by its positions pos [s]."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _q_block(s: int) -> int:
    for qb in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if s % qb == 0:
            return qb
    return 1


def attention(q, k, v, mm):
    """Causal GQA. q [s,H,hd], k/v [s,KV,hd] -> [s,H,hd]."""
    s, H, hd = q.shape
    KV = k.shape[1]
    qb = _q_block(s)
    qg = q.reshape(s // qb, qb, KV, H // KV, hd)
    kpos = jnp.arange(s)

    def block(inp):
        i, qi = inp
        scores = mm("qkgd,skd->kgqs", qi, k) / jnp.sqrt(jnp.float32(hd))
        qpos = i * qb + jnp.arange(qb)
        scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
        return mm("kgqs,skd->qkgd", mm.act(jax.nn.softmax(scores, axis=-1)), v)

    out = jax.lax.map(block, (jnp.arange(s // qb), qg))
    return out.reshape(s, H, hd)


def layer(p, x, pos, cfg: dict, mm=REFERENCE):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = p["attn"]
    h = mm.act(rmsnorm(x, p["ln1"]["scale"], eps))
    q = mm.act(rope(rmsnorm(mm("sd,dhk->shk", h, a["wq"]), a["q_norm"], eps), pos, theta))
    k = mm.act(rope(rmsnorm(mm("sd,dhk->shk", h, a["wk"]), a["k_norm"], eps), pos, theta))
    v = mm.act(mm("sd,dhk->shk", h, a["wv"]))
    o = mm.act(attention(q, k, v, mm))
    x = mm.act(x + mm.act(mm("shk,hkd->sd", o, a["wo"])))
    f = p["ffn"]
    h = mm.act(rmsnorm(x, p["ln2"]["scale"], eps))
    g = mm.act(jax.nn.silu(mm("sd,df->sf", h, f["w_gate"])) * mm("sd,df->sf", h, f["w_up"]))
    return mm.act(x + mm.act(mm("sf,fd->sd", g, f["w_down"])))


def logits(params, ids, start: int, cfg: dict, mm=REFERENCE):
    """Logits [s - start, V] at positions start.. of one sequence ids [s]."""
    pos = jnp.arange(ids.shape[0])
    x = mm.act(params["embed"]["tok"][ids])
    x, _ = jax.lax.scan(lambda x, p: (layer(p, x, pos, cfg, mm), None), x,
                        params["blocks"][0]["l0"])
    x = mm.act(rmsnorm(x[start:], params["final_norm"]["scale"], cfg["rms_norm_eps"]))
    return mm("sd,vd->sv", x, params["embed"]["tok"])
