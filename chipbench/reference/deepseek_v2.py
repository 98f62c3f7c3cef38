"""Plain float32 DeepSeek-V2 decoder (deepseek-ai/DeepSeek-V2-Lite): logits of
one sequence, for the experts a configuration holds.

One layer: RMSNorm; multi-head latent attention in its decompressed form:
the query h Wq split into a no-rope and a rope part; the latent c =
RMSNorm(h W_dkv), from which each head's no-rope key c W_uk and value c W_uv
are decompressed; one rope key h W_kr shared by every head; YaRN
frequencies and YaRN's softmax scale (192^-1/2 mscale^2, arXiv:2309.00071 as
DeepSeek-V2 applies it); causal softmax over all earlier positions; the
output through Wo added to the residual. Then RMSNorm and a feed-forward
added to the residual: in the first ``first_k_dense_replace`` layers a
SwiGLU of ``intermediate_size``; after them the router's softmax over all
its outputs, the top ``num_experts_per_tok`` kept as gates (renormalized
only where ``norm_topk_prob``), each held expert's SwiGLU applied to every
token times its gate where the router chose it and nought elsewhere, and the
shared experts' SwiGLU of ``n_shared_experts`` times the expert width. A
final RMSNorm and the untied head give the logits. Attention is computed in
blocks of queries so that a 16k-token sequence fits the chip.

Departures from the published model, as in the program:
- rope is rotate-half over the 64 rope columns; DeepSeek's code
  de-interleaves the pairs first, which under random weights is a fixed
  permutation of the rope columns of Wq and W_kr;
- only the held routed experts (``first_expert`` onward, ``n_routed_experts``
  of ``router_experts``) add their part: what the experts on other chips of
  the deployment would add is left out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import REFERENCE, rmsnorm
from .qwen3 import _q_block


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(cfg: dict) -> dict:
    """The rope's inverse frequencies, the factor on its cos and sin, and the
    softmax scale, from the configuration's ``rope_scaling``."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def turns_at(rotations):  # the pair that turns ``rotations`` times over orig
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_at(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    inv = extra / factor * (1 - keep) + extra * keep
    m_all = _mscale(factor, rs["mscale_all_dim"])
    q_dim = cfg["qk_nope_head_dim"] + dim
    return {"inv": inv, "low": low, "high": high,
            "rope_factor": _mscale(factor, rs["mscale"]) / m_all,
            "scale": q_dim ** -0.5 * m_all * m_all}


def rope(x, pos, y):
    """x [s, heads, d] rotated by its positions pos [s] (rotate-half)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None, None] * y["inv"]
    cos, sin = jnp.cos(ang) * y["rope_factor"], jnp.sin(ang) * y["rope_factor"]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, scale, mm):
    """Causal attention. q, k [s,H,dk], v [s,H,dv] -> [s,H,dv]."""
    s, H, dk = q.shape
    qb = _q_block(s)
    qs = q.reshape(s // qb, qb, H, dk)
    kpos = jnp.arange(s)

    def block(inp):
        i, qi = inp
        scores = mm("qhd,shd->hqs", qi, k) * scale
        qpos = i * qb + jnp.arange(qb)
        scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
        return mm("hqs,shd->qhd", mm.act(jax.nn.softmax(scores, axis=-1)), v)

    return jax.lax.map(block, (jnp.arange(s // qb), qs)).reshape(s, H, v.shape[-1])


def mla(a, h, pos, cfg: dict, y: dict, mm):
    nope = cfg["qk_nope_head_dim"]
    q = mm("sd,dhk->shk", h, a["wq"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, y)], axis=-1)
    c = mm.act(rmsnorm(mm("sd,dl->sl", h, a["w_dkv"]), a["kv_norm"], cfg["rms_norm_eps"]))
    k_nope = mm("sl,lhk->shk", c, a["w_uk"])
    v = mm.act(mm("sl,lhk->shk", c, a["w_uv"]))
    k_rope = rope(mm("sd,dk->sk", h, a["w_kr"])[:, None, :], pos, y)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:2] + k_rope.shape[2:])],
                        axis=-1)
    o = mm.act(attention(mm.act(q), mm.act(k), v, y["scale"], mm))
    return mm("shk,hkd->sd", o, a["wo"])


def swiglu(f, h, mm):
    g = mm.act(jax.nn.silu(mm("sd,df->sf", h, f["w_gate"])) * mm("sd,df->sf", h, f["w_up"]))
    return mm("sf,fd->sd", g, f["w_down"])


def moe(m, h, cfg: dict, mm):
    """The held experts' part of the routed result, plus the shared experts."""
    probs = jax.nn.softmax(mm("sd,de->se", h, m["router"]), axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    def expert(out, inp):
        e, w_gate, w_up, w_down = inp
        gate = jnp.sum(jnp.where(idx == cfg["first_expert"] + e, gates, 0.0), axis=-1)
        y = swiglu({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, h, mm)
        return out + gate[:, None] * y, None

    held = m["w_gate"].shape[0]
    out, _ = jax.lax.scan(expert, swiglu(m["shared"], h, mm),
                          (jnp.arange(held), m["w_gate"], m["w_up"], m["w_down"]))
    return out


def layer(p, x, pos, cfg: dict, y: dict, mm=REFERENCE):
    eps = cfg["rms_norm_eps"]
    h = mm.act(rmsnorm(x, p["ln1"]["scale"], eps))
    x = mm.act(x + mm.act(mla(p["attn"], h, pos, cfg, y, mm)))
    h = mm.act(rmsnorm(x, p["ln2"]["scale"], eps))
    f = swiglu(p["ffn"], h, mm) if "ffn" in p else moe(p["moe"], h, cfg, mm)
    return mm.act(x + mm.act(f))


def logits(params, ids, start: int, cfg: dict, mm=REFERENCE):
    """Logits [s - start, V] at positions start.. of one sequence ids [s]."""
    pos = jnp.arange(ids.shape[0])
    y = yarn(cfg)
    x = mm.act(params["embed"]["tok"][ids])
    dense, moe_layers = params["blocks"]
    x = layer(dense["l0"], x, pos, cfg, y, mm)
    x, _ = jax.lax.scan(lambda x, p: (layer(p, x, pos, cfg, y, mm), None), x, moe_layers["l0"])
    x = mm.act(rmsnorm(x[start:], params["final_norm"]["scale"], cfg["rms_norm_eps"]))
    return mm("sd,vd->sv", x, params["embed"]["head"])
