"""Mixture-of-Experts FFN: dropless grouped dispatch over the experts held.

A layer holds routed experts ``first_expert ..`` (``cfg.num_held_experts``
of the ``num_experts`` its router routes over: one chip's share under
expert parallelism, or all of them) and computes their part of the result:
every token is routed over all the router's outputs, the token-expert pairs
whose expert is held are sorted by expert, and each held expert's SwiGLU
runs as one grouped product (``lax.ragged_dot``) over its rows. No pair is
dropped and no capacity is set, so the operations follow the pairs that
land on the held experts. The shared experts are added once.

Expert weights are [E_held, d, f]. ``apply_moe_shardmap`` is the explicit
expert-parallel exchange over a mesh (capacity-based, all experts held).

Routers: softmax top-k, renormalized where ``norm_topk_prob`` (deepseek-v2
publishes it false), or sigmoid top-1 (llama4).  An auxiliary load-balance
loss (Switch-style) is returned for training.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from .layers import init_dense_ffn, apply_dense_ffn


def init_moe(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts
    held = cfg.num_held_experts
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (held, d, f), jnp.float32) * s_in,
        "w_up": jax.random.normal(ks[2], (held, d, f), jnp.float32) * s_in,
        "w_down": jax.random.normal(ks[3], (held, f, d), jnp.float32) * s_out,
    }
    if cfg.num_shared_experts:
        shared_f = f * cfg.num_shared_experts
        p["shared"] = init_dense_ffn(ks[4], cfg, d_ff=shared_f)
    return p


def _route(router, xt, cfg: ModelConfig):
    """xt [T, D] -> (logits [T,E], probs [T,E], gates [T,k], idx [T,k]), in
    float32 over all the router's outputs."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    if cfg.router_type == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        gates, idx = jax.lax.top_k(probs, cfg.top_k)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.top_k)
        if cfg.norm_topk_prob:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gates, idx


def _aux_loss(logits, probs, idx, cfg: ModelConfig):
    """Switch-style load-balance aux: E * sum_e f_e * p_e."""
    E = cfg.num_experts
    f_e = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1).mean(0)
    p_e = jax.nn.softmax(logits, -1).mean(0) if cfg.router_type == "sigmoid" else probs.mean(0)
    return E * jnp.sum(f_e * p_e)


def apply_moe_shardmap(p, x, cfg: ModelConfig):
    """Explicit expert-parallel MoE under shard_map (the "moe_shardmap"
    §Perf profile).

    GSPMD cannot partition the data-dependent dispatch scatter without
    resorting to full-tensor all-gathers/all-reduces (measured: 1.8-12 TB
    per device per step on deepseek train_4k).  Here the collective schedule
    is written by hand instead:

      per (data, model) rank: route OWN seq-slice tokens -> local sort ->
      send buffer [E, C, D] -> all_to_all over "model" (the EP exchange) ->
      local expert GEMMs on the rank's E/M experts -> reverse all_to_all ->
      local combine.

    Per-device collective volume is exactly 2 * T_local * k * D bytes of
    all-to-all per layer — the EP floor.  The shared expert and the aux loss
    run outside (plain GSPMD).  Output is seq-sharded over "model" (each
    rank computed its seq slice); the residual add re-gathers it.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import _CTX, batch_axes

    mesh = _CTX.mesh
    dt = x.dtype
    B, S, D = x.shape
    k, E = cfg.top_k, cfg.num_experts
    M = mesh.shape["model"]
    b_axes = batch_axes(mesh)
    DP = 1
    for a in b_axes:
        DP *= mesh.shape[a]
    T_lm = (B // DP) * (S // M)              # tokens per rank
    C = int(np.ceil(T_lm * k / E * cfg.capacity_factor))
    C = max(8, -(-C // 8) * 8)
    E_loc = E // M

    # llama4-scale models keep expert weights FSDP-sharded (F over "data") at
    # rest; the body all-gathers them in bf16 per layer (ZeRO-3 semantics).
    fsdp_gather = bool((_CTX.rules or {}).get("moe_fsdp_gather"))

    def body(xb, router, wg, wu, wd):
        # xb [B_l, S, D] (replicated over model); take this rank's seq slice
        m = jax.lax.axis_index("model")
        B_l = xb.shape[0]
        xs = jax.lax.dynamic_slice_in_dim(xb, m * (S // M), S // M, axis=1)
        xt = xs.reshape(T_lm, D)
        if fsdp_gather:
            wg = jax.lax.all_gather(wg.astype(dt), "data", axis=2, tiled=True)
            wu = jax.lax.all_gather(wu.astype(dt), "data", axis=2, tiled=True)
            wd = jax.lax.all_gather(wd.astype(dt), "data", axis=1, tiled=True)

        _, _, gates, idx = _route(router, xt, cfg)

        flat_e = idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
        start = jnp.cumsum(counts) - counts
        rank_ = jnp.arange(T_lm * k) - start[sorted_e]
        token_of = order // k

        send = jnp.zeros((E, C, D), dt).at[sorted_e, rank_].set(
            xt[token_of], mode="drop"
        )
        # EP exchange: expert e lives on rank e // E_loc
        recv = jax.lax.all_to_all(
            send.reshape(M, E_loc, C, D), "model", split_axis=0, concat_axis=0,
            tiled=True,
        )                                     # [M_src, E_loc, C, D]
        h = recv.transpose(1, 0, 2, 3).reshape(E_loc, M * C, D)
        g_ = jnp.einsum("ecd,edf->ecf", h, wg.astype(dt))
        u_ = jnp.einsum("ecd,edf->ecf", h, wu.astype(dt))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g_) * u_, wd.astype(dt))
        back = jax.lax.all_to_all(
            y.reshape(E_loc, M, C, D).transpose(1, 0, 2, 3), "model",
            split_axis=0, concat_axis=0, tiled=True,
        )                                     # [M, E_loc, C, D] expert-major
        rows_all = back.reshape(E, C, D)
        rows = rows_all.at[sorted_e, rank_].get(mode="fill", fill_value=0)
        contrib = rows * gates.reshape(-1)[order][:, None].astype(dt)
        out = jnp.zeros((T_lm, D), dt).at[token_of].add(contrib)
        return out.reshape(B_l, S // M, D)

    bspec = P(b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None), None, None)
    if fsdp_gather:
        w_specs = (P("model", None, "data"), P("model", None, "data"),
                   P("model", "data", None))
    else:
        w_specs = (P("model", None, None),) * 3
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(bspec, P(None, None)) + w_specs,
        out_specs=P(bspec[0], "model", None),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    aux = jnp.zeros((), jnp.float32)  # load-balance loss skipped in EP mode
    if cfg.num_shared_experts:
        out = out + apply_dense_ffn(p["shared"], x.reshape(B * S, D), cfg).reshape(
            B, S, D
        )
    return out, aux


def apply_moe(p, x, cfg: ModelConfig):
    """x [B, S, D] -> (y [B, S, D], aux_loss): the held experts' part of the
    routed result, dropless, plus the shared experts."""
    from repro.distributed.sharding import _CTX

    rules = _CTX.rules or {}
    if rules.get("moe_impl") == "shard_map" and _CTX.mesh is not None and not _CTX.mesh.empty:
        return apply_moe_shardmap(p, x, cfg)

    dt = x.dtype
    B, S, D = x.shape
    T, k, held = B * S, cfg.top_k, cfg.num_held_experts
    xt = x.reshape(T, D)

    with jax.named_scope("route"):
        logits, probs, gates, idx = _route(p["router"], xt, cfg)
        aux = _aux_loss(logits, probs, idx, cfg)
    with jax.named_scope("dispatch"):
        # Each pair's held expert, or ``held`` where its expert lies elsewhere:
        # sorted, the held experts' pairs come first, grouped by expert.
        expert = idx.reshape(T * k) - cfg.first_expert
        expert = jnp.where((expert >= 0) & (expert < held), expert, held)
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.bincount(expert, length=held + 1)[:held].astype(jnp.int32)
        token_of = order // k
        rows = xt[token_of]                                   # [T*k, D]
    with jax.named_scope("experts"):
        # Rows past the held groups' sizes are computed by no group.
        g = jax.lax.ragged_dot(rows, p["w_gate"].astype(dt), sizes)
        u = jax.lax.ragged_dot(rows, p["w_up"].astype(dt), sizes)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u, p["w_down"].astype(dt), sizes)
    with jax.named_scope("combine"):
        mine = expert[order] < held
        gate = gates.reshape(T * k)[order].astype(dt)
        contrib = jnp.where(mine[:, None], y * gate[:, None], 0)
        out = jnp.zeros((T, D), dt).at[token_of].add(contrib)
    if cfg.num_shared_experts:
        with jax.named_scope("shared"):
            out = out + apply_dense_ffn(p["shared"], xt, cfg)
    return out.reshape(B, S, D), aux
