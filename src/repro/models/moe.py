"""Mixture-of-Experts FFN with sort-based capacity dispatch (EP-friendly).

Dispatch is gather/scatter (no [T, E, C] one-hot matmul): token-expert pairs
are sorted by expert, ranked within their expert group, and dropped beyond
capacity C = ceil(T * top_k / E * capacity_factor).  FLOPs are therefore the
honest E*C*(3*2*d*f) expert compute — crucial for roofline fidelity (a dense
one-hot dispatch would inflate llama4's compute 128x).

Expert weights are [E, d, f]; sharding E over the `model` mesh axis gives
expert parallelism (llama4: 128/16 = 8 experts per shard); the scatter/gather
lowers to all-to-all under GSPMD.

Routers: softmax top-k with renormalization (deepseek) or sigmoid top-1
(llama4).  An auxiliary load-balance loss (Switch-style) is returned for
training.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from .layers import init_dense_ffn, apply_dense_ffn


def init_moe(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (E, d, f), jnp.float32) * s_in,
        "w_up": jax.random.normal(ks[2], (E, d, f), jnp.float32) * s_in,
        "w_down": jax.random.normal(ks[3], (E, f, d), jnp.float32) * s_out,
    }
    if cfg.num_shared_experts:
        shared_f = f * cfg.num_shared_experts
        p["shared"] = init_dense_ffn(ks[4], cfg, d_ff=shared_f)
    return p


def _route(p, xt, cfg: ModelConfig):
    """xt [T, D] -> (gates [T,k], idx [T,k], aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    k, E = cfg.top_k, cfg.num_experts
    if cfg.router_type == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        gates, idx = jax.lax.top_k(probs, k)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * sum_e f_e * p_e.
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)        # [T, E]
    f_e = onehot.mean(0)
    p_e = probs.mean(0) if cfg.router_type != "sigmoid" else jax.nn.softmax(logits, -1).mean(0)
    aux = E * jnp.sum(f_e * p_e)
    return gates, idx, aux


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

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        if cfg.router_type == "sigmoid":
            probs = jax.nn.sigmoid(logits)
            gates, idx = jax.lax.top_k(probs, k)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            gates, idx = jax.lax.top_k(probs, k)
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

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
    """x [B, S, D] -> (y [B, S, D], aux_loss).

    Dispatch is *grouped*: tokens are split into G groups matching the batch
    sharding (G = token_group_count(); 1 on a single device / baseline
    profile).  Sorting, capacity ranking, scatter and gather all use
    group-local indices, so under the "moe_local" profile every index
    operation is shard-local — no cross-device all-reduce of [T, D] scatter
    partials (the dominant collective of the naive global dispatch; see
    EXPERIMENTS.md §Perf cell B).  Capacity is per group (C/G each), which
    slightly raises drop variance vs a global capacity pool — recorded in
    DESIGN.md.
    """
    from repro.distributed.sharding import _CTX, token_group_count

    rules = _CTX.rules or {}
    if rules.get("moe_impl") == "shard_map" and _CTX.mesh is not None and not _CTX.mesh.empty:
        return apply_moe_shardmap(p, x, cfg)

    dt = x.dtype
    B, S, D = x.shape
    T = B * S
    k, E = cfg.top_k, cfg.num_experts
    G = token_group_count()
    if T % G:
        G = 1
    Tg = T // G
    xt = x.reshape(T, D)

    gates, idx, aux = _route(p, xt, cfg)
    C = int(np.ceil(Tg * k / E * cfg.capacity_factor))
    C = max(8, -(-C // 8) * 8)  # multiple of 8, >= 8

    flat_e = idx.reshape(G, Tg * k)                           # [G, Tg*k]
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    counts = jax.nn.one_hot(flat_e, E, dtype=jnp.int32).sum(1)  # [G, E]
    start = jnp.cumsum(counts, axis=-1) - counts
    rank = jnp.arange(Tg * k)[None] - jnp.take_along_axis(start, sorted_e, axis=-1)
    token_of = order // k                                     # group-local ids
    g_idx = jnp.arange(G)[:, None] * jnp.ones((1, Tg * k), jnp.int32)

    # Group x expert layout: buf [G, E, C, D] sharded (batch, experts) — each
    # device scatters its own tokens into its own experts' rows; rank >= C
    # drops (mode="drop").
    xg = shard(xt.reshape(G, Tg, D), "tokens", None, None)
    xin = jnp.take_along_axis(xg, token_of[..., None], axis=1)  # [G, Tg*k, D]
    buf = shard(jnp.zeros((G, E, C, D), dt), "tokens", "experts", None, None)
    buf = buf.at[g_idx, sorted_e, rank].set(xin, mode="drop")
    g_ = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dt))
    u = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(dt))
    y = jnp.einsum("gecf,efd->gecd", jax.nn.silu(g_) * u, p["w_down"].astype(dt))
    y = shard(y, "tokens", "experts", None, None)

    gate_sorted = jnp.take_along_axis(gates.reshape(G, Tg * k), order, axis=-1)
    rows = y.at[g_idx, sorted_e, rank].get(mode="fill", fill_value=0)  # [G,Tg*k,D]
    contrib = rows * gate_sorted[..., None].astype(dt)
    out = jnp.zeros((G, Tg, D), dt).at[g_idx, token_of].add(contrib)
    out = shard(out, "tokens", None, None).reshape(T, D)

    if cfg.num_shared_experts:
        out = out + apply_dense_ffn(p["shared"], xt, cfg)
    return out.reshape(B, S, D), aux
