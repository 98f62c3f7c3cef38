"""Training cells: the program's train step, composed as ``launch/train.py``
composes it, driven from the seed.

Set-up builds one jitted step with the memory plan's offload policy, its
weights (made here from the seed) and its AdamW state, and runs it until
two successive steps compile nothing; its first three steps are the checked
ones. The same step object then runs the window: whole steps until
``seconds`` have passed, each on fresh rows, the next batch prepared while
the device works. After the window the program's state is freed and the
plain reference replays the checked steps.
"""

from __future__ import annotations

import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, checks, weights
from chipbench.reference import adamw as ref_adamw
from chipbench.reference.common import REFERENCE
from chipbench.traffic import SyntheticTokens

CHECKED_STEPS = 3


def _batches(cfg: dict, wl: dict, seed: int):
    data = SyntheticTokens(cfg["vocab_size"], wl["seq_len"], wl["batch"], seed)

    def at(k: int) -> np.ndarray:
        # Model.loss shifts the labels itself (position i predicts labels[i+1]),
        # so tokens and labels are the same ids.
        return data.ids_at(k)[:, : wl["seq_len"]]

    return at


def _feed(ids: np.ndarray) -> dict:
    x = jnp.asarray(ids)
    return {"tokens": x, "labels": x}


def reference_readings(cfg: dict, wl: dict, seed: int, mm=REFERENCE,
                       keep_rows: int | None = None) -> dict:
    """Losses, the first gradient (a tree on the host) and change norms of
    the reference over the checked steps, from the seed's weights and rows
    (the first ``keep_rows`` of each batch, where given: a planted fault)."""
    ref = bench.kind(cfg["kind"]).reference
    o = wl["optimizer"]
    at = _batches(cfg, wl, seed)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def one(params, m, v, t, ids):
        loss, grads = ref.loss_and_grad(params, ids, cfg, wl["reference_rows"], mm)
        params, m, v, clipped = ref_adamw.step(
            params, grads, m, v, t, lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], clip=o["clip"])
        return params, m, v, loss, clipped

    key = weights.seed_key(seed)
    params = weights.make(cfg, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    for t in range(1, CHECKED_STEPS + 1):
        ids = at(t) if keep_rows is None else at(t)[:keep_rows]
        params, m, v, loss, g = one(params, m, v, jnp.float32(t), jnp.asarray(ids))
        losses.append(float(loss))
        if grad is None:
            grad = jax.device_get(g)
        del g
    change = jax.jit(partial(checks.change_norms, cfg))(params, key)
    return {"losses": losses, "grad": grad, "change": np.asarray(change)}


def readings(cfg: dict, wl: dict, seed: int, want: dict) -> dict:
    """What the limits are set from, beside the program's own numbers: the
    control (the reference in float8, in the program's place) and half of
    each batch left out, each compared with the reference as a run compares
    the program."""
    from chipbench.reference.common import CONTROL

    with jax.default_matmul_precision("highest"):
        control = reference_readings(cfg, wl, seed, mm=CONTROL)
        half = reference_readings(cfg, wl, seed, keep_rows=wl["batch"] // 2)
    out = {}
    for name, r in (("control", control), ("half_batch", half)):
        out.update({f"{name}.{k}": v for k, v in checks.train_numbers(r, want).items()})
    return out


def run(wl: dict, cfg: dict, ctx) -> dict:
    from repro.core.planner import MemoryPlanner
    from repro.core.simulator import TPU_V5E
    from repro.launch.steps import build_train_step
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.plan import PlanCache, PlanKey

    seed, dev = ctx.seed, ctx.devices[0]
    B, S = wl["batch"], wl["seq_len"]
    o = wl["optimizer"]
    mc = ctx.program_config(cfg)
    model = build_model(mc)
    at = _batches(cfg, wl, seed)
    key = weights.seed_key(seed)

    params = weights.make(cfg, seed)
    bad = weights.layout_mismatch(jax.eval_shape(lambda: params), model.init_shapes())
    if bad:
        raise SystemExit(f"chipbench: the weights do not fit the program's layout: {bad}")

    t = time.perf_counter()
    probe = {k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "labels")}
    planner = MemoryPlanner(
        lambda p, b: model.loss(p, b)[0], model.init_shapes(), probe, hw=TPU_V5E,
        cache=PlanCache(str(bench.PLAN_CACHE)),
        key=PlanKey(cfg["arch"], f"train:b{B}s{S}:L{cfg['layers']}", TPU_V5E.name),
    )
    budget = wl["hbm_budget"]
    if budget == "bytes_limit":
        budget = dev.memory_stats()["bytes_limit"]
    plan = planner.offload_plan(int(budget))
    plan_s = time.perf_counter() - t
    bench.log(f"plan at {budget} bytes: offload {plan.offload_names}, "
              f"from cache {planner.from_cache}, {plan_s:.3f} s")

    step_fn = build_train_step(model, mc, lr=o["lr"], remat_policy=plan.policy())
    step = jax.jit(ctx.break_step(step_fn), donate_argnums=(0, 1))
    opt = jax.jit(adamw_init)(params)
    change = jax.jit(partial(checks.change_norms, cfg))

    # Warm-up: the checked steps, then on until two successive steps compile nothing.
    k, quiet, losses = 0, 0, []
    grad = delta = None
    while k < CHECKED_STEPS or quiet < 2:
        k += 1
        batch = _feed(at(k))
        before = ctx.compiles.count
        params, opt, met = step(params, opt, batch, jnp.int32(k))
        jax.block_until_ready(met)
        quiet = quiet + 1 if ctx.compiles.count == before else 0
        if k <= CHECKED_STEPS:
            losses.append(float(met["loss"]))
        if k == 1:
            # The first gradient as AdamW got it: m after one step over 1 - b1.
            grad = jax.tree.map(lambda m: m / (1.0 - o["b1"]), jax.device_get(opt.m))
        if k == CHECKED_STEPS:
            delta = np.asarray(change(params, key))
    bench.log(f"warm-up: {k} steps, losses {losses}")
    mem = step.lower(params, opt, batch, jnp.int32(k)).compile().memory_analysis()

    # The window.
    spans = ctx.spans
    batch = _feed(at(k + 1))
    window_losses = []
    profiler = ctx.start_trace()
    compile_s = ctx.compiles.seconds + ctx.compiles.lowering_s
    t0, c0 = time.perf_counter(), ctx.compiles.count
    with spans("window"):
        while True:
            k += 1
            with spans("dispatch"):
                params, opt, met = step(params, opt, batch, jnp.int32(k))
            with spans("batch_prep"):
                batch = _feed(at(k + 1))
            with spans("sync"):
                jax.block_until_ready(met)
            window_losses.append(met["loss"])
            elapsed = time.perf_counter() - t0
            if (ctx.trace and len(window_losses) >= wl["trace_steps"]) or (
                    not ctx.trace and elapsed >= ctx.seconds):
                break
    t1 = time.perf_counter()
    trace = ctx.stop_trace(profiler)
    window_compiles = ctx.compiles.count - c0
    steps = len(window_losses)
    failed = int(np.sum(~np.isfinite(np.asarray([float(x) for x in window_losses]))))
    peak = dev.memory_stats()["peak_bytes_in_use"] if dev.platform == "tpu" else 0
    setup_s = t0 - ctx.start

    del params, opt, met, batch, window_losses
    gc.collect()

    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        want = reference_readings(cfg, wl, seed)
    got = {"losses": losses, "grad": grad, "change": delta}
    numbers = checks.train_numbers(got, want)
    bench.log(f"reference: {time.perf_counter() - t:.1f} s, losses {want['losses']}")
    if ctx.extra.get("readings"):
        numbers.update(readings(cfg, wl, seed, want))

    tokens_per_s = steps * B * S / (t1 - t0)
    footprint = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "attempted": steps,
        "failed": failed,
        "numbers": numbers,
        "window_s": t1 - t0,
        "window_compiles": window_compiles,
        "memory_peak_bytes": peak,
        "trace": trace,
        "e2e": {
            "train_tokens_per_s": tokens_per_s,
            "setup_s": setup_s,
            "peak_hbm_gib": footprint / 2**30,
        },
        "obs": {
            "kind": "train",
            "plan_s": plan_s,
            "compile_s": compile_s,
            "host_temp_bytes": mem.host_temp_size_in_bytes,
            "offload_names": list(plan.offload_names),
            "tokens_per_s": tokens_per_s,
            "flops_per_token": bench.kind(cfg["kind"]).train_flops_per_token(cfg),
            "peaks": ctx.peaks,
            "trace": trace,
        },
        "memory": {
            "peak_bytes_in_use": peak,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "host_temp_bytes": mem.host_temp_size_in_bytes,
            "footprint_bytes": footprint,
        },
    }
