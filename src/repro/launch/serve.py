"""Batched serving driver: prefill then decode with KV caches.

Serves a (smoke or full) model on the available devices: batches requests,
prefill-fills the cache from the prompt, then decodes greedily with the
donated-cache serve step — the same functions the decode dry-run cells
lower.  The AutoSwap planner can report on the serve step too (--plan):
with MoE models its candidate filter picks up inactive expert shards, with
dense models the KV cache dominates and the planner correctly reports
nothing swappable below the threshold (documented behaviour, DESIGN.md §6).

With ``--plan-cache DIR`` the prefill and decode step plans are solved
through the repro.plan pipeline and persisted as per-arch artifacts keyed
by (arch, step signature, hardware): a second serving process — e.g. a
decode worker next to a prefill worker, or the next restart — restores the
solved plan from DIR instead of re-tracing the step.

``--layers N`` serves the first N layers at published widths (whole
periods of the layer program), leaving room for the KV cache on one chip.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \\
      --batch 4 --prompt-len 32 --gen 16 [--plan] [--plan-cache /tmp/plans]
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --layers 2 \\
      --batch 4 --prompt-len 512 --gen 8 --plan
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import config_tag, launch_config, list_archs
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model


@dataclass
class ServeRun:
    """One ``main`` call: the generated tokens, plus what a caller needs to
    check the decode path against a prefill (the model, its parameters, the
    prompt, the cache length and the logits of the first decode step)."""

    tokens: jax.Array                    # [B, gen] greedy ids
    first_decode_logits: jax.Array | None  # [B, 1, V]; None when gen == 1
    prompt: dict
    model: Any
    params: Any
    max_seq: int


def serve_batch_struct(cfg, B: int, P: int) -> dict:
    """Shape/dtype spec of one serving batch — the single source of truth
    shared by the planner (abstract trace) and main() (concrete arrays)."""
    batch = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    if cfg.frontend == "vision_stub":
        npatch = min(cfg.num_patch_tokens, 8)
        batch["patch_embeds"] = jax.ShapeDtypeStruct((B, npatch, cfg.d_model), jnp.float32)
        batch["positions"] = jax.ShapeDtypeStruct((3, B, P + npatch), jnp.int32)
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model), jnp.float32)
    return batch


def plan_serve_steps(model, cfg, args, max_seq: int, plan_cache=None):
    """Solve (or restore) the memory plans for the prefill and decode steps.

    Returns {role: (planner, PoolReport)} for "prefill" and "decode".
    """
    from repro.core.planner import MemoryPlanner
    from repro.core.simulator import TPU_V5E
    from repro.plan import PlanCache, PlanKey

    if plan_cache is None and args.plan_cache:
        plan_cache = PlanCache(args.plan_cache)
    B, P = args.batch, args.prompt_len
    pshapes = model.init_shapes()
    batch = serve_batch_struct(cfg, B, P)

    def prefill_fn(params, b):
        return model.prefill(params, b, max_seq=max_seq)

    _, cache_struct = jax.eval_shape(prefill_fn, pshapes, batch)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    steps = {
        "prefill": (prefill_fn, (pshapes, batch)),
        "decode": (model.decode_step, (pshapes, cache_struct, tok, pos)),
    }
    tag = config_tag(smoke=args.smoke, layers=args.layers)
    out = {}
    for role, (fn, fargs) in steps.items():
        key = PlanKey(args.arch, f"{role}:b{B}p{P}s{max_seq}{tag}", TPU_V5E.name)
        planner = MemoryPlanner(
            fn, *fargs, hw=TPU_V5E, cache=plan_cache, key=key, size_threshold=1 << 18
        )
        rep = planner.report()
        src = "restored from cache" if planner.from_cache else "solved"
        print(
            f"[plan] {role}: {src}  vars={rep.num_variables} "
            f"peak={rep.peak_load/2**20:.1f}MiB smartpool x{rep.smartpool_ratio:.4f} "
            f"cnmem x{rep.cnmem_ratio:.4f}"
        )
        # AutoSwap at 80% of peak: MoE models surface inactive expert shards
        # here; dense models correctly report nothing swappable (DESIGN.md §6).
        sw = planner.swap_report(int(rep.peak_load * 0.8))
        print(
            f"[plan] {role}: AutoSwap@80%: {sw.num_selected} vars "
            f"({sw.selected_bytes/2**20:.1f}MiB) swappable, "
            f"simulated overhead {sw.overhead*100:.2f}%"
        )
        out[role] = (planner, rep)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep the first N layers at published widths "
                         "(default: the config's full depth)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan", action="store_true",
                    help="print SmartPool/AutoSwap reports for prefill + decode steps")
    ap.add_argument("--plan-cache", default=None,
                    help="directory of solved plan artifacts shared across "
                         "prefill/decode processes (solve once, reload after)")
    ap.add_argument("--colocate", action="store_true",
                    help="co-schedule the prefill and decode steps as two "
                         "tenants of the shared-HBM memory runtime and print "
                         "the per-tenant overhead / aggregate peak report")
    ap.add_argument("--colocate-budget-frac", type=float, default=0.8,
                    help="shared budget as a fraction of summed step peaks")
    ap.add_argument("--channels", type=int, default=2,
                    help="DMA channels for the --colocate runtime")
    from repro.obs import add_obs_args

    add_obs_args(ap)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = launch_config(args.arch, smoke=args.smoke, layers=args.layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    B, P = args.batch, args.prompt_len
    max_seq = P + args.gen + (cfg.num_patch_tokens if cfg.frontend == "vision_stub" else 0)

    if args.plan or args.plan_cache or args.colocate:
        from repro.plan import PlanCache

        plan_cache = PlanCache(args.plan_cache) if args.plan_cache else None
        planned = plan_serve_steps(model, cfg, args, max_seq, plan_cache=plan_cache)
        if args.colocate:
            # The serving colocation case: prefill + decode as two tenants of
            # one shared HBM budget (TENSILE's regime), driven by the same
            # solved programs the planner just produced/restored.
            from repro.core.simulator import TPU_V5E
            from repro.launch.colocate import print_colocation
            from repro.obs import export_monitor, export_trace, recorder_for
            from repro.runtime import colocate_programs

            programs = {
                f"{args.arch}:{role}": planner.program
                for role, (planner, _rep) in planned.items()
            }
            recorder = recorder_for(args)
            result = colocate_programs(
                programs, TPU_V5E,
                budget_frac=args.colocate_budget_frac,
                channels=args.channels,
                size_threshold=1 << 18,
                cache=plan_cache,
                record_events=args.record_events,
                obs=recorder,
            )
            print_colocation(result)
            export_trace(args, recorder, result.report)
            export_monitor(args, recorder)
            if args.verify:
                from repro.analyze import verify_launch

                verify_launch(args, programs=programs, recorder=recorder,
                              report=result.report)
    key = jax.random.PRNGKey(args.seed + 1)
    spec = serve_batch_struct(cfg, B, P)
    batch = {"tokens": jax.random.randint(key, spec["tokens"].shape, 0, cfg.vocab_size, jnp.int32)}
    if "patch_embeds" in spec:
        batch["patch_embeds"] = jnp.zeros(spec["patch_embeds"].shape, spec["patch_embeds"].dtype)
        S = spec["positions"].shape[-1]
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, None], spec["positions"].shape
        )
    if "frames" in spec:
        batch["frames"] = jnp.zeros(spec["frames"].shape, spec["frames"].dtype)

    t0 = time.time()
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_seq=max_seq))
    logits, cache = prefill(params, batch)
    next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    jax.block_until_ready((next_tok, cache))
    print(f"prefill: {B}x{P} in {time.time()-t0:.2f}s")

    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    pos0 = P + (min(cfg.num_patch_tokens, 8) if cfg.frontend == "vision_stub" else 0)
    out_tokens = [next_tok]
    first_decode_logits = None
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(params, cache, next_tok, jnp.asarray(pos0 + i, jnp.int32))
        if i == 0:
            first_decode_logits = logits
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out_tokens.append(next_tok)
    jax.block_until_ready((next_tok, cache))
    dt = time.time() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"decode: {args.gen} tokens x {B} seqs in {dt:.2f}s "
          f"({B*(args.gen-1)/max(dt,1e-9):.1f} tok/s)")
    print("sample:", gen[0, :12].tolist())
    return ServeRun(gen, first_decode_logits, batch, model, params, max_seq)


if __name__ == "__main__":
    main()
