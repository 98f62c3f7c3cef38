import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE two lines above must execute before any jax import (device count locks on
first init) — hence their position.  Never set that flag globally: smoke
tests and benches must see 1 device.

Per cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds sharded ShapeDtypeStructs for params, optimizer state, batch or
     KV cache (launch/steps.py),
  3. ``jit(step).lower(...).compile()`` — proving the distribution config is
     coherent (sharding mismatches, OOM-at-compile, unsupported collectives
     all fail here),
  4. records memory_analysis(), cost_analysis(), and the collective-op byte
     census parsed from the compiled HLO into results/dryrun/<cell>.json —
     the roofline analysis (benchmarks/bench_roofline.py) reads these.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
"""

import argparse
import gc
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, input_specs, list_archs, supports_shape
from repro.core.costmodel import jaxpr_flops_bytes, loop_aware_collectives
from repro.distributed.sharding import use_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    batch_specs,
    build_prefill_step,
    build_serve_step,
    build_train_step,
    cache_specs_tree,
    init_optimizer_shapes,
    param_specs,
    with_sharding,
)
from repro.models import build_model

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(typeexpr: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(typeexpr):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_census(hlo_text: str) -> dict:
    """Per-device collective byte counts from the post-SPMD compiled HLO."""
    out: dict[str, dict] = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        head, _, rest = line.partition("=")
        rest = rest.strip()
        for c in _COLLECTIVES:
            # match `<type> opcode(` including async -start forms; skip -done
            # (same buffer as its -start; counting both would double-count).
            m = re.search(rf"^(.*?)\s{c}(-start)?\(", rest)
            if m:
                out[c]["count"] += 1
                out[c]["bytes"] += _shape_bytes(m.group(1))
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def count_params(shapes) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(shapes)))


def count_active_params(cfg, shapes) -> int:
    """MoE-aware active parameter count (top_k + shared of E experts)."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        n = int(np.prod(leaf.shape))
        parent = keys[-2] if len(keys) > 1 else ""
        if keys[-1] in ("w_gate", "w_up", "w_down") and parent == "moe" and cfg.num_experts:
            n = int(n * cfg.top_k / cfg.num_experts)
        total += n
    return total


# §Perf profiles: each is (logical-rule overrides, train-step kwargs).
# "baseline" is the paper-faithful-era configuration recorded in §Roofline;
# the others are the beyond-paper optimizations iterated in EXPERIMENTS §Perf.
PROFILES: dict[str, dict] = {
    "baseline": {},
    # batch fully sharded over the whole mesh for activations: turns the
    # Megatron-style per-layer activation all-reduces into tiny b-local ones
    "fsdp_act": {"rules": {"batch": ("pod", "data", "model")}},
    # the paper's technique on TPU: offload saved block inputs to pinned_host
    "offload": {"offload_names": ["block_in"]},
    # gradient accumulation: 8 microbatches
    "accum8": {"accum_steps": 8},
    "fsdp_accum8": {"rules": {"batch": ("pod", "data", "model")}, "accum_steps": 8},
    "fsdp_offload": {"rules": {"batch": ("pod", "data", "model")},
                     "offload_names": ["block_in"]},
    # flash-style chunked attention even at 4k: bounds the per-layer scores
    # working set to q_block x S instead of S x S
    "fsdp_chunked": {"rules": {"batch": ("pod", "data", "model")},
                     "chunked_attn": True},
    # B6: expert weights sharded over model only (no FSDP F-dim over data)
    "accum8_nofsdp": {"accum_steps": 8, "fsdp_params": False},
    # B8: hand-written EP all-to-all under shard_map (models/moe.py)
    "moe_shardmap": {"rules": {"moe_impl": "shard_map"}, "fsdp_params": False},
    "moe_shardmap_accum8": {"rules": {"moe_impl": "shard_map"},
                            "accum_steps": 8, "fsdp_params": False},
    # B9: EP shard_map + batch_full attention activations
    "fsdp_moe_shardmap": {"rules": {"moe_impl": "shard_map",
                                    "batch": ("pod", "data", "model")},
                          "fsdp_params": False},
    "fsdp_moe_shardmap_accum8": {"rules": {"moe_impl": "shard_map",
                                           "batch": ("pod", "data", "model")},
                                 "accum_steps": 8, "fsdp_params": False},
    # C: llama4-scale — EP shard_map + ZeRO-3 weight gather + batch_full attn
    "ep_zero3": {"rules": {"moe_impl": "shard_map", "moe_fsdp_gather": True,
                           "batch": ("pod", "data", "model")}},
    "ep_zero3_accum8": {"rules": {"moe_impl": "shard_map", "moe_fsdp_gather": True,
                                  "batch": ("pod", "data", "model")},
                        "accum_steps": 8},
}


def lower_cell(arch: str, shape: str, mesh_kind: str, profile: str = "baseline"):
    """Returns the JSON record for one (arch, shape, mesh) cell."""
    cfg = get_config(arch)
    model = build_model(cfg)
    sp = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    prof = PROFILES[profile]
    rec: dict = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "profile": profile,
        "mesh_shape": dict(mesh.shape), "kind": sp.kind,
        "seq_len": sp.seq_len, "global_batch": sp.global_batch,
    }
    step_kwargs = {}
    if prof.get("accum_steps"):
        step_kwargs["accum_steps"] = prof["accum_steps"]
    if prof.get("offload_names"):
        from repro.core.offload import remat_policy_for

        step_kwargs["remat_policy"] = remat_policy_for(prof["offload_names"]).policy()
    from repro.models import attention as attn_mod

    attn_mod.CHUNKED_THRESHOLD = 2048 if prof.get("rules", {}).get("chunked_attn") or prof.get("chunked_attn") else 8192

    with use_mesh(mesh, rules=prof.get("rules")):
        pshapes = model.init_shapes()
        pspecs = param_specs(cfg, pshapes, mesh, fsdp=prof.get("fsdp_params"))
        params_in = with_sharding(mesh, pshapes, pspecs)
        rec["n_params"] = count_params(pshapes)
        rec["n_active_params"] = count_active_params(cfg, pshapes)

        t0 = time.time()
        if sp.kind == "train":
            ospecs = init_optimizer_shapes(pshapes)
            from repro.launch.steps import opt_specs_like
            ospec_tree = opt_specs_like(pspecs)
            opt_in = with_sharding(mesh, ospecs, ospec_tree)
            batch = input_specs(cfg, shape)["batch"]
            bspecs = batch_specs(cfg, batch, mesh)
            batch_in = with_sharding(mesh, batch, bspecs)
            step_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
            fn = build_train_step(model, cfg, **step_kwargs)
            args = (params_in, opt_in, batch_in, step_in)
            lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(*args)
            rec["tokens_per_step"] = sp.global_batch * sp.seq_len
        elif sp.kind == "prefill":
            batch = input_specs(cfg, shape)["batch"]
            bspecs = batch_specs(cfg, batch, mesh)
            batch_in = with_sharding(mesh, batch, bspecs)
            fn = build_prefill_step(model, cfg)
            args = (params_in, batch_in)
            lowered = jax.jit(fn).lower(*args)
            rec["tokens_per_step"] = sp.global_batch * sp.seq_len
        else:  # decode
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(sp.global_batch, sp.seq_len)
            )
            cspecs = cache_specs_tree(cfg, cache_shapes, mesh)
            cache_in = with_sharding(mesh, cache_shapes, cspecs)
            ns = lambda spec: NamedSharding(mesh, spec)
            toks = jax.ShapeDtypeStruct(
                (sp.global_batch, 1), jnp.int32,
                sharding=ns(batch_specs(cfg, {"tokens": jax.ShapeDtypeStruct((sp.global_batch, 1), jnp.int32)}, mesh)["tokens"]),
            )
            pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=ns(P()))
            fn = build_serve_step(model, cfg)
            args = (params_in, cache_in, toks, pos)
            lowered = jax.jit(fn, donate_argnums=(1,)).lower(*args)
            rec["tokens_per_step"] = sp.global_batch
        rec["lower_s"] = round(time.time() - t0, 2)

        # Analytic global cost (loop-aware; see core/costmodel.py for why
        # compiled.cost_analysis() alone can't be trusted across scans).
        closed = jax.make_jaxpr(fn)(*args)
        rec["analytic"] = jaxpr_flops_bytes(closed)
        del closed

        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(ma, k))
            for k in dir(ma)
            if k.endswith("_in_bytes") and isinstance(getattr(ma, k), (int, np.integer))
        }
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rec["cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }
        hlo_text = compiled.as_text()
        rec["collectives"] = collective_census(hlo_text)
        rec["collectives_loop_aware"] = loop_aware_collectives(hlo_text)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--profile", choices=list(PROFILES), default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = list_archs() if args.all else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        cfg = get_config(a)
        for s in shapes:
            if not supports_shape(cfg, s):
                continue
            for m in meshes:
                cells.append((a, s, m))

    os.makedirs(args.out, exist_ok=True)
    suffix = "" if args.profile == "baseline" else f"__{args.profile}"
    n_ok = n_fail = n_skip = 0
    for arch, shape, mesh_kind in cells:
        path = os.path.join(args.out, f"{arch}__{shape}__{mesh_kind}{suffix}.json")
        if args.skip_existing and os.path.exists(path):
            n_skip += 1
            continue
        print(f"=== {arch} x {shape} x {mesh_kind} x {args.profile} ===", flush=True)
        try:
            rec = lower_cell(arch, shape, mesh_kind, args.profile)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            mem = rec["memory"]
            per_dev = mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)
            print(
                f"    ok  lower={rec['lower_s']}s compile={rec['compile_s']}s "
                f"args+temp={per_dev/2**30:.2f}GiB/dev "
                f"flops={rec['cost']['flops']/1e12:.2f}TF/dev "
                f"coll={rec['collectives']['total_bytes']/2**20:.0f}MiB/dev",
                flush=True,
            )
            n_ok += 1
        except Exception as e:
            print(f"    FAIL {type(e).__name__}: {e}", flush=True)
            with open(path + ".err", "w") as f:
                f.write(traceback.format_exc())
            n_fail += 1
        gc.collect()
    print(f"\ndone: ok={n_ok} fail={n_fail} skip={n_skip}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
