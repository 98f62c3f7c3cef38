"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py --workload <name> [--plain]

Prints ``memory_analysis()`` of each program the cell's window or set-up
runs (train: the train step with the plan's offload policy, or with
``--plain`` the same step without offload; serve: the prefill and the decode
step), or the compiler's refusal. Nothing runs, so nothing here is a chip
result: it sizes a cell before a chip call. Run by hand; it loads the TPU
compiler, which one process at a time may hold.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import bench  # noqa: E402


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _show(name, fn, args, donate):
    try:
        mem = jax.jit(fn, donate_argnums=donate).lower(*args).compile().memory_analysis()
    except Exception as e:  # the compiler's refusal is the result
        print(f"{name}: refused: {str(e).splitlines()[0][:400]}")
        return
    gib = lambda b: f"{b / 2**30:.2f} GiB"
    print(f"{name}: arguments {gib(mem.argument_size_in_bytes)}, outputs "
          f"{gib(mem.output_size_in_bytes)}, aliased {gib(mem.alias_size_in_bytes)}, "
          f"temporaries {gib(mem.temp_size_in_bytes)}, host temporaries "
          f"{gib(mem.host_temp_size_in_bytes)}")


def train(wl, cfg, model, mc, chip, plain: bool, budget: int):
    from repro.core.planner import MemoryPlanner
    from repro.core.simulator import TPU_V5E
    from repro.launch.steps import build_train_step, init_optimizer_shapes

    B, S = wl["batch"], wl["seq_len"]
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "labels")}
    pshapes = model.init_shapes()
    policy = None
    if not plain:
        planner = MemoryPlanner(lambda p, b: model.loss(p, b)[0], pshapes, batch, hw=TPU_V5E)
        plan = planner.offload_plan(budget)
        print(f"plan at {budget / 2**30:.2f} GiB: offload {plan.offload_names}")
        policy = plan.policy()
    step = build_train_step(model, mc, lr=wl["optimizer"]["lr"], remat_policy=policy)
    args = _on(chip, (pshapes, init_optimizer_shapes(pshapes), batch,
                      jax.ShapeDtypeStruct((), jnp.int32)))
    _show("train step" + (" (no offload)" if plain else ""), step, args, (0, 1))


def serve(wl, cfg, model, mc, chip):
    B, P, max_seq = wl["batch"], wl["prompt_len"], wl["max_seq"]
    pshapes = model.init_shapes()
    batch = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    prefill = lambda p, b: model.prefill(p, b, max_seq=max_seq)
    _show("prefill", prefill, _on(chip, (pshapes, batch)), ())
    _, cache = jax.eval_shape(prefill, pshapes, batch)

    def serve_decode_step(params, cache, tok, pos):
        logits, cache = model.decode_step(params, cache, tok, pos)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None], cache

    args = _on(chip, (pshapes, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32),
                      jax.ShapeDtypeStruct((), jnp.int32)))
    _show("decode step", serve_decode_step, args, (1,))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plain", action="store_true", help="train step without offload")
    ap.add_argument("--budget-gib", type=float, default=15.75,
                    help="HBM budget the plan is solved for, where the cell takes the "
                         "device's bytes_limit (a described chip has none)")
    args = ap.parse_args(argv)

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    wl = bench.workload(args.workload)
    cfg = bench.config(wl["config"])
    mc = bench.program_config(cfg)
    model = build_model(mc)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    if wl["driver"] == "train":
        budget = wl["hbm_budget"]
        budget = int(args.budget_gib * 2**30) if budget == "bytes_limit" else int(budget)
        train(wl, cfg, model, mc, chip, args.plain, budget)
    else:
        serve(wl, cfg, model, mc, chip)


if __name__ == "__main__":
    main()
