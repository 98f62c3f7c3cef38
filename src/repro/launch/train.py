"""End-to-end training driver with fault tolerance.

Runs on whatever devices exist (CPU smoke -> v5e pods): builds the model from
``--arch`` (full or ``--smoke`` reduced config), sharded data pipeline,
AdamW, checkpoint/restart, and the paper's memory planner wired in:

  * ``--plan``       print the SmartPool/AutoSwap report for this exact step
                     function before training (jaxpr-transparent, §III/§IV);
  * ``--plan-cache`` directory of solved plan artifacts: the one-time solve
                     is keyed by (arch, step signature, hardware) and reused
                     across restarts / sibling processes without re-tracing;
  * ``--hbm-limit``  GB budget per device: AutoSwap picks the activation
                     classes to offload (pinned_host) and the train step is
                     rebuilt with that remat policy (§IV applied via XLA).
  * ``--layers N``   the first N layers at published widths (whole periods
                     of the layer program), so a large model fits one chip.

Fault tolerance:
  * atomic keep-k checkpoints (async), auto-resume from the latest step;
  * step-level failure injection hook (--fail-at) exercised by the tests:
    the process can be killed at any step and relaunched with identical
    results (deterministic data keyed by step);
  * straggler watchdog: steps exceeding ``--step-timeout`` x median are
    logged and counted (on real multi-host runs this triggers re-slicing —
    here it feeds the elastic-resume test).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \\
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --layers 2 \\
      --batch 1 --seq 2048 --steps 3 --hbm-limit-gb 2
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import config_tag, launch_config, list_archs
from repro.core.planner import MemoryPlanner
from repro.data import SyntheticTokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import build_train_step
from repro.models import build_model
from repro.optim import adamw_init


@dataclass
class TrainRun:
    """What one ``main`` call did: the loss of every step it ran and the
    activation classes its step offloaded to host memory."""

    losses: list[float]
    offload_names: list[str] = field(default_factory=list)


def make_batch_fn(cfg, batch: int, seq: int, seed: int):
    ds = SyntheticTokens(cfg.vocab_size, seq, batch, seed=seed)

    def at(step: int) -> dict:
        b = {k: jnp.asarray(v) for k, v in ds.batch_at(step).items()}
        if cfg.frontend == "vision_stub":
            npatch = min(cfg.num_patch_tokens, 8)
            b["patch_embeds"] = jnp.zeros((batch, npatch, cfg.d_model), jnp.float32)
            S = seq + npatch
            b["positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, None], (3, batch, S)
            )
        if cfg.is_encoder_decoder:
            b["frames"] = jnp.zeros((batch, cfg.enc_seq, cfg.d_model), jnp.float32)
        return b

    return at


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-runnable)")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep the first N layers at published widths "
                         "(default: the config's full depth)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=-1, help="inject a crash at step N (tests)")
    ap.add_argument("--step-timeout", type=float, default=10.0, help="straggler factor vs median")
    ap.add_argument("--plan", action="store_true", help="print SmartPool/AutoSwap report")
    ap.add_argument("--dist-plan", default=None, metavar="MESH",
                    help='solve per-device plans for a mesh (e.g. "data=4") '
                         "before training; cached under a topology-extended key")
    ap.add_argument("--plan-cache", default=None,
                    help="directory of solved plan artifacts (reused across runs)")
    ap.add_argument("--hbm-limit-gb", type=float, default=None,
                    help="AutoSwap offload budget per device (GB)")
    ap.add_argument("--log-every", type=int, default=10)
    from repro.obs import add_obs_args

    add_obs_args(ap)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = launch_config(args.arch, smoke=args.smoke, layers=args.layers)
    model = build_model(cfg)
    batch_fn = make_batch_fn(cfg, args.batch, args.seq, args.seed)

    if args.dist_plan:
        # Mesh-aware planning (repro.dist): per-device trace capture under
        # the launch/steps.py PartitionSpecs, solved once per device group
        # and cached under a topology-extended PlanKey — so this process's
        # sharded plan never aliases the single-device plan below.
        from repro.core.simulator import TPU_V5E
        from repro.dist import MeshSpec, solve_sharded
        from repro.launch.shardplan import capture_for_mesh, probe_from_model
        from repro.plan import PlanCache, PlanKey

        mesh = MeshSpec.parse(args.dist_plan)
        step_probe, example_args = probe_from_model(model, batch_fn)
        capture = capture_for_mesh(cfg, step_probe, example_args, mesh, TPU_V5E)
        tag = config_tag(smoke=args.smoke, layers=args.layers)
        base_key = PlanKey(args.arch, f"train:b{args.batch}s{args.seq}{tag}", TPU_V5E.name)
        dist_cache = PlanCache(args.plan_cache) if args.plan_cache else None
        solved = solve_sharded(
            capture, TPU_V5E, base_key=base_key, cache=dist_cache,
            limit=(int(args.hbm_limit_gb * 2**30) if args.hbm_limit_gb is not None else None),
        )
        for g, program in solved.programs.items():
            trace = program.require_trace()
            src = " (restored from cache)" if solved.cache_hits[g] else ""
            print(
                f"[dist-plan] mesh {mesh.signature() or '1'} group {g}: "
                f"per-device peak {trace.peak_load()/2**20:.1f}MiB, "
                f"{len(capture.groups[g].collectives)} collectives, "
                f"solved in {solved.solve_ms[g]:.1f} ms{src}"
            )
        if args.trace_out or args.verify:
            # Observability run: execute the solved mesh plans through the
            # runtime (contended shared link, the headline configuration)
            # and export the Perfetto trace before training proper starts.
            from repro.dist import run_mesh
            from repro.obs import export_monitor, export_trace, recorder_for

            shard_peak = max(
                p.require_trace().peak_load() for p in solved.programs.values()
            )
            # Default to the full shard peak: smoke traces are too small to
            # swap-plan below their peak, and an unschedulable tenant yields
            # an empty (vacuous) trace.
            budget = (
                int(args.hbm_limit_gb * 2**30)
                if args.hbm_limit_gb is not None
                else int(shard_peak)
            )
            recorder = recorder_for(args)
            mesh_run = run_mesh(
                solved, TPU_V5E, budget_per_device=budget, iterations=2,
                record_events=args.record_events, obs=recorder,
            )
            print(
                f"[dist-plan] mesh run: makespan "
                f"{mesh_run.report.makespan_s*1e3:.2f}ms, mean overhead "
                f"{mesh_run.mean_overhead()*100:.2f}%"
            )
            export_trace(args, recorder, mesh_run.report)
            export_monitor(args, recorder)
            if args.verify:
                from repro.analyze import verify_launch

                verify_launch(args, programs=solved.programs,
                              recorder=recorder, report=mesh_run.report)

    remat_policy = None
    offload_names: list[str] = []
    if args.plan or args.plan_cache or args.hbm_limit_gb is not None:
        from repro.core.simulator import TPU_V5E
        from repro.plan import PlanCache, PlanKey

        probe = jax.eval_shape(lambda: batch_fn(0))
        pshapes = model.init_shapes()

        def step_probe(params, batch):
            return model.loss(params, batch)[0]

        plan_cache = PlanCache(args.plan_cache) if args.plan_cache else None
        tag = config_tag(smoke=args.smoke, layers=args.layers)
        key = PlanKey(args.arch, f"train:b{args.batch}s{args.seq}{tag}", TPU_V5E.name)
        planner = MemoryPlanner(step_probe, pshapes, probe, hw=TPU_V5E,
                                cache=plan_cache, key=key)
        rep = planner.report()
        src = " (restored from cache)" if planner.from_cache else ""
        print(
            f"[plan] vars={rep.num_variables} peak={rep.peak_load/2**20:.1f}MiB "
            f"smartpool x{rep.smartpool_ratio:.4f} cnmem x{rep.cnmem_ratio:.4f}{src}"
        )
        if args.hbm_limit_gb is not None:
            limit = int(args.hbm_limit_gb * 2**30)
            plan = planner.offload_plan(limit)
            sw = planner.swap_report(limit)
            print(
                f"[plan] AutoSwap@{args.hbm_limit_gb}GB: offload {plan.offload_names} "
                f"(~{plan.predicted_savings/2**20:.1f}MiB relief, "
                f"simulated overhead {sw.overhead*100:.2f}%)"
            )
            remat_policy = plan.policy()
            offload_names = list(plan.offload_names)

    train_step = build_train_step(model, cfg, lr=args.lr, remat_policy=remat_policy)
    jit_step = jax.jit(train_step, donate_argnums=(0, 1))

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    params = model.init(jax.random.PRNGKey(args.seed))
    opt = adamw_init(params)
    start = 0
    if mgr and mgr.latest_step() is not None:
        (params, opt), start = mgr.restore((params, opt))
        start += 1
        print(f"[resume] restored checkpoint, continuing at step {start}")

    losses = []
    times: list[float] = []
    stragglers = 0
    for step in range(start, args.steps):
        if step == args.fail_at:
            raise RuntimeError(f"injected failure at step {step}")
        t0 = time.time()
        batch = batch_fn(step)
        params, opt, metrics = jit_step(params, opt, batch, jnp.asarray(step, jnp.int32))
        jax.block_until_ready((params, opt, metrics))
        dt = time.time() - t0
        loss = float(metrics["loss"])
        if len(times) >= 5 and dt > args.step_timeout * float(np.median(times)):
            stragglers += 1
            print(f"[watchdog] step {step} took {dt:.2f}s (median {np.median(times):.2f}s)")
        times.append(dt)
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {loss:.4f}  {dt*1000:.0f} ms")
        if mgr and args.ckpt_every and step and step % args.ckpt_every == 0:
            mgr.async_save((params, opt), step)
    if mgr:
        mgr.wait()
        mgr.save((params, opt), args.steps - 1)
    print(
        f"done: first-loss {losses[0]:.4f} last-loss {losses[-1]:.4f} "
        f"stragglers={stragglers}"
    )
    return TrainRun(losses, offload_names)


if __name__ == "__main__":
    main()
