"""Readings that the cells' limits are set from, at a cell's own size on the chip.

    python chipbench/control.py --workload <name> --seeds 1,2,3 [--controls 3] [--seconds 15]

Run by hand (the benchmark's own runs never run it), one process for all
seeds. Each seed is a whole run of the cell through its timed path, with a
window of ``--seconds``; the first ``--controls`` seeds also read the
control and the planted faults. Every reading goes through the cell's own
limits, as a run's numbers do, and prints one JSON line: the seed, what was
read, its numbers and whether they came out correct.

- Training cells: the program; the control (the reference computed in
  float8, in the program's place); half of each batch left out, the mean
  taken over the rest, planted in the reference. A state left unchanged
  reads 1 by the measure of the change and needs no run.
- Serving cells: the program; the control's gap on the same prompts and
  served tokens; one served token altered per request; and a second run
  whose decode step returns the cache unchanged.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import bench, checks  # noqa: E402

# Serving readings: each is judged as the program's served_gap would be.
SERVE_READINGS = {"program": "served_gap", "control": "control_gap",
                  "altered_token": "altered_token_gap"}


def frozen_cache(fn):
    def step(params, cache, tok, pos):
        tok, _ = fn(params, cache, tok, pos)
        return tok, cache

    return step


def _run(wl, cfg, seed, seconds, devices, readings, break_step=None,
         program_config=bench.program_config):
    from chipbench.run import Ctx, _driver

    ctx = Ctx(seed=seed, seconds=seconds, trace=False, devices=devices,
              peaks=bench.peaks(devices[0].device_kind), start=time.perf_counter(),
              compiles=bench.Compiles(), program_config=program_config,
              extra={"readings": readings})
    if break_step is not None:
        ctx.break_step = break_step
    try:
        return _driver(wl["driver"]).run(wl, cfg, ctx)
    finally:
        ctx.compiles.close()


def judged(wl, numbers: dict, readings: bool) -> list[tuple[str, dict, bool]]:
    """[(what, its compared numbers, correct)] for one run's numbers."""
    limits = wl["limits"]
    if wl["driver"] == "train":
        prefixes = {"program": ""}
        if readings:
            prefixes.update(control="control.", half_batch="half_batch.")
        sides = {what: {k[len(p):]: v for k, v in numbers.items()
                        if k.startswith(p) and "." not in k[len(p):]}
                 for what, p in prefixes.items()}
    else:
        keys = SERVE_READINGS if readings else {"program": "served_gap"}
        sides = {what: {"served_gap": numbers[key]} for what, key in keys.items()}
    return [(what, sub, checks.verdict(sub, limits)[0]) for what, sub in sides.items()]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=3,
                    help="how many of the first seeds also read the control and the faults")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    wl = bench.cell(args.workload)
    cfg = bench.config(wl["config"])
    devices = bench.check_devices(1)

    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        readings = i < args.controls
        t = time.perf_counter()
        r = _run(wl, cfg, seed, args.seconds, devices, readings)
        rows = judged(wl, r["numbers"], readings)
        if readings and wl["driver"] == "serve":
            frozen = _run(wl, cfg, seed, args.seconds, devices, False, break_step=frozen_cache)
            rows += [("frozen_cache", sub, ok)
                     for _, sub, ok in judged(wl, frozen["numbers"], False)]
        for what, sub, ok in rows:
            print(json.dumps({"seed": seed, "what": what, "correct": ok, "numbers": sub}),
                  flush=True)
        print(json.dumps({"seed": seed, "all_numbers": r["numbers"],
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
