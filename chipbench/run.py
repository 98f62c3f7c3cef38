"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell is found by its name in
``BENCHMARK.json``. With ``--trace 0`` the result's metrics are the cell's
end-to-end metrics; with ``--trace 1`` a profiled window gives its per-layer
metrics, plus the device's busy time and a breakdown. Every run checks what
its timed path produced against the plain reference; the numbers compared,
each beside its limit, are the last lines on standard error and the last key
of the result. The last line of standard output is the result, as one JSON
object. Without a TPU whose kind ``peaks.json`` lists, the run exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import bench  # noqa: E402

HOST_SPANS = {"window", "dispatch", "sync", "batch_prep", "next_turn"}


@dataclass
class Ctx:
    """What a driver gets from the harness."""

    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    start: float
    compiles: Any
    program_config: Callable = bench.program_config
    # Tests put a broken step under the timed path here; a run never does.
    break_step: Callable = staticmethod(lambda fn: fn)
    # The control script asks the drivers for its extra readings here.
    extra: dict = field(default_factory=dict)

    def spans(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start_trace(self):
        if not self.trace:
            return None
        import jax

        logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(logdir)
        return logdir

    def stop_trace(self, logdir):
        if logdir is None:
            return None
        import jax

        from chipbench import trace

        jax.profiler.stop_trace()
        try:
            return trace.reduce(trace.extract(logdir, HOST_SPANS))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)


def _driver(name: str):
    path = bench.HERE / "drivers" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_driver_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(wl: dict, cfg: dict, ctx: Ctx, metric_entries: list[dict]) -> dict:
    """Drive the cell, decide ``correct`` and assemble the result."""
    import numpy as np

    from chipbench import checks

    out = _driver(wl["driver"]).run(wl, cfg, ctx)
    bench.log(f"numbers {json.dumps(out['numbers'])}")
    correct, shown = checks.verdict(out["numbers"], wl["limits"])
    metrics = {}
    for m in metric_entries:
        if ctx.trace:
            value = bench.metric_reader(m["name"])(out["obs"])
            if value is None:
                continue
        else:
            value = out["e2e"][m["name"]]
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(ctx.devices),
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    result = {"correct": bool(correct), "attempted": int(out["attempted"])}
    if "per_request" in out:
        limit = wl["limits"]["served_gap"]
        result["failed"] = int(np.sum(~(np.asarray(out["per_request"]) <= limit)))
    else:
        result["failed"] = int(out["failed"])
    result["metrics"] = metrics
    result["device"] = device
    if out["trace"] is not None:
        tr = out["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    result["memory"] = out["memory"]
    result["window_compiles"] = out["window_compiles"]
    result["checks"] = shown
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse(argv)
    wl = bench.cell(args.workload)
    cfg = bench.config(wl["config"])
    entry = next(w for w in bench.benchmark()["workloads"] if w["name"] == args.workload)
    devices = bench.check_devices(entry["chips"])

    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # Cache every program, however quick to compile, so that set-up is the
    # same work in every run after the first.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = bench.Compiles()
    ctx = Ctx(seed=args.seed, seconds=args.seconds, trace=bool(args.trace), devices=devices,
              peaks=bench.peaks(devices[0].device_kind), start=START, compiles=compiles)
    result = run_cell(wl, cfg, ctx, bench.metrics_for(args.workload, ctx.trace))
    bench.log(f"window: {result['window_compiles']} compilations inside the window")
    bench.log(f"memory: {json.dumps(result['memory'])}")
    for name, c in result["checks"].items():
        bench.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
