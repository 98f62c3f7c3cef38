"""What every cell shares: its files, the device check, compile counting,
the program's configuration, and the result line.

A cell is found by its name in ``BENCHMARK.json``: its traffic in
``workloads/<name>.json``, its configuration in ``configs/<config>.json``,
its kind of model in ``kinds/<kind>.py``, its loop in
``drivers/<driver>.py`` and each of its per-layer metrics in
``metrics/<metric>.py``. Adding a cell or a metric adds files; nothing here
changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
PLAN_CACHE = ROOT / ".plan_cache"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    """The file ``workloads/<name>.json``, whether BENCHMARK.json names it or not."""
    return dict(load_json(HERE / "workloads" / f"{name}.json"), name=name)


def cell(name: str) -> dict:
    """The workload file of a cell that BENCHMARK.json names."""
    names = [w["name"] for w in benchmark()["workloads"]]
    if name not in names:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json; known: {names}")
    return workload(name)


def config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"chipbench: device kind {device_kind!r} is not in peaks.json "
                         f"({sorted(table)}); no peaks, no result")
    return table[device_kind]


def check_devices(chips: int):
    """The cell's TPU chips; exits without a result where they are not there."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX's default device is {devices[0].platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX sees {len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:chips]


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file, checked key by key
    against the file: the file holds the configuration as it is run."""
    from repro.configs import launch_config

    mc = launch_config(cfg["arch"], layers=cfg["layers"])
    for key, attr in cfg["program_keys"].items():
        if getattr(mc, attr) != cfg[key]:
            raise SystemExit(f"chipbench: {cfg['name']}: the program runs {attr}="
                             f"{getattr(mc, attr)!r}, the file says {key}={cfg[key]!r}")
    return mc


class Compiles:
    """Counts XLA compilations (cache loads included) and their host time."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        self.lowering_s = 0.0

        def listen(event, duration, **_):
            if event == BACKEND_COMPILE:
                self.count += 1
                self.seconds += duration
            elif event == LOWERING:
                self.lowering_s += duration

        jax.monitoring.register_event_duration_secs_listener(listen)
        self._listen = listen

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listen)


def kind(name: str):
    """The module of ``kinds/<name>.py``: what the benchmark knows of a kind of model."""
    return importlib.import_module(f"chipbench.kinds.{name}")


def metric_reader(name: str):
    """``read(obs)`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(cell_name: str, trace: bool) -> list[dict]:
    """The metric entries of BENCHMARK.json that this cell reports."""
    b = benchmark()
    if not trace:
        return [m for m in b["end_to_end"] if cell_name in m.get("workloads", [cell_name])]
    e2e = {m["name"] for m in b["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])}
    return [m for m in b["per_layer"]
            if cell_name in m.get("workloads", [cell_name] if m["moves"] in e2e else [])]


def log(*args):
    print("[chipbench]", *args, file=sys.stderr, flush=True)
