"""Small cells for CPU tests: the repository's smoke configurations under
the real cells' drivers and limits, with the device check left out."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from chipbench import bench  # noqa: E402

MAMBA2 = {
    "name": "mamba2-smoke", "arch": "mamba2-370m", "kind": "mamba2", "layers": 3,
    "d_model": 64, "n_layer": 3, "vocab_size": 512, "d_state": 16, "d_conv": 4,
    "expand": 2, "headdim": 16, "ngroups": 1, "chunk_size": 16, "norm_epsilon": 1e-06,
    "tie_embeddings": True, "compute_dtype": "float32",
}
QWEN3 = {
    "name": "qwen3-smoke", "arch": "qwen3-4b", "kind": "qwen3", "layers": 3,
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
    "qk_norm": True, "compute_dtype": "float32", "cache_dtype": "float32",
    # At width 64, Qwen3's 0.02 would leave the layers' outputs far below the
    # embedding, and greedy decode would copy its input token whatever the
    # context. 0.3 gives the layers the weight against the embedding that 0.02
    # gives them at width 2560.
    "initializer_range": 0.3,
}
for _small, _real in ((MAMBA2, "mamba2-370m"), (QWEN3, "qwen3-4b-L9")):
    _small["program_keys"] = bench.config(_real)["program_keys"]

SHAPES = {
    "mamba2-370m.train.offload": dict(batch=4, seq_len=32, hbm_budget=1 << 20, reference_rows=2),
    "qwen3-4b.serve.ctx16k": dict(batch=2, prompt_len=16, turn_len=32, max_seq=48,
                                  check_requests=2),
    "qwen3-4b.serve.chat1k": dict(batch=4, prompt_len=8, turn_len=24, max_seq=32,
                                  check_requests=4),
}
CELLS = sorted(SHAPES)


def small_cell(name: str):
    """(workload, config) of a cell at smoke size, with the cell's own limits.

    The cell's file is read directly, so that a training cell kept out of
    BENCHMARK.json (PERF.md, Open questions) still drives its loop here."""
    wl = dict(bench.workload(name), **SHAPES[name])
    cfg = copy.deepcopy(MAMBA2 if wl["driver"] == "train" else QWEN3)
    return wl, cfg


def smoke_program_config(cfg: dict):
    from repro.configs import get_smoke_config

    mc = get_smoke_config(cfg["arch"])
    for key, attr in cfg["program_keys"].items():
        assert getattr(mc, attr) == cfg[key], (key, attr)
    return mc


def ctx(seed: int = 3, seconds: float = 0.5, trace: bool = False, break_step=None):
    import jax

    from chipbench.run import Ctx

    kw = {} if break_step is None else {"break_step": break_step}
    return Ctx(seed=seed, seconds=seconds, trace=trace, devices=jax.devices()[:1],
               peaks=bench.load_json(bench.HERE / "peaks.json")["devices"]["TPU v5 lite"],
               start=0.0, compiles=bench.Compiles(), program_config=smoke_program_config, **kw)


def run_small(name: str, break_step=None, seed: int = 3) -> dict:
    """A whole run of a cell at smoke size, as ``run.py`` makes it, less the
    device check; ``break_step`` breaks the timed step underneath."""
    from chipbench.run import run_cell

    wl, cfg = small_cell(name)
    c = ctx(seed=seed, break_step=break_step)
    try:
        return run_cell(wl, cfg, c, bench.metrics_for(name, False))
    finally:
        c.compiles.close()
