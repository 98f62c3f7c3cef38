"""Architecture registry: ``get_config(arch)`` / ``get_smoke_config(arch)``.

All 10 assigned architectures, and the expert-parallel share of
deepseek-v2-lite-16b that one chip holds, are selectable by id
(``--arch <id>``); the paper's own CNN benchmark families live in
models/cnn.py and are addressed by name ("vgg16", "resnet50", ...) in the
benchmarks.
"""

from __future__ import annotations

from importlib import import_module

from .base import SHAPES, LayerSpec, ModelConfig, ShapeSpec, cut_depth, uniform_program  # noqa: F401
from .specs import cache_specs, input_specs, supports_shape  # noqa: F401

ARCHS: dict[str, str] = {
    "qwen3-4b": "qwen3_4b",
    "gemma3-4b": "gemma3_4b",
    "starcoder2-7b": "starcoder2_7b",
    "gemma2-9b": "gemma2_9b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "deepseek-v2-lite-16b-ep8": "deepseek_v2_lite_16b_ep8",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "mamba2-370m": "mamba2_370m",
    "whisper-large-v3": "whisper_large_v3",
    "hymba-1.5b": "hymba_1_5b",
}


def _module(arch: str):
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCHS)}")
    return import_module(f"repro.configs.{ARCHS[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).config()


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).smoke_config()


def list_archs() -> list[str]:
    return list(ARCHS)


def launch_config(arch: str, *, smoke: bool = False, layers: int | None = None) -> ModelConfig:
    """The config a launcher runs: published (or ``smoke``), cut to its
    first ``layers`` layers when given (see ``cut_depth``)."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    return cfg if layers is None else cut_depth(cfg, layers)


def config_tag(*, smoke: bool = False, layers: int | None = None) -> str:
    """Step-signature suffix naming a ``launch_config`` variant, so plans of
    a smoke or depth-cut model never alias the published model's."""
    return (":smoke" if smoke else "") + ("" if layers is None else f":L{layers}")
