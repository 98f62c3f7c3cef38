"""Hymba's token mixer: attention heads and Mamba heads side by side.

Both read the same normed input; each branch's output is RMS-normed and the
two are averaged.  It is the attention and Mamba mixers composed: it holds
their params (``attn``, ``mamba``) and caches (``kv``, ``ssm``) beside its
own ``branch_norm_a`` and ``branch_norm_m``.  The attention branch keeps the
layer's sliding window (``attention._window``).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from . import attention, ssm
from .layers import rmsnorm

SCOPE = "hybrid"


def init(keys, cfg: ModelConfig, spec: LayerSpec):
    """Attention from the first of the mixer ``keys``, Mamba from the second."""
    return {
        **attention.init(keys, cfg, spec),
        **ssm.init(keys[1:], cfg, spec),
        "branch_norm_a": jnp.ones((cfg.d_model,), jnp.float32),
        "branch_norm_m": jnp.ones((cfg.d_model,), jnp.float32),
    }


def _merge(p, a, m, cfg: ModelConfig):
    return 0.5 * (
        rmsnorm(p["branch_norm_a"], a, cfg.norm_eps)
        + rmsnorm(p["branch_norm_m"], m, cfg.norm_eps)
    )


def forward(p, x, cfg: ModelConfig, spec: LayerSpec, angles, causal: bool = True):
    a = attention.forward(p, x, cfg, spec, angles, causal)
    m = ssm.forward(p, x, cfg, spec, angles, causal)
    return _merge(p, a, m, cfg)


def prefill(p, x, cfg: ModelConfig, spec: LayerSpec, angles, max_seq: int):
    a, kv = attention.prefill(p, x, cfg, spec, angles, max_seq)
    m, state = ssm.prefill(p, x, cfg, spec, angles, max_seq)
    return _merge(p, a, m, cfg), {**kv, **state}


def init_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int, dtype):
    return {**attention.init_cache(cfg, spec, batch, max_seq, dtype),
            **ssm.init_cache(cfg, spec, batch, max_seq, dtype)}


def decode(p, x, cache, index, pos, cfg: ModelConfig, spec: LayerSpec, angles):
    a, kv = attention.decode(p, x, cache, index, pos, cfg, spec, angles)
    m, state = ssm.decode(p, x, cache, index, pos, cfg, spec, angles)
    return _merge(p, a, m, cfg), {**kv, **state}
