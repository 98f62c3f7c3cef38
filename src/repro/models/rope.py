"""Rotary position embeddings: standard RoPE, YaRN (deepseek-v2) and M-RoPE
(qwen2-vl).

Positions are explicit everywhere so that decode (single position), prefill
(arange) and M-RoPE (3-channel t/h/w positions) share one code path.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import YarnScaling


def rope_freqs(head_dim: int, theta: float, yarn: YarnScaling | None = None) -> jnp.ndarray:
    """[head_dim/2] inverse frequencies, stretched by YaRN where given."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if yarn is not None:
        low, high = yarn_correction_range(yarn, head_dim, theta)
        # Pairs below ``low`` keep their frequency, pairs above ``high`` are
        # divided by the factor, and the ramp between blends the two.
        ramp = np.clip((np.arange(half, dtype=np.float32) - low) / max(high - low, 1e-3), 0, 1)
        inv = inv / yarn.factor * ramp + inv * (1 - ramp)
    return inv


def yarn_correction_range(yarn: YarnScaling, head_dim: int, theta: float) -> tuple[int, int]:
    """The rope pairs between which YaRN's ramp runs: the pair that turns
    ``beta_fast`` times, and the one that turns ``beta_slow`` times, over the
    original context."""

    def pair(turns):
        return head_dim * math.log(yarn.original_max_position / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = math.floor(pair(yarn.beta_fast)), math.ceil(pair(yarn.beta_slow))
    return max(low, 0), min(high, head_dim - 1)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rope_factor(yarn: YarnScaling | None) -> float:
    """What YaRN multiplies rope's cos and sin by."""
    if yarn is None:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)


def rope_angles(
    positions: jnp.ndarray, head_dim: int, theta: float, yarn: YarnScaling | None = None
) -> jnp.ndarray:
    """positions [...] -> angles [..., head_dim/2] (f32)."""
    inv = rope_freqs(head_dim, theta, yarn)
    return positions.astype(jnp.float32)[..., None] * inv


def mrope_angles(
    positions: jnp.ndarray, head_dim: int, theta: float, sections: tuple[int, int, int]
) -> jnp.ndarray:
    """M-RoPE: positions [3, ...] (t/h/w) -> angles [..., head_dim/2].

    The frequency spectrum is partitioned into ``sections`` (in units of
    freq pairs, summing to head_dim/2); each section takes its position from
    the corresponding channel.  Text tokens carry identical t/h/w positions,
    which makes M-RoPE coincide with RoPE for them.
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    inv = rope_freqs(head_dim, theta)
    full = positions.astype(jnp.float32)[..., None] * inv  # [3, ..., half]
    chunks = []
    start = 0
    for ch, width in enumerate(sections):
        chunks.append(full[ch, ..., start : start + width])
        start += width
    return jnp.concatenate(chunks, axis=-1)


def apply_rope(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """x [..., S, n, head_dim] (or [..., S, head_dim]); angles [..., S, head_dim/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if angles.ndim == x.ndim - 1:       # broadcast over the head axis
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
