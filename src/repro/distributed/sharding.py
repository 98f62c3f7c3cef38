"""Logical-axis sharding: mesh rules + activation constraints + param specs.

Models annotate tensors with *logical* axes ("batch", "seq", "embed", ...);
this module maps them to mesh axes under the active rule set.  With no mesh
active every annotation is a no-op, so models run unchanged on a single CPU
device (smoke tests) and under the 512-device dry-run.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
"pod" behaves as an outer data-parallel axis.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes); None = replicated
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_cap": None,
    "lora": None,
    "state": None,
    "conv": None,
    "layers": None,
    "fsdp": "data",     # FSDP param dim (llama4-scale models)
    "seq_model": "model",  # context-parallel fallback for attention scores
    # attention scores batch dim over the WHOLE mesh: attention is
    # embarrassingly parallel over batch, so when enough batch exists this
    # beats both head sharding (no output all-reduce) and seq sharding
    "batch_full": ("pod", "data", "model"),
    # batch over (pod, data) REGARDLESS of profile: the chunked-CE logits must
    # keep vocab on "model" (otherwise the batch_full profile forces a full
    # embedding-table all-gather per CE chunk — 75 GB/dev/step on gemma3).
    "batch_pd": ("pod", "data"),
}


class _Ctx(threading.local):
    mesh: Mesh | None = None
    rules: dict | None = None


_CTX = _Ctx()


@contextmanager
def use_mesh(mesh: Mesh, rules: dict | None = None):
    """Activate a mesh + logical rules for model-internal constraints."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, dict(DEFAULT_RULES, **(rules or {}))
    try:
        with mesh:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def _resolve(axes: Sequence[str | None]) -> P:
    rules = _CTX.rules or DEFAULT_RULES
    mesh_axes = set(_CTX.mesh.axis_names) if _CTX.mesh is not None else set()

    def one(a):
        if a is None:
            return None
        m = rules.get(a)
        if m is None:
            return None
        if isinstance(m, tuple):
            present = tuple(x for x in m if x in mesh_axes)
            return present or None
        return m if m in mesh_axes else None

    return P(*[one(a) for a in axes])


def logical_spec(axes: Sequence[str | None]) -> P:
    return _resolve(axes)


def _dedupe(spec: P) -> P:
    """Drop mesh axes already claimed by an earlier dim (left precedence) —
    profiles may map several logical axes onto overlapping mesh axes."""
    used: set = set()
    out = []
    for p in spec:
        parts = p if isinstance(p, tuple) else ((p,) if p else ())
        keep = tuple(a for a in parts if a not in used)
        used.update(keep)
        out.append(keep if len(keep) > 1 else (keep[0] if keep else None))
    return P(*out)


def shard(x, *axes: str | None):
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    if _CTX.mesh is None or _CTX.mesh.empty:
        return x
    spec = _dedupe(_resolve(axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def _spec_divides(shape, spec: P) -> bool:
    mesh = _CTX.mesh
    for dim, part in zip(shape, spec):
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        size = 1
        for p in parts:
            size *= mesh.shape[p]
        if dim % size != 0:
            return False
    return True


def shard_pick(x, *candidates: Sequence[str | None]):
    """Apply the first candidate logical-axes constraint that divides x's
    shape evenly; no-op if none do (or no mesh).  Used where the preferred
    sharding axis (attention heads) may not divide the mesh axis for some
    architectures (e.g. 40 or 25 heads on model=16) and a fallback dim
    (query/key sequence) must carry the partitioning instead."""
    if _CTX.mesh is None or _CTX.mesh.empty:
        return x
    for axes in candidates:
        spec = _resolve(axes)
        # a mesh axis may appear at most once across the whole spec (profiles
        # can map several logical axes onto overlapping mesh axes)
        used: list = []
        for p in spec:
            used += list(p) if isinstance(p, tuple) else ([p] if p else [])
        if len(used) != len(set(used)):
            continue
        if any(p is not None for p in spec) and _spec_divides(x.shape, spec):
            return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))
    return x


def named_sharding(mesh: Mesh, *axes: str | None, rules: dict | None = None) -> NamedSharding:
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, dict(DEFAULT_RULES, **(rules or {}))
    try:
        return NamedSharding(mesh, _resolve(axes))
    finally:
        _CTX.mesh, _CTX.rules = prev


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
