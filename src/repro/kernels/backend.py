"""Which way a Pallas kernel runs on the current backend."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, else compiled on a TPU and interpreted on the
    CPU.  Any other backend is an error: these are TPU kernels, and nothing
    falls back to a reference behind the caller's back."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas TPU kernels run on a TPU, or interpreted on the CPU; "
            f"the default backend is {backend!r}"
        )
    return backend == "cpu"
