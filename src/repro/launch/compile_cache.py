"""Where JAX keeps its persistent compilation cache for the launchers.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and no
other directory is set here.  Otherwise a TPU run caches at a fixed path
inside the checkout (``.jax_cache/``, git-ignored), so a second run of the
same program finds the first one's executables.  The path is part of the
cache key, which is why it never contains a temporary name, a pid or a time.
A CPU run (the test suite, the smoke configs) compiles in seconds and keeps
no cache in the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at its directory; returns
    it, or None where no cache is kept.

    Call before the first compilation: JAX opens the cache once per process.
    """
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
