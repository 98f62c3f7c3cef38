"""Shared child-process runner for the distributed tests.

The distributed tests exec a child python with
``--xla_force_host_platform_device_count`` to get multi-device XLA.  In
sandboxes that can't provide that (no backend, too few devices, OOM-killed
child, or a machine too slow to finish in the timeout) the child fails for
reasons that say nothing about this repo's code.  An import or attribute
error from JAX is not such a reason: the code targets the installed JAX,
so an API break fails.  ``run_child_or_skip`` distinguishes those environmental
failures (-> ``pytest.skip`` with the matched reason, so tier-1 signal stays
deterministic across environments) from real code errors (-> a normal
assertion failure with the child's output attached).
"""

from __future__ import annotations

import os
import re
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# Patterns that mean "this sandbox cannot run the child", not "the code is
# wrong".  Checked against the child's stderr (last match wins the message).
_ENV_PATTERNS = [
    # Backend / platform unavailable.
    r"Unable to initialize backend",
    r"No visible \w+ devices",
    r"failed to initialize \w* ?backend",
    r"No such platform",
    # Forced host device count did not take effect: mesh creation fails
    # reshaping the single visible device into the (4, 2) grid.  Size 1
    # only — a larger size means the forcing worked and the mesh code
    # itself is wrong, which must fail, not skip.
    r"cannot reshape array of size 1 into shape",
    r"[Rr]equires \d+ devices",
    # Sandbox resource limits (XLA's allocator, not a python-level bug).
    r"RESOURCE_EXHAUSTED",
]


def classify_env_failure(proc: subprocess.CompletedProcess) -> str | None:
    """Return a human-readable environmental reason, or None for real bugs."""
    if proc.returncode is not None and proc.returncode < 0:
        return f"child killed by signal {-proc.returncode} (sandbox resource limit?)"
    text = proc.stderr or ""
    for pat in _ENV_PATTERNS:
        m = re.search(pat, text)
        if m:
            return m.group(0)
    return None


def run_child_or_skip(src: str, timeout: int = 420) -> subprocess.CompletedProcess:
    """Run child code that must print CHILD_OK; skip on environmental failure."""
    env = dict(os.environ, PYTHONPATH=SRC)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", src],
            capture_output=True, text=True, env=env, timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        pytest.skip(f"distributed child exceeded {timeout}s (environment too slow)")
    if "CHILD_OK" in proc.stdout:
        return proc
    reason = classify_env_failure(proc)
    if reason:
        pytest.skip(f"distributed child unavailable in this environment: {reason}")
    pytest.fail(
        "distributed child failed:\n"
        f"--- stdout (tail) ---\n{proc.stdout[-800:]}\n"
        f"--- stderr (tail) ---\n{proc.stderr[-2000:]}"
    )
