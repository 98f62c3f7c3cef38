"""End-to-end system tests: train loop with checkpoints, failure injection,
elastic resume, serve loop, planner-integrated training."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


def test_train_loss_decreases(tmp_path):
    losses = train_mod.main([
        "--arch", "qwen3-4b", "--smoke", "--steps", "30",
        "--batch", "4", "--seq", "64",
    ]).losses
    assert losses[-1] < losses[0]


def test_train_failure_injection_and_resume(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    args = ["--arch", "qwen3-4b", "--smoke", "--steps", "24", "--batch", "2",
            "--seq", "32", "--ckpt-dir", ckpt, "--ckpt-every", "10"]
    with pytest.raises(RuntimeError, match="injected failure"):
        train_mod.main(args + ["--fail-at", "15"])
    # relaunch: resumes from step 10's checkpoint and completes
    losses = train_mod.main(args).losses
    assert len(losses) > 0
    # checkpoints exist and the final one is step 23
    from repro.checkpoint.manager import latest_step

    assert latest_step(ckpt) == 23


def test_train_with_planner_offload(tmp_path):
    """--hbm-limit engages AutoSwap-driven offload remat; training still runs.
    (At 4 x 1024 tokens the smoke model's activation classes are large
    enough, 1 MiB each, for the planner to consider them.)"""
    run = train_mod.main([
        "--arch", "qwen3-4b", "--smoke", "--steps", "8", "--batch", "4",
        "--seq", "1024", "--plan", "--hbm-limit-gb", "0.001",
    ])
    assert run.offload_names
    assert np.isfinite(run.losses).all()


def test_serve_generates(tmp_path):
    gen = serve_mod.main([
        "--arch", "qwen3-4b", "--smoke", "--batch", "2",
        "--prompt-len", "16", "--gen", "6",
    ]).tokens
    assert gen.shape == (2, 6)
    assert (np.asarray(gen) >= 0).all()


def test_deterministic_restart_same_loss(tmp_path):
    """Determinism: two runs from scratch produce identical loss curves."""
    args = ["--arch", "mamba2-370m", "--smoke", "--steps", "6", "--batch", "2",
            "--seq", "32"]
    l1 = train_mod.main(args).losses
    l2 = train_mod.main(args).losses
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_compile_cache_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it a
    CPU run keeps no cache, and a TPU run would use the fixed checkout path."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert compile_cache.CHECKOUT_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
