"""Bring-up check of the main path on one TPU v5e chip.

Runs, in this one process and in this order:

  device   the default device is a TPU whose ``device_kind`` the planner's
           hardware table describes (``core/simulator.py``'s TPU_V5E);
  kernels  flash_attention (qwen3-4b widths, S=4096), ssd_scan (mamba2-370m
           widths) and rmsnorm (d=2560), each compiled for the chip and
           compared with its ``kernels/ref.py`` oracle;
  train    ``repro.launch.train.main`` on qwen3-4b cut to 2 layers at its
           published widths, with an HBM budget below the planned peak so
           that AutoSwap offloads activations to pinned host memory;
  serve    ``repro.launch.serve.main`` on the same model: prefill plus greedy
           decode, the first decode step checked against a prefill over the
           prompt plus that token.

Weights are random, made from the launchers' seeds.  Any failure ends the
run with a non-zero exit and no result line; so does a missing chip.  The
last line of standard output is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage (from the repository root): python chip_smoke.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.simulator import TPU_V5E, hardware_for  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm  # noqa: E402
from repro.kernels.ssd_scan import ssd_scan  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# Solved memory plans persist here between runs (git-ignored).
PLAN_CACHE = ROOT / ".plan_cache"

# Kernel widths: qwen3-4b attention (32 query / 8 KV heads of 128) at 4096
# positions; mamba2-370m's SSD (32 heads of 64, state 128, chunk 256); and
# qwen3-4b's RMSNorm width.
FLASH = dict(B=1, S=4096, H=32, KV=8, hd=128)
SSD = dict(b=1, s=4096, h=32, p=64, g=1, n=128, chunk=256)
NORM = dict(rows=4096, d=2560)
# Every kernel result must satisfy max|kernel - ref| <= KERNEL_TOL * max|ref|.
# flash_attention and rmsnorm take bf16 inputs and return bf16, whose 8-bit
# mantissa alone rounds by up to 2^-8 of the value; flash_attention also
# rounds its softmax weights to bf16 before the PV product.  ssd_scan is f32
# throughout, but the chip's matrix unit may multiply f32 operands in bf16
# passes.  1% of the output's scale covers both; a wrong tile or layout
# gives errors of the order of the output itself.
KERNEL_TOL = 1e-2

TRAIN_ARGV = [
    "--arch", "qwen3-4b", "--layers", "2", "--batch", "1", "--seq", "2048",
    "--steps", "3", "--log-every", "1", "--plan",
    # The 2-layer loss plans a 2.92 GiB peak; at 2 GiB AutoSwap offloads
    # the block_in activations to pinned host memory.
    "--hbm-limit-gb", "2",
]
# First loss: ln(151936) = 11.93 for uniform logits, plus sigma^2/2 for the
# spread sigma ~ 0.02 * sqrt(2560) ~ 1.0 that the tied, 0.02-scaled
# embedding gives over unit-RMS hidden states.
FIRST_LOSS, FIRST_LOSS_TOL = 12.4, 0.3

SERVE_BATCH, SERVE_GEN = 4, 8
SERVE_ARGV = [
    "--arch", "qwen3-4b", "--layers", "2", "--batch", str(SERVE_BATCH),
    "--prompt-len", "512", "--gen", str(SERVE_GEN), "--plan",
]
# The first decode step must match a prefill over prompt + that token within
# SERVE_TOL * max|logits|.  Both run bf16 activations (8-bit mantissa) and
# reach the same logits through different reductions (a 513-position causal
# softmax against a masked softmax over the cache ring), so they agree to a
# few bf16 roundings, not bitwise.
SERVE_TOL = 2e-2


def check_device():
    """The default device, if it is a chip the planner describes."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (default device is {dev.platform!r})")
    if hardware_for(dev.device_kind) is not TPU_V5E:
        raise SystemExit(f"chip_smoke: {dev.device_kind!r} is not the v5e the planner plans for")
    return dev


def compare(name: str, got, want, tol: float) -> None:
    """Fail unless max|got - want| <= tol * max|want|."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    if got.shape != want.shape:
        raise SystemExit(f"chip_smoke: {name}: shape {got.shape} != reference {want.shape}")
    if not bool(jnp.isfinite(got).all()):
        raise SystemExit(f"chip_smoke: {name}: non-finite output")
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    ratio = err / scale
    print(f"[smoke] {name}: max|err| {err!r} over max|ref| {scale!r} = {ratio!r} (tol {tol})")
    if not ratio <= tol:
        raise SystemExit(f"chip_smoke: {name} disagrees with its reference")


def phase_kernels() -> None:
    k = jax.random.split(jax.random.PRNGKey(0), 10)
    bf = jnp.bfloat16

    f = FLASH
    q = jax.random.normal(k[0], (f["B"], f["S"], f["H"], f["hd"]), bf)
    kk = jax.random.normal(k[1], (f["B"], f["S"], f["KV"], f["hd"]), bf)
    v = jax.random.normal(k[2], (f["B"], f["S"], f["KV"], f["hd"]), bf)
    got = jax.jit(lambda *a: flash_attention(*a, causal=True, interpret=False))(q, kk, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: ref.mha_reference(*a, causal=True))(q, kk, v)
    compare("flash_attention", got, want, KERNEL_TOL)
    del q, kk, v, got, want

    s = SSD
    x = jax.random.normal(k[3], (s["b"], s["s"], s["h"], s["p"])) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k[4], (s["b"], s["s"], s["h"])))
    A = -jnp.exp(jax.random.normal(k[5], (s["h"],)) * 0.3)
    Bm = jax.random.normal(k[6], (s["b"], s["s"], s["g"], s["n"])) * 0.5
    Cm = jax.random.normal(k[7], (s["b"], s["s"], s["g"], s["n"])) * 0.5
    got = jax.jit(lambda *a: ssd_scan(*a, chunk=s["chunk"], interpret=False))(x, dt, A, Bm, Cm)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.ssd_reference)(x, dt, A, Bm, Cm)
    compare("ssd_scan", got, want, KERNEL_TOL)

    n = NORM
    xn = jax.random.normal(k[8], (n["rows"], n["d"]), bf)
    scale = 1.0 + 0.1 * jax.random.normal(k[9], (n["d"],))
    got = jax.jit(lambda a, b: rmsnorm(a, b, interpret=False))(xn, scale)
    want = jax.jit(ref.rmsnorm_reference)(xn, scale)
    compare("rmsnorm", got, want, KERNEL_TOL)


def phase_train(dev) -> None:
    run = train.main(TRAIN_ARGV + ["--plan-cache", str(PLAN_CACHE)])
    print(f"[smoke] train: offload_names {run.offload_names}")
    print(f"[smoke] train: losses {run.losses!r}")
    stats = dev.memory_stats()
    print(f"[smoke] train: peak_bytes_in_use {stats['peak_bytes_in_use']}")
    if not run.offload_names:
        raise SystemExit("chip_smoke: the plan offloaded nothing at the train budget")
    if len(run.losses) != 3 or not all(jnp.isfinite(jnp.asarray(run.losses))):
        raise SystemExit(f"chip_smoke: expected 3 finite losses, got {run.losses}")
    if abs(run.losses[0] - FIRST_LOSS) > FIRST_LOSS_TOL:
        raise SystemExit(f"chip_smoke: first loss {run.losses[0]} is not {FIRST_LOSS}±{FIRST_LOSS_TOL}")


def phase_serve() -> None:
    run = serve.main(SERVE_ARGV + ["--plan-cache", str(PLAN_CACHE)])
    vocab = run.model.cfg.vocab_size
    print(f"[smoke] serve: tokens {run.tokens.shape} first row {run.tokens[0].tolist()}")
    if run.tokens.shape != (SERVE_BATCH, SERVE_GEN):
        raise SystemExit(f"chip_smoke: generated {run.tokens.shape}, "
                         f"expected {(SERVE_BATCH, SERVE_GEN)}")
    if not bool(((run.tokens >= 0) & (run.tokens < vocab)).all()):
        raise SystemExit("chip_smoke: generated ids outside the vocabulary")
    # The first decode step scored position P of prompt + first token; a
    # prefill over those P + 1 tokens scores the same position.
    tokens = jnp.concatenate([run.prompt["tokens"], run.tokens[:, :1]], axis=1)
    prefill = jax.jit(lambda p, b: run.model.prefill(p, b, max_seq=run.max_seq))
    want, _ = prefill(run.params, {"tokens": tokens})
    compare("serve decode vs prefill logits", run.first_decode_logits, want, SERVE_TOL)


def main() -> None:
    use_compile_cache()
    dev = check_device()
    print(f"[smoke] device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}")
    phase_kernels()
    phase_train(dev)
    phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
