"""Pallas kernels vs ref.py oracles: shape/dtype/flag sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import flash_mha, fused_rmsnorm, ssd
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan


def _qkv(key, B, Sq, Sk, H, KV, hd, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, Sq, H, hd), dtype)
    k = jax.random.normal(k2, (B, Sk, KV, hd), dtype)
    v = jax.random.normal(k3, (B, Sk, KV, hd), dtype)
    return q, k, v


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KV,hd,block",
    [
        (1, 128, 2, 2, 64, 128),    # MHA
        (2, 256, 4, 2, 64, 128),    # GQA
        (1, 256, 4, 1, 128, 128),   # MQA, wide head
        (2, 512, 2, 2, 64, 256),    # bigger blocks
    ],
)
def test_flash_causal_sweep(dtype, B, S, H, KV, hd, block):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, S, H, KV, hd, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    exp = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("window", [32, 100, 512])
def test_flash_window(window):
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 256, 256, 2, 2, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, block_q=128, block_k=128)
    exp = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=2e-5)


def test_flash_softcap_and_noncausal():
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 128, 128, 2, 2, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=False, softcap=30.0, block_q=128, block_k=128)
    exp = ref.mha_reference(q, k, v, causal=False, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=2e-5)


def test_flash_cross_lengths():
    """Sq != Sk (cross-attention shape)."""
    q, _, _ = _qkv(jax.random.PRNGKey(3), 1, 128, 128, 4, 4, 64, jnp.float32)
    _, k, v = _qkv(jax.random.PRNGKey(4), 1, 128, 256, 4, 4, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=128, block_k=128)
    exp = ref.mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=2e-5)


def test_flash_ops_fallback_on_odd_shapes():
    # 1500 (whisper) isn't block-divisible: ops.flash_mha must fall back.
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 96, 96, 2, 2, 64, jnp.float32)
    out = flash_mha(q, k, v, causal=False)
    exp = ref.mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("g", [1, 2])
def test_ssd_sweep(chunk, g):
    b, s, h, p, n = 2, 128, 4, 16, 8
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), (h,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(key, 4), (b, s, g, n)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(key, 5), (b, s, g, n)) * 0.5
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    exp = ref.ssd_reference(x, dt, A, Bm, Cm)
    scale = float(jnp.abs(exp).max()) + 1e-9
    assert float(jnp.abs(y - exp).max()) / scale < 1e-4


def test_ssd_matches_model_ssd():
    """The model's pure-jnp chunked SSD and the kernel agree too."""
    from repro.models.ssm import ssd_chunked

    b, s, h, p, g, n = 1, 64, 2, 8, 1, 4
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 2), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), (h,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(key, 4), (b, s, g, n)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(key, 5), (b, s, g, n)) * 0.5
    y_model, _ = ssd_chunked(x, dt, A, Bm, Cm, 16)
    y_kernel = ssd_scan(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kernel), rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(
    rows=st.sampled_from([1, 4, 37, 256]),
    d=st.sampled_from([64, 256, 1024]),
    scale_val=st.floats(0.5, 2.0),
)
def test_rmsnorm_property(rows, d, scale_val):
    x = jax.random.normal(jax.random.PRNGKey(rows * d), (rows, d), jnp.float32)
    s = jnp.full((d,), scale_val, jnp.float32)
    out = rmsnorm(x, s)
    exp = ref.rmsnorm_reference(x, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5, atol=1e-5)


def test_rmsnorm_bf16_and_3d():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 128), jnp.bfloat16)
    s = jnp.ones((128,), jnp.float32)
    out = fused_rmsnorm(x, s)
    exp = ref.rmsnorm_reference(x, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_default_follows_backend(monkeypatch, backend, want):
    """Interpret mode by default only on the CPU; a backend that is neither
    CPU nor TPU is an error, never a silent fallback."""
    from repro.kernels import backend as kb

    monkeypatch.setattr(kb.jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            kb.resolve_interpret(None)
    else:
        assert kb.resolve_interpret(None) is want
    assert kb.resolve_interpret(False) is False
