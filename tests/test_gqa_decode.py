"""Decode attention for one query position == the expanded path.

``attention._sdpa`` contracts each KV head with its G query heads when there
is one query position (the decode step) and expands K and V to the H heads
when there are more (prefill, training).  The expanded path is the reference
here: the query is given twice, which sends ``_sdpa`` down that path, and the
first position's output must match the one-query path's.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import attention

KV, HD, B, W = 2, 32, 3, 48
# bf16 keeps 8 significant bits: outputs may differ by a rounding of the
# scores and of the softmax weights, which is two units in the last place.
BF16_ULP = 2.0**-7


def _mask(kind):
    """Decode masks over a W-slot cache (``attention.decode``'s rule): a full
    cache half written, a ring past its wrap, one row per batch entry."""
    idx = jnp.arange(W)
    if kind == "none":
        return None
    if kind == "per_row":
        pos = jnp.array([5, W // 2, W - 1])[:, None]
        return (idx[None] <= pos)[:, None, None, :]
    pos = W // 2 if kind in ("full", "softcap") else 3 * W + 5
    written_at = pos - jnp.mod(pos - idx, W)
    return (written_at >= 0)[None, None, None, :]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["full", "ring", "softcap", "none", "per_row"])
@pytest.mark.parametrize("G", [1, 2, 4, 5])
def test_one_query_matches_expanded(G, kind, dtype):
    cfg = get_smoke_config("qwen3-4b")
    if kind == "softcap":
        cfg = dataclasses.replace(cfg, attn_softcap=1.0)
    dt = jnp.dtype(dtype)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(G), 3)
    q = (2.0 * jax.random.normal(kq, (B, 1, KV * G, HD))).astype(dt)
    k = jax.random.normal(kk, (B, W, KV, HD)).astype(dt)
    v = jax.random.normal(kv, (B, W, KV, HD)).astype(dt)
    mask = _mask(kind)

    got = attention._sdpa(q, k, v, mask, cfg)
    want = attention._sdpa(jnp.concatenate([q, q], 1), k, v, mask, cfg)[:, :1]
    assert got.shape == want.shape == q.shape and got.dtype == dt
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dt == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=2 * BF16_ULP * np.abs(want).max())


def test_one_query_never_expands_kv(monkeypatch):
    """The decode step's attention does not build the expanded K and V."""
    def refuse(*_):
        raise AssertionError("one query position expanded K and V")

    monkeypatch.setattr(attention, "_expand_kv", refuse)
    cfg = get_smoke_config("qwen3-4b")
    q = jnp.ones((B, 1, KV * 4, HD), jnp.bfloat16)
    k = jnp.ones((B, W, KV, HD), jnp.bfloat16)
    assert attention._sdpa(q, k, k, _mask("full"), cfg).shape == q.shape
