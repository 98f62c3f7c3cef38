"""Prefill+decode == full forward: the KV-cache/state handoff is exact.

For each family, the next-token logits from (prefill T tokens, decode token
T) must match the last-position logits of a full (T+1)-token forward, and so
must those of each of several consecutive decode steps, each fed the cache
the one before returned, so a cache write that is lost shows.
Exercises: full cache, sliding-window ring cache past the window, MLA
compressed cache (absorbed decode), Mamba recurrent state, hybrid both.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config, list_archs
from repro.models import build_model

TOL = dict(rtol=2e-3, atol=2e-3)


def _batch_for(cfg, tokens):
    B, S = tokens.shape
    batch = {"tokens": tokens}
    if cfg.frontend == "vision_stub":
        npatch = cfg.num_patch_tokens
        batch["patch_embeds"] = jnp.full((B, npatch, cfg.d_model), 0.01, jnp.float32)
        St = S + npatch
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(St, dtype=jnp.int32)[None, None], (3, B, St)
        )
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.full((B, cfg.enc_seq, cfg.d_model), 0.01, jnp.float32)
    return batch


def _smoke_model(arch):
    cfg = get_smoke_config(arch)
    if cfg.num_experts:
        # Capacity dropping is batch-composition-dependent by design (same
        # tokens rank differently in a 25- vs 1-token batch); raise capacity
        # so the equivalence check isolates the cache/state handoff.
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    return cfg, build_model(cfg)


@pytest.mark.parametrize("arch", list_archs())
def test_decode_matches_full_forward(arch):
    cfg, model = _smoke_model(arch)
    params = model.init(jax.random.PRNGKey(1))
    B, T = 2, 24  # > smoke window (16): exercises the ring cache wrap
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(key, (B, T + 1), 0, cfg.vocab_size, jnp.int32)

    extra = cfg.num_patch_tokens if cfg.frontend == "vision_stub" else 0
    full_logits, _ = model.prefill(params, _batch_for(cfg, toks), max_seq=T + 1 + extra)

    _, cache = model.prefill(params, _batch_for(cfg, toks[:, :T]), max_seq=T + 1 + extra)
    dec_logits, _ = model.decode_step(
        params, cache, toks[:, T : T + 1], jnp.asarray(T + extra, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(dec_logits[:, -1]), np.asarray(full_logits[:, -1]), **TOL
    )


@pytest.mark.parametrize("arch", list_archs())
def test_consecutive_decode_steps_match_full_forward(arch):
    cfg, model = _smoke_model(arch)
    params = model.init(jax.random.PRNGKey(1))
    B, T, STEPS = 2, 24, 4  # T > smoke window (16): every step writes a wrapped ring
    extra = cfg.num_patch_tokens if cfg.frontend == "vision_stub" else 0
    max_seq = T + STEPS + extra
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, T + 1), 0, cfg.vocab_size, jnp.int32)

    prefill = jax.jit(model.prefill, static_argnames="max_seq")
    _, cache = prefill(params, _batch_for(cfg, toks[:, :T]), max_seq=max_seq)
    decode = jax.jit(model.decode_step)
    for step in range(STEPS):
        # Each step is fed the cache the step before returned, and its token.
        logits, cache = decode(params, cache, toks[:, -1:], jnp.asarray(T + step + extra, jnp.int32))
        full_logits, _ = prefill(params, _batch_for(cfg, toks), max_seq=max_seq)
        np.testing.assert_allclose(
            np.asarray(logits[:, -1]), np.asarray(full_logits[:, -1]), **TOL,
            err_msg=f"decode step {step}",
        )
        toks = jnp.concatenate([toks, jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)], 1)
