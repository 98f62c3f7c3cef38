"""Prefill+decode == full forward: the KV-cache/state handoff is exact.

For each family, the next-token logits from (prefill T tokens, decode token
T) must match the last-position logits of a full (T+1)-token forward, and so
must those of each of several consecutive decode steps, each fed the cache
the one before returned, so a cache write that is lost shows.
Exercises: full cache, sliding-window ring cache past the window, MLA
compressed cache (absorbed decode), Mamba recurrent state, hybrid both.
"""


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


@pytest.mark.parametrize(
    "arch", [a for a in list_archs() if not get_smoke_config(a).is_encoder_decoder])
def test_prefill_in_row_groups_matches_one_pass(arch, monkeypatch):
    """A batch prefilled a row at a time, each row written into the batch's
    one cache, gives the logits and the cache of one pass over the batch."""
    from repro.models import transformer

    cfg, model = _smoke_model(arch)
    params = model.init(jax.random.PRNGKey(1))
    B, T = 2, 24
    extra = cfg.num_patch_tokens if cfg.frontend == "vision_stub" else 0
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, T), 0, cfg.vocab_size, jnp.int32)
    batch = _batch_for(cfg, toks)

    def prefill():
        return jax.jit(lambda p, b: model.prefill(p, b, max_seq=T + 4 + extra))(params, batch)

    assert transformer.prefill_group_rows(cfg, B, T) == B
    want = prefill()
    monkeypatch.setattr(transformer, "PREFILL_GROUP_BYTES", 0)
    assert transformer.prefill_group_rows(cfg, B, T) == 1
    got = prefill()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **TOL)
