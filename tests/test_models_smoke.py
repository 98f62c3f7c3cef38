"""Per-architecture smoke tests: reduced config, one train step on CPU,
output shapes + finite values.  (Deliverable f: one smoke per assigned arch.)"""

from typing import get_args

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (
    SHAPES, cut_depth, get_config, get_smoke_config, input_specs, launch_config,
    list_archs, supports_shape,
)
from repro.configs.base import AttnKind
from repro.models import build_model
from repro.models.transformer import MIXERS


def smoke_batch(cfg, B=2, S=32):
    batch = {"tokens": jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) % cfg.vocab_size}
    batch["labels"] = batch["tokens"]
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


@pytest.mark.parametrize("arch", list_archs())
def test_arch_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = smoke_batch(cfg)

    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch), has_aux=True
    )(params)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"
    gsq = jax.tree.reduce(
        lambda a, b: a + b, jax.tree.map(lambda g: jnp.sum(g.astype(jnp.float32) ** 2), grads)
    )
    assert bool(jnp.isfinite(gsq)), f"{arch} grads not finite"
    # loss should be near ln(vocab) for random init
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 2.5 * np.log(cfg.vocab_size)


@pytest.mark.parametrize("arch", list_archs())
def test_arch_decode_smoke(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 24
    batch = {k: v for k, v in smoke_batch(cfg, B, S).items() if k != "labels"}
    extra = cfg.num_patch_tokens if cfg.frontend == "vision_stub" else 0
    logits, cache = model.prefill(params, batch, max_seq=S + extra + 8)
    assert logits.shape[0] == B and logits.shape[-1] == cfg.vocab_size
    tok = jnp.zeros((B, 1), jnp.int32)
    logits2, cache = model.decode_step(params, cache, tok, jnp.asarray(S + extra, jnp.int32))
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits2).all()), f"{arch} decode logits not finite"


@pytest.mark.parametrize("arch", list_archs())
def test_prefill_emits_the_cache_init_cache_lays_out(arch):
    """Prefill's cache has init_cache's structure, shapes and dtypes, which
    grouped prefill relies on: each group writes its rows into a cache that
    init_cache made for the whole batch."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init_shapes()
    B, S = 2, 8
    batch = {k: v for k, v in smoke_batch(cfg, B, S).items() if k != "labels"}
    extra = cfg.num_patch_tokens if cfg.frontend == "vision_stub" else 0
    max_seq = 16 + extra
    _, got = jax.eval_shape(lambda p, b: model.prefill(p, b, max_seq=max_seq), params, batch)
    want = jax.eval_shape(lambda: model.init_cache(B, max_seq))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


def test_every_layer_kind_has_a_mixer():
    assert set(get_args(AttnKind)) - {"none"} == set(MIXERS)


@pytest.mark.parametrize("arch", list_archs())
def test_full_config_exact_assignment(arch):
    """The FULL configs match the assigned table (no allocation: shapes only)."""
    cfg = get_config(arch)
    expected = {
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151_936),
        "gemma3-4b": (34, 2560, 8, 4, 10240, 262_144),
        "starcoder2-7b": (32, 4608, 36, 4, 18432, 49_152),
        "gemma2-9b": (42, 3584, 16, 8, 14336, 256_000),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102_400),
        "deepseek-v2-lite-16b-ep8": (27, 2048, 16, 16, 10944, 102_400),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 16384, 202_048),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152_064),
        "mamba2-370m": (48, 1024, 0, 0, 0, 50_280),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51_866),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32_001),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.d_ff, cfg.vocab_size)
    assert got == expected
    # input specs exist for every supported shape
    for shape in SHAPES:
        if supports_shape(cfg, shape):
            specs = input_specs(cfg, shape)
            assert specs


def test_long500k_only_for_subquadratic():
    assert supports_shape(get_config("mamba2-370m"), "long_500k")
    assert supports_shape(get_config("hymba-1.5b"), "long_500k")
    for arch in ("qwen3-4b", "gemma2-9b", "whisper-large-v3"):
        assert not supports_shape(get_config(arch), "long_500k")


def test_param_counts_in_expected_band():
    """Full-config parameter counts should be near the nameplate sizes."""
    bands = {
        "qwen3-4b": (3.5e9, 4.5e9),
        "starcoder2-7b": (6.5e9, 7.9e9),
        "gemma2-9b": (8.0e9, 10.5e9),
        "deepseek-v2-lite-16b": (14e9, 18e9),
        "llama4-maverick-400b-a17b": (380e9, 420e9),
        "mamba2-370m": (0.30e9, 0.45e9),
        "hymba-1.5b": (1.2e9, 2.0e9),
    }
    for arch, (lo, hi) in bands.items():
        cfg = get_config(arch)
        model = build_model(cfg)
        shapes = model.init_shapes()
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B params out of band ({lo/1e9}-{hi/1e9})"


@pytest.mark.parametrize("arch,layers,program", [
    ("qwen3-4b", 2, ((1, 2),)),
    ("gemma3-4b", 12, ((6, 2),)),
    ("gemma3-4b", 34, ((6, 5), (4, 1))),
    ("hymba-1.5b", 17, ((1, 1), (1, 15), (1, 1))),
])
def test_cut_depth_keeps_widths_in_whole_periods(arch, layers, program):
    full = get_config(arch)
    cut = launch_config(arch, layers=layers)
    assert cut.num_layers == layers
    assert tuple((len(unit), reps) for unit, reps in cut.program) == program
    assert [unit for unit, _ in cut.program] == [unit for unit, _ in full.program][: len(program)]
    for f in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff", "vocab_size"):
        assert getattr(cut, f) == getattr(full, f)
    assert launch_config(arch) == full


@pytest.mark.parametrize("arch,layers", [("gemma3-4b", 3), ("gemma2-9b", 5), ("qwen3-4b", 0),
                                         ("qwen3-4b", 37)])
def test_cut_depth_rejects_split_periods_and_bad_counts(arch, layers):
    with pytest.raises(ValueError):
        cut_depth(get_config(arch), layers)
