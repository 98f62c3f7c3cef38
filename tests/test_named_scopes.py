"""The model code's named scopes reach the compiled program's ``op_name``
metadata, where a device profile finds the layer each op belongs to, and stay
out of the planner's trace, whose variables keep their ``checkpoint_name``s."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.trace import trace_step_fn
from repro.models import build_model

SERVE_SCOPES = {"embed", "layers", "attn", "qkv", "kv_write", "sdpa", "out_proj", "ffn", "head"}
MOE_SCOPES = {"route", "dispatch", "experts", "combine", "shared"}
ALL_SCOPES = SERVE_SCOPES | MOE_SCOPES | {"mla", "mamba", "hybrid", "cross_attn", "moe"}


def compiled_scopes(fn, *args) -> set[str]:
    """Every part of every ``op_name`` in the optimized HLO of ``fn``."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return {part for name in re.findall(r'op_name="([^"]*)"', text) for part in name.split("/")}


def serve_args(arch: str, batch: int = 2, prompt: int = 8, max_seq: int = 16):
    model = build_model(get_smoke_config(arch))
    params = model.init_shapes()
    cache = jax.eval_shape(lambda: model.init_cache(batch, max_seq))
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    prompt_batch = {"tokens": jax.ShapeDtypeStruct((batch, prompt), jnp.int32)}
    return model, params, cache, tok, pos, prompt_batch


@pytest.mark.parametrize("mode", ["decode", "prefill"])
def test_qwen3_serving_programs_carry_every_layer_scope(mode):
    model, params, cache, tok, pos, prompt = serve_args("qwen3-4b")
    if mode == "decode":
        found = compiled_scopes(model.decode_step, params, cache, tok, pos)
    else:
        found = compiled_scopes(lambda p, b: model.prefill(p, b, max_seq=16), params, prompt)
    assert SERVE_SCOPES <= found


@pytest.mark.parametrize("mode", ["decode", "prefill"])
def test_mamba2_serving_programs_carry_the_mamba_scope(mode):
    model, params, cache, tok, pos, prompt = serve_args("mamba2-370m")
    if mode == "decode":
        found = compiled_scopes(model.decode_step, params, cache, tok, pos)
    else:
        found = compiled_scopes(lambda p, b: model.prefill(p, b, max_seq=16), params, prompt)
    assert {"embed", "layers", "mamba", "head"} <= found
    assert not found & {"attn", "sdpa", "kv_write"}


@pytest.mark.parametrize("mode", ["decode", "prefill"])
def test_mla_moe_serving_programs_carry_the_attention_and_expert_scopes(mode):
    model, params, cache, tok, pos, prompt = serve_args("deepseek-v2-lite-16b-ep8")
    if mode == "decode":
        found = compiled_scopes(model.decode_step, params, cache, tok, pos)
    else:
        found = compiled_scopes(lambda p, b: model.prefill(p, b, max_seq=16), params, prompt)
    assert {"embed", "layers", "mla", "qkv", "kv_write", "sdpa", "out_proj", "ffn", "moe",
            "head"} | MOE_SCOPES <= found
    assert "attn" not in found


def test_named_scopes_stay_out_of_the_planners_trace():
    model = build_model(get_smoke_config("qwen3-4b"))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}

    def step(params, batch):
        return model.loss(params, batch)[0]

    names = {v.name for v in trace_step_fn(step, model.init_shapes(), batch).variables}
    assert {"attn_out", "ffn_out", "block_in"} <= names
    assert not any("/" in n for n in names)
    assert not names & ALL_SCOPES
