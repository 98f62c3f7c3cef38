"""Compiles of the main path for a described (not attached) TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip described by
``topologies.get_topology_desc``: it refuses what the chip would refuse
(tile-misaligned kernel blocks, programs that do not fit HBM), which
interpret mode cannot show.  Nothing runs, so these say nothing about
results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Where it cannot be described, the fixture skips.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import launch_config
from repro.core.offload import OffloadPlan
from repro.core.planner import MemoryPlanner
from repro.core.simulator import TPU_V5E
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.steps import build_train_step, init_optimizer_shapes
from repro.launch.train import make_batch_fn
from repro.models import build_model

HBM_BYTES = 16 * 2**30
# chip_smoke.py's train phase: qwen3-4b cut to 2 layers, batch 1 x 2048
# positions, at an HBM budget of 2 GiB.
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_LIMIT = 2, 1, 2048, 2 * 2**30
# A serving step of qwen3-4b cut to 2 layers: batch 4 over a 4,096-position
# cache (K and V stacked over the layers: 0.125 GiB).
DECODE_LAYERS, DECODE_BATCH, DECODE_MAX_SEQ = 2, 4, 4096
# The qwen3-4b.serve.ctx16k cell: qwen3-4b cut to 9 layers, batch 4 over a
# 16,896-position cache.
CTX16K_LAYERS, CTX16K_BATCH, CTX16K_MAX_SEQ = 9, 4, 16896
# The deepseek-v2-lite.serve.ctx16k cell: one chip's expert share of
# deepseek-v2-lite-16b cut to 9 layers, batch 32 prompts of 16,384 tokens over
# a 16,640-position latent cache (5.71 GiB with its lanes padded to 640).
MLA_ARCH, MLA_LAYERS, MLA_BATCH, MLA_PROMPT, MLA_MAX_SEQ = (
    "deepseek-v2-lite-16b-ep8", 9, 32, 16384, 16640)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a compile
    for a described chip is written to it but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _compile_kernel(fn, sharding, *shapes):
    compiled = jax.jit(fn).lower(*_on(sharding, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_qwen3_4b_widths(one_chip):
    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16)
    _compile_kernel(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        one_chip, q, kv, kv,
    )


def test_ssd_scan_compiles_at_mamba2_370m_widths(one_chip):
    b, s, h, p, g, n = 1, 4096, 32, 64, 1, 128
    f32 = jnp.float32
    _compile_kernel(
        lambda *a: ssd_scan(*a, chunk=256, interpret=False),
        one_chip,
        jax.ShapeDtypeStruct((b, s, h, p), f32),
        jax.ShapeDtypeStruct((b, s, h), f32),
        jax.ShapeDtypeStruct((h,), f32),
        jax.ShapeDtypeStruct((b, s, g, n), f32),
        jax.ShapeDtypeStruct((b, s, g, n), f32),
    )


def test_rmsnorm_compiles_at_d2560(one_chip):
    _compile_kernel(
        lambda x, s: rmsnorm(x, s, interpret=False),
        one_chip,
        jax.ShapeDtypeStruct((4096, 2560), jnp.bfloat16),
        jax.ShapeDtypeStruct((2560,), jnp.float32),
    )


def test_offloaded_train_step_compiles_and_fits(one_chip):
    """The 2-layer qwen3-4b step with the plan's pinned_host offload policy
    fits one chip's HBM and keeps temporaries in host memory."""
    cfg = launch_config("qwen3-4b", layers=TRAIN_LAYERS)
    model = build_model(cfg)
    pshapes = model.init_shapes()
    batch_fn = make_batch_fn(cfg, TRAIN_BATCH, TRAIN_SEQ, 0)
    batch = jax.eval_shape(lambda: batch_fn(0))
    planner = MemoryPlanner(
        lambda p, b: model.loss(p, b)[0], pshapes, batch, hw=TPU_V5E
    )
    plan: OffloadPlan = planner.offload_plan(TRAIN_LIMIT)
    assert plan.offload_names

    step = build_train_step(model, cfg, remat_policy=plan.policy())
    args = _on(one_chip, (
        pshapes, init_optimizer_shapes(pshapes), batch,
        jax.ShapeDtypeStruct((), jnp.int32),
    ))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.host_temp_size_in_bytes > 0
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= HBM_BYTES


def _decode_step(sharding, arch, layers, batch, max_seq):
    """(config, cache shapes, compiled decode step with the cache donated)."""
    cfg = launch_config(arch, layers=layers)
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(batch, max_seq))
    args = _on(sharding, (
        model.init_shapes(), cache,
        jax.ShapeDtypeStruct((batch, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    ))
    return cfg, cache, jax.jit(model.decode_step, donate_argnums=(1,)).lower(*args).compile()


def test_decode_step_writes_the_donated_cache_in_place(one_chip):
    """The decode step writes each layer's new token into the donated cache in
    place and copies no layer's cache whole: its output cache is the donated
    buffer, and its temporaries hold little beyond the hoisted bf16 casts of
    the weights (0.38 GiB here; a step that copies each layer's cache through
    the layer loop needs 0.75 GiB, one that also expands K and V to the query
    heads 0.49 GiB)."""
    _, cache, compiled = _decode_step(
        one_chip, "qwen3-4b", DECODE_LAYERS, DECODE_BATCH, DECODE_MAX_SEQ)
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes <= 0.42 * 2**30


def _top_level_shapes(hlo: str):
    """(opcode, dims) of each array an instruction outside a fused
    computation makes, from optimized HLO text."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo))
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            comp = head.group(1)
            continue
        inst = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if inst and comp not in fused:
            for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", inst.group(1)):
                yield inst.group(2), tuple(int(d) for d in dims.split(",") if d)


def test_gqa_decode_step_reads_each_kv_head_once(one_chip):
    """The qwen3-4b.serve.ctx16k cell's decode step contracts each KV head
    with its query group: no top-level op makes K or V expanded to the query
    heads, no copy relays the cache, and the temporaries are 1.95 GiB
    (2.35 GiB when K and V were expanded; a grouped einsum lowered to a
    convolution that copied the stacked cache into another layout before the
    layer loop and back after it, 3.11 GiB)."""
    cfg, cache, compiled = _decode_step(
        one_chip, "qwen3-4b", CTX16K_LAYERS, CTX16K_BATCH, CTX16K_MAX_SEQ)
    B, W, KV, H, hd = (CTX16K_BATCH, CTX16K_MAX_SEQ, cfg.num_kv_heads, cfg.num_heads,
                       cfg.head_dim)
    expanded = {(B, W, KV, H // KV, hd), (B, W, H, hd)}
    stacked = {a.shape for a in jax.tree.leaves(cache)}
    cache_shapes = stacked | {s[1:] for s in stacked}
    shapes = list(_top_level_shapes(compiled.as_text()))
    assert ("parameter", next(iter(stacked))) in shapes
    for op, dims in shapes:
        assert dims not in expanded, (op, dims)
        assert not (op.startswith("copy") and dims in cache_shapes), (op, dims)
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.1 * 2**30


def _footprint(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes)


def test_mla_decode_step_fits_and_writes_the_latent_cache_in_place(one_chip):
    """The deepseek cell's decode step fits the chip, its output cache is the
    donated one, and the stacked latent cache is never copied whole. The
    temporaries are 2.13 GiB, most of them the hoisted bf16 weight casts; a
    batch-major cache made XLA relay the stacked latent out and back each
    step (5.87 GiB), and a 576-wide one did not fit (17.15 GiB in all)."""
    _, cache, compiled = _decode_step(one_chip, MLA_ARCH, MLA_LAYERS, MLA_BATCH, MLA_MAX_SEQ)
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes <= 2.5 * 2**30
    assert _footprint(mem) <= HBM_BYTES


def test_mla_prefill_in_row_groups_fits(one_chip):
    """The deepseek cell's prefill takes its 32 prompts two rows at a time,
    each group writing its rows into the one cache of the batch, and fits."""
    from repro.models.transformer import prefill_group_rows

    cfg = launch_config(MLA_ARCH, layers=MLA_LAYERS)
    model = build_model(cfg)
    assert prefill_group_rows(cfg, MLA_BATCH, MLA_PROMPT) == 2
    args = _on(one_chip, (
        model.init_shapes(), {"tokens": jax.ShapeDtypeStruct((MLA_BATCH, MLA_PROMPT), jnp.int32)},
    ))
    compiled = jax.jit(lambda p, b: model.prefill(p, b, max_seq=MLA_MAX_SEQ)).lower(*args).compile()
    assert _footprint(compiled.memory_analysis()) <= HBM_BYTES


def test_described_chip_is_the_planners_v5e(topo):
    from repro.core.simulator import hardware_for

    assert hardware_for(topo.devices[0].device_kind) is TPU_V5E
    with pytest.raises(ValueError, match="no HardwareSpec"):
        hardware_for("TPU v4")
