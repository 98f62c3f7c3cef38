"""DeepSeek-V2 (latent attention, YaRN, a share of the routed experts) at smoke
size on the CPU: the program against the plain reference on seeded weights,
the expert share against the uncut layer, the YaRN table at the published
values, and whole serving runs of the cell through its driver and limits."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, weights
from chipbench.control import frozen_cache
from chipbench.reference import deepseek_v2 as ref
from chipbench.reference.common import CONTROL
from chipbench.run import _driver, run_cell
from chipbench.tests import cells

CELL = "deepseek-v2-lite.serve.ctx16k"
CONFIG = "deepseek-v2-lite-L9-ep8"
# The smoke model of repro.configs' deepseek-v2-lite-16b-ep8: 2 of 8 routed
# experts held, top-2, YaRN stretched beyond 16 positions, float32 compute.
SMALL = {
    "name": "deepseek-v2-smoke", "arch": "deepseek-v2-lite-16b-ep8", "kind": "deepseek_v2",
    "layers": 3, "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 160, "vocab_size": 512, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 32,
    "n_shared_experts": 2, "num_experts_per_tok": 2, "router_experts": 8, "n_routed_experts": 2,
    "first_expert": 0, "first_k_dense_replace": 1, "norm_topk_prob": False,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "compute_dtype": "float32", "cache_dtype": "float32",
    # At width 64 the cell's 0.02 would leave the layers' outputs far below
    # the embedding; 0.15 gives a linear layer the gain (std times the square
    # root of its width) that 0.02 gives it at width 2048.
    "initializer_range": 0.15,
    "program_keys": bench.config(CONFIG)["program_keys"],
}
SHAPE = dict(batch=2, prompt_len=24, turn_len=16, max_seq=40, check_requests=2)
# Both sides compute in float32; they agree to the rounding of reductions
# taken in another order.
TOL = 2e-5


def _model(cfg=SMALL, **replace):
    from repro.models import build_model

    mc = dataclasses.replace(cells.smoke_program_config(cfg), **replace)
    return build_model(mc)


def _close(got, want):
    scale = float(jnp.max(jnp.abs(want)))
    return float(jnp.max(jnp.abs(got - want))) <= TOL * scale


def test_weights_fit_the_programs_layout_small_and_published():
    params = jax.eval_shape(lambda: weights.init(SMALL, weights.seed_key(1)))
    assert weights.layout_mismatch(params, _model().init_shapes()) is None
    cfg = bench.config(CONFIG)
    from repro.models import build_model

    params = jax.eval_shape(lambda: weights.init(cfg, weights.seed_key(1)))
    program = build_model(bench.program_config(cfg)).init_shapes()
    assert weights.layout_mismatch(params, program) is None


def _decode_logits(model, params, ids, P):
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_seq=ids.shape[1]))
    last, cache = prefill(params, {"tokens": ids[:, :P]})
    decode = jax.jit(model.decode_step)
    steps = []
    for t in range(P, ids.shape[1]):
        logits, cache = decode(params, cache, ids[:, t:t + 1], jnp.asarray(t, jnp.int32))
        steps.append(logits[:, 0])
    return last, jnp.stack(steps, axis=1)


def _reference(params, ids, start):
    return jax.jit(lambda p, i: ref.logits(p, i, start, SMALL))(params, ids)


def _ids(n=2, s=28):
    rng = np.random.default_rng(1)
    return jnp.asarray(rng.integers(0, SMALL["vocab_size"], (n, s)), jnp.int32)


def test_prefill_and_four_decode_steps_match_the_reference():
    model, params, ids, P = _model(), weights.make(SMALL, 7), _ids(), 24
    last, decoded = _decode_logits(model, params, ids, P)
    for row in range(ids.shape[0]):
        assert _close(decoded[row], _reference(params, ids[row], P))
        assert _close(last[row, 0], _reference(params, ids[row, :P], P - 1)[0])


@pytest.mark.parametrize("fault", ["renormalized_gates", "no_yarn"])
def test_a_layer_that_renormalizes_or_drops_yarn_fails_the_comparison(fault):
    replace = {"renormalized_gates": {"norm_topk_prob": True},
               "no_yarn": {"rope_scaling": None}}[fault]
    model, params, ids, P = _model(**replace), weights.make(SMALL, 7), _ids(), 24
    _, decoded = _decode_logits(model, params, ids, P)
    want = _reference(params, ids[0], P)
    gap = float(jnp.max(jnp.abs(decoded[0] - want))) / float(jnp.max(jnp.abs(want)))
    assert gap > 100 * TOL


def _moe_layer(params, layer=0):
    return jax.tree.map(lambda a: a[layer], params["blocks"][1]["l0"]["moe"])


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips holding one expert each: their parts, with the shared
    expert counted once, add up to the reference's layer with every expert."""
    from repro.models.moe import apply_moe

    whole = dict(SMALL, n_routed_experts=8)
    m = _moe_layer(weights.make(whole, 3))
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 12, SMALL["hidden_size"]))
    parts = []
    for rank in range(8):
        cfg = _model(experts_held=1, first_expert=rank).cfg
        share = dict(m, **{k: m[k][rank:rank + 1] for k in ("w_gate", "w_up", "w_down")})
        parts.append(apply_moe(share, h, cfg)[0])
    shared = ref.swiglu(m["shared"], h.reshape(-1, SMALL["hidden_size"]), ref.REFERENCE)
    total = sum(parts).reshape(-1, SMALL["hidden_size"]) - 7 * shared
    assert _close(total, ref.moe(m, h.reshape(-1, SMALL["hidden_size"]), whole, ref.REFERENCE))


def test_a_batch_routed_to_one_expert_loses_no_pair():
    """Every token's first choice is expert 1: dropless, all 96 pairs land."""
    from repro.models.moe import apply_moe

    D = SMALL["hidden_size"]
    m = _moe_layer(weights.make(SMALL, 4))
    u = jax.nn.one_hot(0, D)
    h = jax.random.normal(jax.random.PRNGKey(1), (4, 24, D)) + 3.0 * u
    m["router"] = m["router"].at[:, 1].set(10.0 * u)
    xt = h.reshape(-1, D)
    idx = jax.lax.top_k(xt @ m["router"], SMALL["num_experts_per_tok"])[1]
    assert bool(jnp.all(idx[:, 0] == 1))
    got = apply_moe(m, h, _model().cfg)[0].reshape(xt.shape)
    assert _close(got, ref.moe(m, xt, SMALL, ref.REFERENCE))


def test_blocked_mla_prefill_equals_the_dense_form(monkeypatch):
    from repro.models import mla

    cfg = _model().cfg
    p = jax.tree.map(lambda a: a, weights.make(SMALL, 2)["blocks"][0]["l0"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, SMALL["hidden_size"]))
    angles = jax.jit(lambda pos: _model()._angles(pos))(jnp.broadcast_to(jnp.arange(64), (2, 64)))
    dense = mla._mla(p, x, cfg, angles, causal=True)[0]
    monkeypatch.setattr(mla, "CHUNKED_THRESHOLD", 64)
    monkeypatch.setattr(mla, "Q_BLOCK", 16)
    blocked = mla._mla(p, x, cfg, angles, causal=True)[0]
    assert _close(blocked, dense)


def test_the_yarn_table_at_the_published_values():
    from repro.models.mla import _mla_scale
    from repro.models.rope import rope_freqs, yarn_correction_range

    cfg = bench.config(CONFIG)
    mc = bench.program_config(cfg)
    y = ref.yarn(cfg)
    assert (y["low"], y["high"]) == (10, 23) == yarn_correction_range(mc.rope_scaling, 64, 1e4)
    assert y["scale"] == pytest.approx(0.11472, abs=5e-6)
    assert _mla_scale(mc) == pytest.approx(y["scale"], rel=1e-12)
    assert y["rope_factor"] == 1.0
    inv = np.asarray(rope_freqs(64, 1e4, mc.rope_scaling))
    plain = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[24:], plain[24:] / 40, rtol=1e-6)
    np.testing.assert_allclose(inv, np.asarray(y["inv"]), rtol=1e-6)


def test_decode_counts_by_hand():
    cfg = bench.config(CONFIG)
    kind = bench.kind(cfg["kind"])
    # Attention a layer: q 2*2048*16*192, latent and rope key 2*2048*576,
    # W_uk 2*16*128*512, W_uv 2*16*512*128, Wo 2*16*128*2048: 27,525,120;
    # plus 2*16*(576 + 512) a live position. Layer 0's SwiGLU 2*2048*10944*3;
    # a MoE layer's router 2*2048*64, 0.75 expected held experts of
    # 2*2048*1408*3 and the shared 2*2048*2816*3; the head 2*2048*102400.
    moe = 262_144 + 0.75 * 17_301_504 + 34_603_008
    assert kind.decode_flops_per_token(cfg, 0) == pytest.approx(
        9 * 27_525_120 + 134_479_872 + 8 * moe + 419_430_400)
    assert kind.decode_flops_per_token(cfg, 1000) - kind.decode_flops_per_token(cfg, 0) \
        == 9 * 34_816 * 1000
    assert kind.weight_count(cfg) == 1_303_685_632
    # 95.7% of the 8 held experts touched at batch 32; 10,368 bytes of
    # latent and rope key a position over 9 layers.
    touched = 8 * (1 - (58 / 64) ** 32)
    assert kind.decode_step_bytes(cfg, 32, 99) == pytest.approx(
        2 * (1_303_685_632 - 8 * (8 - touched) * 8_650_752) + 32 * 100 * 10_368)


# ---------------------------------------------------------- serving runs


def _small_cell():
    return dict(bench.workload(CELL), **SHAPE), copy.deepcopy(SMALL)


def _run(break_step=None, seed=3):
    wl, cfg = _small_cell()
    c = cells.ctx(seed=seed, break_step=break_step)
    try:
        return run_cell(wl, cfg, c, bench.metrics_for(CELL, False))
    finally:
        c.compiles.close()


def altered_token(fn):
    def step(params, cache, tok, pos):
        tok, cache = fn(params, cache, tok, pos)
        return jnp.where(pos % 3 == 0, (tok + 1) % SMALL["vocab_size"], tok), cache

    return step


def test_a_sound_serve_run_is_correct():
    r = _run(seed=2**31 + 21)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"decode_tokens_per_s", "decode_step_p95_ms", "peak_hbm_gib",
                                 "setup_s"}


@pytest.mark.parametrize("fault", [frozen_cache, altered_token])
def test_a_broken_decode_step_is_not_correct(fault):
    r = _run(fault)
    assert r["correct"] is False, r["checks"]
    assert r["failed"] >= 1


def test_the_float8_control_fails_the_serve_limit():
    wl, cfg = _small_cell()
    drv = _driver("serve")
    traffic = drv.Traffic(cfg, wl, 5)
    rng = np.random.default_rng(5)
    requests = []
    for turn in range(wl["check_requests"]):
        served = rng.integers(0, cfg["vocab_size"], wl["turn_len"]).astype(np.int32)
        requests.append((drv.served_sequence(traffic, turn, 0, served), served))
    with jax.default_matmul_precision("highest"):
        gaps = drv.reference_gaps(cfg, wl, 5, requests, rank_by=CONTROL)
    assert max(float(np.max(g)) for g in gaps) > wl["limits"]["served_gap"]
