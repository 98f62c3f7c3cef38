"""The split of a traced window by the program's named scopes, on a small
recorded window and a hand-written HLO module."""

import pytest

from chipbench import scopes

# A layer loop whose body holds scoped ops and a copy without metadata; in the
# entry computation an embedding, the loop, a copy of the loop's result, a
# weight cast of a parameter, and the serving step's argmax, which has no scope.
HLO = """\
HloModule jit_serve_decode_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_name="jit(serve_decode_step)/layers/while/body/closed_call/attn/sdpa/mul"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %p), index=1
  %multiply_reduce_fusion.5 = f32[8]{0} fusion(f32[8]{0} %get-tuple-element.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(serve_decode_step)/layers/while/body/closed_call/attn/sdpa/bhqs,bshd->bqhd/dot_general" stack_frame_id=7}
  %dynamic-update-slice.2 = f32[8]{0} dynamic-update-slice(f32[8]{0} %multiply_reduce_fusion.5), metadata={op_name="jit(serve_decode_step)/layers/while/body/closed_call/attn/kv_write/dynamic_update_slice"}
  %copy.95 = f32[8]{0} copy(f32[8]{0} %dynamic-update-slice.2)
  %fusion.153 = f32[8]{0} fusion(f32[8]{0} %copy.95), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(serve_decode_step)/layers/while/body/closed_call/attn/qkv/bsd,dhk->bshk/dot_general"}
  %constant.3 = s32[] constant(1)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(s32[] %constant.3, f32[8]{0} %fusion.153)
}

%condition (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %constant.4 = pred[] constant(false)
}

ENTRY %main.18 (tok.1: f32[8], w.1: f32[8]) -> s32[] {
  %tok.1 = f32[8]{0} parameter(0), metadata={op_name="tok"}
  %w.1 = f32[8]{0} parameter(1), metadata={op_name="params[\\'w\\']"}
  %fusion = f32[8]{0} fusion(f32[8]{0} %tok.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(serve_decode_step)/embed/gather"}
  %constant.1 = s32[] constant(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(s32[] %constant.1, f32[8]{0} %fusion)
  %while.12 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), condition=%condition, body=%body, metadata={op_name="jit(serve_decode_step)/layers/while"}
  %get-tuple-element.9 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %while.12), index=1
  %copy.117 = f32[8]{0} copy(f32[8]{0} %get-tuple-element.9)
  %convert.51 = bf16[8]{0} convert(f32[8]{0} %w.1)
  ROOT %reduce.1 = s32[] reduce(f32[8]{0} %copy.117, bf16[8]{0} %convert.51), metadata={op_name="jit(serve_decode_step)/argmax"}
}
"""


def _ev(name, start_us, dur_us):
    return [f"%{name} = f32[8]{{0}} op(...)", start_us * 1000, dur_us * 1000]


def _run(t0):
    """One run of the step (100 us): [op, start, duration] in us from t0."""
    return [
        _ev("fusion", t0, 5),                       # embed
        _ev("while.12", t0 + 5, 75),                # the loop's own event, holding its body
        _ev("multiply_reduce_fusion.5", t0 + 10, 30),  # sdpa
        _ev("dynamic-update-slice.2", t0 + 40, 10),  # kv_write
        _ev("copy.95", t0 + 50, 5),                 # body copy, no metadata: layers
        _ev("fusion.153", t0 + 55, 20),             # qkv
        _ev("copy.117", t0 + 80, 8),                # copy of the loop's result: layers
        _ev("convert.51", t0 + 88, 6),              # hoisted weight cast: unattributed
        _ev("reduce.1", t0 + 94, 6),                # argmax: unattributed
    ]


RAW = {
    "ops": {"0": _run(0) + [_ev("other", 150, 10)] + _run(200)},
    "modules": {"0": [["jit_serve_decode_step(1)", 0, 100_000], ["jit_other(2)", 150_000, 10_000],
                      ["jit_serve_decode_step(1)", 200_000, 100_000]]},
    "host": [["window", 0, 400_000]],
}


def test_each_instruction_takes_its_innermost_scope_or_inherits_one():
    sc = scopes.hlo_scopes(HLO)
    assert sc["multiply_reduce_fusion.5"] == "sdpa"
    assert sc["dynamic-update-slice.2"] == "kv_write"
    assert sc["fusion.153"] == "qkv"
    assert sc["fusion"] == "embed"
    assert sc["while.12"] == "layers"
    # No op_name: the body's copy takes the scope of the while that calls its
    # computation; the entry's copy that of the loop result it copies.
    assert sc["copy.95"] == "layers"
    assert sc["copy.117"] == "layers"
    # A cast of a parameter, and an op named outside every scope, have none.
    assert sc["convert.51"] is None
    assert sc["reduce.1"] is None


def test_scope_times_of_a_recorded_window_by_hand():
    r = scopes.scope_times(RAW, scopes.hlo_scopes(HLO), "serve_decode_step")
    assert r["runs"] == 2
    assert r["module_ms"] == pytest.approx(0.1)
    want_us = {"embed": 5, "sdpa": 30, "kv_write": 10, "qkv": 20,
               # the loop's self time (75 - 65) and the two copies
               "layers": 10 + 5 + 8, "unattributed": 6 + 6}
    assert r["scope_ms"] == pytest.approx({k: v * 1e-3 for k, v in want_us.items()})
    assert sum(r["scope_ms"].values()) == pytest.approx(r["module_ms"])
    assert [op for op, _ in r["unattributed_top"]] == ["convert.51", "reduce.1"]
    assert r["missing_ops"] == []


def test_the_decode_layers_and_unattributed_add_up_to_the_step():
    r = scopes.scope_times(RAW, scopes.hlo_scopes(HLO), "serve_decode_step")
    layers = {m: sum(r["scope_ms"].get(s, 0.0) for s in ss)
              for m, ss in scopes.DECODE_LAYERS.items()}
    assert layers == pytest.approx({"decode_sdpa_ms": 0.030, "decode_kv_cache_ms": 0.033,
                                    "decode_weights_ms": 0.025})
    assert sum(layers.values()) + r["scope_ms"]["unattributed"] == pytest.approx(r["module_ms"])
    used = [s for ss in scopes.DECODE_LAYERS.values() for s in ss]
    assert len(used) == len(set(used)) and set(used) <= scopes.SCOPES


def test_a_program_without_scopes_or_runs_gives_nothing():
    unscoped = HLO.replace("/embed/", "/").replace("/layers/", "/").replace("/attn/", "/") \
        .replace("/sdpa/", "/").replace("/kv_write/", "/").replace("/qkv/", "/") \
        .replace("/layers\"", "\"")
    assert set(scopes.hlo_scopes(unscoped).values()) == {None}
    assert scopes.scope_times(RAW, scopes.hlo_scopes(unscoped), "serve_decode_step") is None
    assert scopes.scope_times(RAW, scopes.hlo_scopes(HLO), "train_step") is None


def test_an_event_names_its_instruction():
    name = ("%copy.95 = bf16[4,16896,8,128]{3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[4,16896,8,128]{3,0,2,1:T(8,128)(2,1)S(1)} %bitcast_dynamic-update-slice_fusion.2)")
    assert scopes.instruction_of(name) == "copy.95"
    assert scopes.scope_of_op_name("jit(f)/layers/while/body/closed_call/attn/sdpa/jit(_where)/select_n") \
        == "sdpa"
    assert scopes.scope_of_op_name("jit(f)/argmax") is None
