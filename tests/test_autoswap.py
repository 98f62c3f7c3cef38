"""AutoSwap: candidates, priority scores, selection, schedule validity."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.autoswap import AutoSwapPlanner
from repro.core.events import IterationTrace, VariableInfo
from repro.core.simulator import GTX_1080TI, HardwareSpec, simulate_swap_schedule

HW = HardwareSpec("test", peak_flops=1e12, hbm_bw=1e12, link_bw=1e10, efficiency=1.0)


def synth_trace(n_layers=8, act_bytes=8 << 20, weight_bytes=4 << 20):
    """Forward/backward-shaped trace: weights read early+late, activations
    produced in forward and consumed in reverse order in backward."""
    vs = []
    idx = 0
    var = 0
    n_ops = 4 * n_layers + 2
    fwd_w, fwd_a = [], []
    for l in range(n_layers):
        # weight: lives whole iteration, accessed in fwd at 2l and bwd late
        w = VariableInfo(var, weight_bytes, 0, n_ops, [2 * l], [False]); var += 1
        a = VariableInfo(var, act_bytes, 2 * l, 0, [2 * l + 1], [True]); var += 1
        vs.append(w); fwd_w.append(w)
        vs.append(a); fwd_a.append(a)
    peak_idx = 2 * n_layers
    for l in reversed(range(n_layers)):
        bwd_idx = 2 * n_layers + 2 * (n_layers - 1 - l) + 1
        fwd_w[l].accesses.append(bwd_idx)
        fwd_w[l].access_is_write.append(False)
        fwd_a[l].accesses.append(bwd_idx)
        fwd_a[l].access_is_write.append(False)
        fwd_a[l].free_index = bwd_idx + 1
    tr = IterationTrace(vs, n_ops)
    tr.op_costs = {i: (1e9, 1e6) for i in range(n_ops)}  # 1 ms per op
    return tr


def test_candidates_filter_size_and_peak():
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW, size_threshold=5 << 20)
    # only activations (8 MiB) pass the 5 MiB threshold; early-layer ones span peak
    assert all(c.size == 8 << 20 for c in pl.candidates if not c.wraps)
    assert len(pl.candidates) > 0


def test_scores_prefer_early_layers():
    """Earlier-layer activations have wider gaps -> higher DOA/AOA."""
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    acts = [c for c in pl.candidates if c.size == 8 << 20 and not c.wraps]
    acts_sorted = sorted(acts, key=lambda c: c.out_after)
    doas = [c.scores["doa"] for c in acts_sorted]
    assert doas == sorted(doas, reverse=True)


def test_selection_meets_limit_synchronously():
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(pl.peak_load * 0.7)
    dec = pl.select(limit, "swdoa")
    assert pl.updated_load(dec).max() <= limit


def test_schedule_validity_invariants():
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(pl.peak_load * 0.7)
    dec = pl.select(limit, "swdoa")
    r = simulate_swap_schedule(tr, dec, HW, limit)
    times = tr.op_times
    by_var = {d.var: d for d in dec}
    # swap-out starts only after the trigger access's original start time
    for var, start, end in r.out_events:
        d = by_var[var]
        assert end > start
        assert start >= times[d.out_after] - 1e-12
    # out stream is serialized
    outs = sorted(r.out_events, key=lambda e: e[1])
    for k in range(1, len(outs)):
        assert outs[k][1] >= outs[k - 1][2] - 1e-12
    ins = sorted(r.in_events, key=lambda e: e[1])
    for k in range(1, len(ins)):
        assert ins[k][1] >= ins[k - 1][2] - 1e-12
    # every decision got swapped in before iteration end or stalled the access
    assert r.overhead >= 0.0


def test_zero_decisions_zero_overhead():
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW)
    r = simulate_swap_schedule(tr, [], HW, None)
    assert r.overhead == 0.0
    assert r.duration_s == pytest.approx(r.baseline_s)


def test_load_min_leq_peak():
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    assert pl.load_min() <= pl.peak_load


def test_swdoa_reranks_with_updated_load():
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    for c in pl.candidates:
        assert "swdoa" in c.scores


def test_wrap_candidates_for_weights():
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    wraps = [c for c in pl.candidates if c.wraps]
    assert wraps, "weights alive across the boundary should yield wrap candidates"
    for c in wraps:
        assert c.in_before <= c.out_after


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 12), st.floats(0.5, 0.95))
def test_property_overhead_nonnegative_and_peak_respected(n_layers, frac):
    tr = synth_trace(n_layers=n_layers)
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(pl.peak_load * frac)
    dec = pl.select(limit, "aoa")
    r = simulate_swap_schedule(tr, dec, HW, limit)
    assert r.overhead >= 0.0
    assert r.duration_s >= r.baseline_s - 1e-9
