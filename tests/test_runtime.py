"""repro.runtime: engine equivalence, channel scaling, multi-tenant invariants.

Covers the runtime tentpole:
  * the refactored engine reproduces the pre-runtime simulator bit-for-bit
    (1 tenant, 2 channels, eager prefetch) against a frozen reference copy;
  * property: more DMA channels never increase simulated overhead;
  * per-channel transfers are serialized (no overlap), directions partitioned;
  * the shared budget is never exceeded by guarded admissions across tenants,
    including the two-in-channel double-admission hazard;
  * admission control queues (not kills) tenants whose floor doesn't fit.
"""

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core._solver_reference import reference_simulate_swap_schedule
from repro.core.autoswap import AutoSwapPlanner
from repro.core.events import IterationTrace, VariableInfo
from repro.core.simulator import HardwareSpec, SwapDecision, simulate_swap_schedule
from repro.plan import MemoryProgram, PassContext, Pipeline, PlanCache, PlanKey, SwapSelection, swap_key
from repro.runtime import (
    ChannelPool,
    MemoryRuntime,
    Tenant,
    colocate_programs,
    planned_peak,
    simulate_program,
    tenant_from_program,
)

HW = HardwareSpec("test", peak_flops=1e12, hbm_bw=1e12, link_bw=1e10, efficiency=1.0)


def synth_trace(n_layers=8, act_bytes=8 << 20, weight_bytes=4 << 20):
    """Forward/backward-shaped trace (same family as test_autoswap)."""
    vs = []
    var = 0
    n_ops = 4 * n_layers + 2
    fwd_w, fwd_a = [], []
    for l in range(n_layers):
        w = VariableInfo(var, weight_bytes, 0, n_ops, [2 * l], [False]); var += 1
        a = VariableInfo(var, act_bytes, 2 * l, 0, [2 * l + 1], [True]); var += 1
        vs.append(w); fwd_w.append(w)
        vs.append(a); fwd_a.append(a)
    for l in reversed(range(n_layers)):
        bwd_idx = 2 * n_layers + 2 * (n_layers - 1 - l) + 1
        fwd_w[l].accesses.append(bwd_idx)
        fwd_w[l].access_is_write.append(False)
        fwd_a[l].accesses.append(bwd_idx)
        fwd_a[l].access_is_write.append(False)
        fwd_a[l].free_index = bwd_idx + 1
    tr = IterationTrace(vs, n_ops)
    tr.op_costs = {i: (1e9, 1e6) for i in range(n_ops)}
    return tr


# --------------------------------------------------------------- reference
# Frozen copy of the pre-runtime ``simulate_swap_schedule`` event loop, now
# shared with benchmarks/bench_churn.py via core/_solver_reference.py.
_reference_simulate = reference_simulate_swap_schedule


FIELDS = ("baseline_s", "duration_s", "peak_resident", "stalls",
          "delayed_mallocs", "tail_spill_s", "out_events", "in_events")


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 12), st.floats(0.45, 0.95))
def test_engine_matches_reference_simulator_exactly(n_layers, frac):
    tr = synth_trace(n_layers=n_layers)
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(pl.peak_load * frac)
    dec = pl.select(limit, "swdoa")
    ref = _reference_simulate(tr, dec, HW, limit)
    got = simulate_swap_schedule(tr, dec, HW, limit)
    for f in FIELDS:
        assert getattr(got, f) == getattr(ref, f), f


def test_engine_matches_reference_no_limit_no_decisions():
    tr = synth_trace()
    ref = _reference_simulate(tr, [], HW, None)
    got = simulate_swap_schedule(tr, [], HW, None)
    for f in FIELDS:
        assert getattr(got, f) == getattr(ref, f), f


# --------------------------------------------------------- channel scaling
@settings(max_examples=25, deadline=None)
@given(st.integers(2, 12), st.floats(0.45, 0.95), st.sampled_from(["swdoa", "aoa"]))
def test_property_more_channels_never_increase_overhead(n_layers, frac, scorer):
    """2 DMA channels never simulate *higher* overhead than 1, nor 4 than 2."""
    tr = synth_trace(n_layers=n_layers)
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(pl.peak_load * frac)
    dec = pl.select(limit, scorer)
    o1 = simulate_program(tr, dec, HW, limit, channels=1).overhead
    o2 = simulate_program(tr, dec, HW, limit, channels=2).overhead
    o4 = simulate_program(tr, dec, HW, limit, channels=4).overhead
    assert o2 <= o1 + 1e-12
    assert o4 <= o2 + 1e-12


def test_channel_pool_direction_partition():
    one = ChannelPool.make(1)
    assert one.out_ids == one.in_ids == (0,)
    two = ChannelPool.make(2)
    assert two.out_ids == (0,) and two.in_ids == (1,)
    five = ChannelPool.make(5)
    assert set(five.out_ids) | set(five.in_ids) == set(range(5))
    assert not set(five.out_ids) & set(five.in_ids)


def test_channels_are_serialized_and_direction_partitioned():
    """No two transfers overlap on one channel; outs/ins stay on their side."""
    tenants = []
    for name, layers, frac in (("A", 8, 0.6), ("B", 6, 0.6)):
        tr = synth_trace(layers)
        pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
        lim = int(pl.peak_load * frac)
        tenants.append(Tenant(name, tr, pl.select(lim, "swdoa"), limit=lim))
    budget = sum(t.limit for t in tenants)
    rt = MemoryRuntime(HW, budget=budget, channels=4)
    rt.run(tenants)
    per_channel = {}
    for run in rt.runs.values():
        for var, s, e, ch in run.out_events:
            assert ch in rt.channels.out_ids
            per_channel.setdefault(ch, []).append((s, e))
        for var, s, e, ch in run.in_events:
            assert ch in rt.channels.in_ids
            per_channel.setdefault(ch, []).append((s, e))
    assert per_channel, "expected swap traffic"
    for ch, spans in per_channel.items():
        spans.sort()
        for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
            assert s1 >= e0 - 1e-12, f"channel {ch} transfers overlap"


# ------------------------------------------------------ multi-tenant budget
def test_colocated_tenants_never_exceed_budget():
    tenants = []
    for name, layers in (("A", 8), ("B", 6), ("C", 4)):
        tr = synth_trace(layers)
        pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
        lim = int(pl.peak_load * 0.7)
        tenants.append(Tenant(name, tr, pl.select(lim, "swdoa"), limit=lim))
    budget = sum(planned_peak(t.trace, t.decisions) for t in tenants)
    rt = MemoryRuntime(HW, budget=budget, channels=2)
    rep = rt.run(tenants)
    assert all(t.status == "completed" for t in rep.tenants)
    assert rep.overflow_events == 0
    assert rep.aggregate_peak <= budget


def test_two_in_channels_do_not_double_admit():
    """Two prefetches due together on two in-channels, headroom for one:
    schedule-time reservation must keep the second out until room appears."""
    MB = 1 << 20
    n_ops = 8
    vs = [
        VariableInfo(0, 1 * MB, 0, n_ops, [0], [True]),              # D: always resident
        VariableInfo(1, 10 * MB, 0, n_ops, [1, 6], [True, False]),   # A
        VariableInfo(2, 10 * MB, 2, n_ops, [3, 6], [True, False]),   # B
    ]
    tr = IterationTrace(vs, n_ops)
    tr.op_costs = {i: (1e9, 0.0) for i in range(n_ops)}
    dec = [SwapDecision(1, 10 * MB, 1, 6), SwapDecision(2, 10 * MB, 3, 6)]
    budget = 21 * MB  # D + both swapped vars: feasible at the deadline only
    rt = MemoryRuntime(HW, budget=budget, channels=4)  # 2 out + 2 in channels
    rep = rt.run([Tenant("t", tr, dec, floor=0)])
    assert rep.overflow_events == 0
    assert rep.aggregate_peak <= budget
    ins = sorted(rt.runs["t"].in_events, key=lambda e: e[1])
    assert len(ins) == 2
    # Despite two free in-channels the transfers must be staggered: the
    # second may only start once the first tenant byte count leaves room
    # (here: after B's own swap-out retires).
    assert ins[1][1] >= ins[0][1] + 1e-12


def test_admission_queues_third_tenant_and_runs_it_later():
    tr = synth_trace(8)
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    lim = int(pl.peak_load * 0.7)
    dec = pl.select(lim, "swdoa")
    floor = planned_peak(tr, dec)
    tenants = [Tenant(f"T{i}", synth_trace(8), list(dec), limit=lim) for i in range(3)]
    budget = int(floor * 2.5)  # fits two floors, not three
    rep = MemoryRuntime(HW, budget=budget, channels=2).run(tenants)
    assert [t.status for t in rep.tenants] == ["completed"] * 3
    waits = [t.queue_wait_s for t in rep.tenants]
    assert waits[0] == 0.0 and waits[1] == 0.0
    assert waits[2] > 0.0, "third tenant should queue for admission"
    t2 = rep.tenant("T2")
    assert t2.admitted_at >= min(rep.tenant("T0").finished_at, rep.tenant("T1").finished_at) - 1e-12


def test_finished_tenants_release_residency_to_later_admissions():
    """Sequential admission: a finished tenant's persistent bytes (freed at
    delta[num_indices], which the op loop never applies) must leave the
    shared accountant, or every later tenant runs in a shrunken budget."""
    tr = synth_trace(8)
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    lim = int(pl.peak_load * 0.7)
    dec = pl.select(lim, "swdoa")
    floor = planned_peak(tr, dec)
    tenants = [Tenant(f"T{i}", synth_trace(8), list(dec), limit=lim) for i in range(4)]
    budget = 2 * floor  # two at a time; T2/T3 admitted after T0/T1 finish
    rep = MemoryRuntime(HW, budget=budget, channels=2).run(tenants)
    assert [t.status for t in rep.tenants] == ["completed"] * 4
    assert rep.overflow_events == 0
    assert rep.aggregate_peak <= budget
    # Later-admitted tenants see the same effective budget: their overhead
    # stays in the same band as the first wave's (channel-contention phase
    # differences aside).  Before the residency-release fix they ran inside
    # a budget shrunken by the finishers' dead bytes (26%+ overhead vs 4%).
    oh = [t.overhead for t in rep.tenants]
    assert max(oh[2], oh[3]) <= max(oh[0], oh[1]) + 0.02


def test_duplicate_tenant_names_rejected():
    """Accounting is keyed by tenant name; two tenants sharing one would
    silently merge residency (and release_residency would free the survivor's
    bytes), so the engine refuses up front."""
    tr = synth_trace(4)
    with pytest.raises(ValueError, match="unique"):
        MemoryRuntime(HW, channels=2).run([Tenant("t", tr), Tenant("t", synth_trace(4))])


def test_unschedulable_tenant_reported_not_killed():
    big = synth_trace(12)
    small = synth_trace(2)
    pl = AutoSwapPlanner(small, HW, size_threshold=1 << 20)
    lim = int(pl.peak_load * 0.8)
    tenants = [
        Tenant("big", big, [], limit=None),           # floor == full peak
        Tenant("small", small, pl.select(lim, "swdoa"), limit=lim),
    ]
    budget = planned_peak(small, tenants[1].decisions)
    rep = MemoryRuntime(HW, budget=budget, channels=2).run(tenants)
    assert rep.tenant("big").status == "unschedulable"
    assert rep.tenant("small").status == "completed"


def test_multi_iteration_tenant_accumulates_duration():
    tr = synth_trace(4)
    one = MemoryRuntime(HW, channels=2).run([Tenant("t", tr, iterations=1)])
    two = MemoryRuntime(HW, channels=2).run([Tenant("t", tr, iterations=2)])
    d1, d2 = one.tenant("t").duration_s, two.tenant("t").duration_s
    assert d2 == pytest.approx(2 * d1, rel=1e-9)
    assert two.aggregate_peak == one.aggregate_peak


# -------------------------------------------------------- plan integration
def test_tenant_from_program_uses_cached_schedule(tmp_path):
    tr = synth_trace(6)
    key = PlanKey("synthetic", "runtime-unit", HW.name)
    prog = MemoryProgram.from_trace(tr, key)
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(pl.peak_load * 0.7)
    cache = PlanCache(tmp_path)
    tenant = tenant_from_program("t", prog, HW, limit, cache=cache)
    assert tenant.decisions, "expected a non-empty schedule at 70% limit"
    assert cache.load(key) is not None, "schedule should persist to the cache"
    # A second build from the restored artifact reuses the stored decisions.
    restored = cache.load(key)
    tenant2 = tenant_from_program("t", restored, HW, limit, cache=cache)
    assert tenant2.decisions == tenant.decisions
    assert restored.swap_summaries[swap_key("swdoa", limit)].decisions == tenant.decisions


def test_colocate_programs_shares_budget_below_isolated_sum():
    progs = {
        "a": MemoryProgram.from_trace(synth_trace(8)),
        "b": MemoryProgram.from_trace(synth_trace(6)),
    }
    result = colocate_programs(progs, HW, budget_frac=0.75, channels=2,
                               size_threshold=1 << 20)
    rep = result.report
    assert all(t.status == "completed" for t in rep.tenants)
    assert rep.aggregate_peak <= result.budget
    assert rep.aggregate_peak < result.sum_natural_peaks
    assert 0.0 < result.sharing_gain < 1.0


def test_planned_peak_subtracts_absence_windows():
    MB = 1 << 20
    vs = [
        VariableInfo(0, 4 * MB, 0, 10, [1, 8], [True, False]),
        VariableInfo(1, 2 * MB, 0, 10, [0], [True]),
    ]
    tr = IterationTrace(vs, 10)
    assert planned_peak(tr, []) == 6 * MB
    assert planned_peak(tr, [SwapDecision(0, 4 * MB, 1, 8)]) == 6 * MB  # ends outside window
    # inside the absence window only var 1 remains
    curve_peak = planned_peak(tr, [SwapDecision(0, 4 * MB, 0, 10)])
    assert curve_peak == 2 * MB
