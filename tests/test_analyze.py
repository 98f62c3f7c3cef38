"""repro.analyze: static plan verifier + event-log race detector.

Two halves:

  * Clean-pass (zero false positives): pipeline-solved programs, the
    committed example traces, and a synthetic clean schedule all certify
    with every invariant green.
  * Mutation kill (the ISSUE's acceptance oracle): take a valid plan or
    event log, inject exactly one hazard per detector class, and assert
    exactly that detector fires — so every detector is proven live and
    every clean verdict is proven discriminating.
"""

import dataclasses
from pathlib import Path

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analyze import (
    Certificate,
    ScheduleView,
    Violation,
    check_view,
    verify_pool_plan,
    verify_program,
    verify_swap_summary,
    verify_trace_file,
)
from repro.analyze.plan_check import ALL_INVARIANTS
from repro.analyze.schedule_check import SCHEDULE_INVARIANTS, Transfer
from repro.core.events import IterationTrace, VariableInfo
from repro.core.simulator import HardwareSpec, SwapDecision
from repro.core.smartpool import AllocationPlan
from repro.plan import (
    MemoryProgram,
    PassContext,
    Pipeline,
    PlanKey,
    PoolPlacement,
    SwapSelection,
    SwapSummary,
    TimingAssign,
)

HW = HardwareSpec("test", peak_flops=1e12, hbm_bw=1e12, link_bw=1e10, efficiency=1.0)
REPO = Path(__file__).resolve().parent.parent
MiB = 1 << 20


def make_trace(intervals):
    """intervals: (size, alloc, free); one write at alloc, one read before free."""
    vs = [
        VariableInfo(i, s, a, f, accesses=[a, max(a, f - 1)],
                     access_is_write=[True, False])
        for i, (s, a, f) in enumerate(intervals)
    ]
    end = max(f for _, _, f in intervals)
    tr = IterationTrace(vs, end)
    tr.op_costs = {i: (1e9, 1e6) for i in range(end)}
    return tr


def solved_program(limit_frac=0.8):
    tr = make_trace([
        (4 * MiB, 0, 3), (2 * MiB, 1, 6), (8 * MiB, 2, 9),
        (1 * MiB, 4, 8), (4 * MiB, 5, 10), (2 * MiB, 7, 10),
    ])
    ctx = PassContext(hw=HW, size_threshold=1 * MiB)
    return Pipeline([
        TimingAssign(),
        PoolPlacement(("best_fit", "first_fit")),
        SwapSelection(limit=int(tr.peak_load() * limit_frac), scorer="swdoa"),
    ]).run(MemoryProgram.from_trace(tr, PlanKey("synthetic", "unit", HW.name)), ctx)


def failing(violations):
    return sorted({v.invariant for v in violations})


# ---------------------------------------------------------------- clean pass
def test_solved_program_certifies_clean():
    cert = verify_program(solved_program())
    assert cert.ok
    assert set(cert.checks) == set(ALL_INVARIANTS)
    assert all(c["violations"] == [] for c in cert.checks.values())
    # pools and one swap summary actually swept, not vacuous
    assert cert.checks["pool_disjoint_lifetimes"]["subjects"] == 2
    assert cert.checks["swap_budget"]["subjects"] == 1


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=50, max_value=95))
def test_solved_program_certifies_clean_across_limits(pct):
    assert verify_program(solved_program(limit_frac=pct / 100)).ok


def test_committed_example_traces_certify_clean():
    for name in ("churn", "mesh_data4"):
        cert = verify_trace_file(str(REPO / "examples" / "traces" / f"{name}.trace.json"))
        assert cert.ok, cert.failed()
        assert set(cert.checks) == set(SCHEDULE_INVARIANTS)


def test_certificate_round_trip():
    cert = verify_program(solved_program())
    back = Certificate.from_dict(cert.to_dict())
    assert back.ok and back.to_dict() == cert.to_dict()
    cert.add("pool_bounds", 1, [Violation("pool_bounds", "pool:x", "boom")])
    assert not cert.ok and cert.failed() == ["pool_bounds"]


# ------------------------------------------------- plan mutations: pool side
def overlapping_pair(trace, plan):
    vs = [v for v in trace.variables if v.size > 0 and v.var in plan.offsets]
    for a in vs:
        for b in vs:
            if a.var < b.var and a.overlaps(b):
                return a, b
    raise AssertionError("fixture needs two lifetime-overlapping variables")


def test_mutation_overlapping_placements_kills_pool_disjoint():
    prog = solved_program()
    trace = prog.require_trace()
    plan = prog.pool_plans["best_fit"]
    a, b = overlapping_pair(trace, plan)
    plan.offsets[b.var] = plan.offsets[a.var]          # collide two live ranges
    plan.lookup[b.alloc_index] = plan.offsets[a.var]   # keep lookup consistent
    assert failing(verify_pool_plan(trace, plan)) == ["pool_disjoint_lifetimes"]


def test_mutation_offset_past_footprint_kills_pool_bounds():
    prog = solved_program()
    trace = prog.require_trace()
    plan = prog.pool_plans["best_fit"]
    v = max(trace.variables, key=lambda v: v.var)
    plan.offsets[v.var] = plan.footprint + 4096
    plan.lookup[v.alloc_index] = plan.offsets[v.var]
    assert failing(verify_pool_plan(trace, plan)) == ["pool_bounds"]


def test_mutation_stale_lookup_kills_pool_lookup():
    prog = solved_program()
    trace = prog.require_trace()
    plan = prog.pool_plans["best_fit"]
    v = trace.variables[0]
    plan.lookup[v.alloc_index] = plan.offsets[v.var] + 256
    assert failing(verify_pool_plan(trace, plan)) == ["pool_lookup"]


# ------------------------------------------------- plan mutations: swap side
def swap_fixture():
    """One variable with a write, then two reads; one valid absence window
    between the write and the first read.  The filler variable's lifetime
    [3, 5) creates the 8 MiB peak *inside* that window, so absenting v0
    brings the resident floor down to 4 MiB — the floor the schedule
    commits to via ``planned_floor``."""
    v = VariableInfo(0, 4 * MiB, 2, 11, accesses=[2, 6, 10],
                     access_is_write=[True, False, False])
    filler = VariableInfo(1, 4 * MiB, 3, 5, accesses=[3, 4],
                          access_is_write=[True, False])
    tr = IterationTrace([v, filler], 12)
    tr.op_costs = {i: (1e9, 1e6) for i in range(12)}
    d = SwapDecision(var=0, size=4 * MiB, out_after=2, in_before=6)
    summary = SwapSummary(
        scorer="swdoa", limit=5 * MiB, decisions=[d],
        peak_load=8 * MiB, load_min=4 * MiB, overhead=0.0, stalls=0,
        planned_floor=4 * MiB,
    )
    return tr, summary


def test_swap_fixture_is_clean():
    tr, summary = swap_fixture()
    assert verify_swap_summary(tr, summary) == []


def test_mutation_in_before_past_read_kills_read_hazard():
    tr, summary = swap_fixture()
    summary.decisions[0] = dataclasses.replace(summary.decisions[0], in_before=10)
    assert failing(verify_swap_summary(tr, summary)) == ["swap_in_before_read"]


def test_mutation_out_before_last_write_kills_write_hazard():
    tr, summary = swap_fixture()
    v = tr.variables[0]
    v.access_is_write[1] = True   # op 6 becomes the last write
    summary.decisions[0] = dataclasses.replace(
        summary.decisions[0], out_after=2, in_before=10
    )
    assert failing(verify_swap_summary(tr, summary)) == ["swap_out_after_write"]


def test_mutation_double_decision_kills_single_residency():
    tr, summary = swap_fixture()
    summary.decisions.append(
        dataclasses.replace(summary.decisions[0], out_after=6, in_before=10)
    )
    assert failing(verify_swap_summary(tr, summary)) == ["swap_single_residency"]


def test_mutation_inverted_window_kills_well_formed():
    tr, summary = swap_fixture()
    summary.decisions[0] = dataclasses.replace(
        summary.decisions[0], out_after=6, in_before=2
    )
    assert failing(verify_swap_summary(tr, summary)) == ["swap_well_formed"]


def test_mutation_dropped_decision_kills_budget():
    tr, summary = swap_fixture()
    summary.decisions.clear()      # floor returns to the full 8 MiB peak
    assert failing(verify_swap_summary(tr, summary)) == ["swap_budget"]


def test_infeasible_limit_makes_budget_vacuous():
    # Legacy summary (no committed floor) at a limit the candidate set
    # provably cannot reach: the budget obligation is vacuous.
    tr, summary = swap_fixture()
    summary.planned_floor = None
    summary.decisions.clear()
    summary.limit = 2 * MiB        # < load_min: recorded-infeasible schedule
    assert verify_swap_summary(tr, summary) == []


def test_legacy_summary_over_feasible_limit_kills_budget():
    # Without a committed floor the verifier falls back to floor <= limit
    # whenever the limit was feasible (limit >= load_min).
    tr, summary = swap_fixture()
    summary.planned_floor = None
    summary.decisions.clear()      # floor returns to the full 8 MiB peak
    assert failing(verify_swap_summary(tr, summary)) == ["swap_budget"]


def test_best_effort_floor_above_limit_is_clean():
    # Greedy selection is best-effort: a committed floor above the limit is
    # a legitimate solver outcome as long as the decisions reproduce it.
    tr, summary = swap_fixture()
    summary.limit = 3 * MiB        # below the committed 4 MiB floor
    assert verify_swap_summary(tr, summary) == []


# -------------------------------------------------------- schedule mutations
def clean_view():
    """Two tenants, one device, serialized transfers, consistent ledgers."""
    report = {
        "budget": 10 * MiB,
        "overflow_events": 0,
        "aggregate_peak": 9 * MiB,
        "tenants": [
            {"name": "a", "status": "completed", "device": None,
             "floor": 4 * MiB, "renegotiation_freed_bytes": 0,
             "attribution": {"overhead_s": 0.5, "swap_in_transfer_s": 0.3,
                             "residual_s": 0.2, "queue_wait_s": 0.1}},
            {"name": "b", "status": "completed", "device": None,
             "floor": 5 * MiB, "renegotiation_freed_bytes": 0,
             "attribution": {"overhead_s": 0.1, "swap_in_transfer_s": 0.1,
                             "residual_s": 0.0, "queue_wait_s": 0.0}},
        ],
        "attribution": {"overhead_s": 0.6, "swap_in_transfer_s": 0.4,
                        "residual_s": 0.2, "queue_wait_s": 0.1},
    }
    view = ScheduleView(source="unit", report=report)
    view.transfers = [
        Transfer("a", "default", "out", 0, 1.0, 2.0, 0, lane=0, ready=1.0, size=MiB),
        Transfer("a", "default", "in", 0, 4.0, 5.0, 0, lane=0, ready=3.5, size=MiB),
        Transfer("b", "default", "out", 1, 2.5, 3.5, 1, lane=1, ready=2.5, size=MiB),
    ]
    view.blackouts = [(2.1, 2.4)]
    view.admissions = [("a", "default", 0.0, 0.0), ("b", "default", 0.0, 0.1)]
    view.finishes = [("a", "default", 6.0), ("b", "default", 7.0)]
    view.hbm_samples = {"default": [3 * MiB, 9 * MiB, 5 * MiB]}
    return view


def test_clean_view_certifies():
    cert = check_view(clean_view())
    assert cert.ok, cert.failed()
    assert set(cert.checks) == set(SCHEDULE_INVARIANTS)


def test_mutation_channel_overlap_kills_channel_exclusive():
    view = clean_view()
    t = view.transfers[1]
    view.transfers.append(dataclasses.replace(t, var=7, lane=None,
                                              start=t.start + 0.2, end=t.end + 0.2))
    cert = check_view(view)
    assert cert.failed() == ["channel_exclusive"]


def test_mutation_lane_overlap_kills_lane_exclusive():
    view = clean_view()
    t = view.transfers[2]
    view.transfers.append(dataclasses.replace(t, var=8, channel=None,
                                              start=t.start + 0.2, end=t.end + 0.2))
    cert = check_view(view)
    assert cert.failed() == ["lane_exclusive"]


def test_mutation_transfer_into_known_blackout_kills_blackout_exclusion():
    view = clean_view()
    # The blackout was registered (start 2.8) before this out transfer
    # acquired its lane (ready 3.0), yet the transfer [4.0, 5.5) crosses it:
    # the scheduler must have skipped the exclusion window.
    view.transfers.append(
        Transfer("b", "default", "out", 9, 4.0, 5.5, 1, lane=1, ready=3.0, size=MiB)
    )
    view.blackouts.append((2.8, 4.6))
    cert = check_view(view)
    assert "blackout_exclusion" in cert.failed()


def test_blackout_after_acquisition_is_legitimate():
    view = clean_view()
    # same overlap, but the blackout starts after ready: registered later
    view.transfers.append(
        Transfer("b", "default", "out", 9, 4.0, 5.5, 1, lane=1, ready=3.0, size=MiB)
    )
    view.blackouts.append((4.5, 5.0))
    view.transfers[-1] = dataclasses.replace(view.transfers[-1], ready=4.0)
    assert check_view(view).ok


def test_mutation_overbudget_sample_kills_budget_monotone():
    view = clean_view()
    view.hbm_samples["default"].append(11 * MiB)
    cert = check_view(view)
    assert cert.failed() == ["budget_monotone"]


def test_mutation_double_admit_kills_reservation_isolation():
    view = clean_view()
    view.admissions.append(("a", "default", 0.0, 0.2))
    cert = check_view(view)
    assert cert.failed() == ["reservation_isolation"]


def test_mutation_floor_oversubscription_kills_reservation_isolation():
    view = clean_view()
    view.report["tenants"][1]["floor"] = 7 * MiB   # 4 + 7 > 10 MiB budget
    cert = check_view(view)
    assert cert.failed() == ["reservation_isolation"]


def test_mutation_leaky_ledger_kills_ledger_closure():
    view = clean_view()
    view.report["tenants"][0]["attribution"]["swap_in_transfer_s"] = 0.4
    cert = check_view(view)
    assert "ledger_closure" in cert.failed()


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=0, max_value=2), st.floats(min_value=0.05, max_value=0.8))
def test_mutation_any_channel_shift_is_caught_or_harmless(idx, shift):
    """Property form: shifting one transfer's start earlier either keeps the
    schedule exclusive (no overlap created) or trips exactly the
    channel/lane detectors — never a silent pass with an overlap present."""
    view = clean_view()
    t = view.transfers[idx]
    moved = dataclasses.replace(t, start=t.start - shift, ready=None)
    view.transfers[idx] = moved
    overlap = any(
        o is not moved and o.channel == moved.channel
        and moved.start < o.end and o.start < moved.end
        for o in view.transfers
    )
    cert = check_view(view)
    if overlap:
        assert not cert.ok
        assert set(cert.failed()) <= {"channel_exclusive", "lane_exclusive"}
    else:
        assert cert.ok


# ------------------------------------------------------------- CLI classifier
def test_analyze_cli_classifies_plan_and_trace(tmp_path):
    import json

    from repro.launch.analyze import main as analyze_main
    from repro.plan.artifact import program_to_json

    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json.dumps(program_to_json(solved_program())))
    trace_path = REPO / "examples" / "traces" / "mesh_data4.trace.json"
    assert analyze_main(["-q", str(plan_path), str(trace_path)]) == 0
    assert analyze_main([str(tmp_path / "missing.json")]) == 1
