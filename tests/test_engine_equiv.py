"""Bit-for-bit equivalence of the vectorized engine vs the frozen reference.

PR 6 rewrote ``runtime/engine.py``'s hot paths onto precomputed structures
(prefetch index, pending-out heap, bisected collective windows, event
frontier, per-decision due constants).  ``runtime/_engine_reference.py`` is
the pre-vectorization engine, frozen verbatim; every simulated quantity the
two produce must be *identical* — not approximately equal — across channel
counts, budgets, seeded churn workloads, renegotiation on/off, and mesh
shapes with a contended HostLink.  The same pinning discipline PR 3 applied
to the solvers (tests/test_solver_equiv.py).
"""

from __future__ import annotations

import json

import pytest

from repro.core.planner import AutoSwapPlanner
from repro.core.simulator import GTX_1080TI
from repro.runtime import _engine_reference as ref
from repro.runtime import engine as fast
from repro.runtime.engine import planned_peak, simulated_report_dict
from repro.runtime.workload import poisson_workload, synthetic_train_trace
from hypothesis import given, settings
from hypothesis import strategies as st

HW = GTX_1080TI
SIZE_THRESHOLD = 1 << 20


def solve(trace, frac=0.7, scorer="swdoa"):
    pl = AutoSwapPlanner(trace, HW, size_threshold=SIZE_THRESHOLD)
    limit = int(pl.peak_load * frac)
    return limit, pl.select(limit, scorer)


# Templates and plans are immutable once solved: build them once.
TEMPLATES = {
    "small": synthetic_train_trace(4),
    "medium": synthetic_train_trace(6),
    "base": synthetic_train_trace(10),
}
PLANS = {name: solve(tr) for name, tr in TEMPLATES.items()}
FLOORS = {n: planned_peak(TEMPLATES[n], PLANS[n][1]) for n in TEMPLATES}
# A medium newcomer doesn't fit next to the base's full floor; a small one
# does — the budget that exercises queueing AND renegotiation.
BUDGET = FLOORS["base"] + (FLOORS["small"] + FLOORS["medium"]) // 2


def canon(report) -> str:
    """Reports reduced to simulated quantities, as a comparable string.

    ``simulated_report_dict`` strips wall-clock counters (engine throughput,
    renegotiation solve ms) and the per-tenant event counts the reference
    engine doesn't track; it accepts reports from either engine.
    """
    return json.dumps(simulated_report_dict(report), sort_keys=True)


def churn_tenants(mod, items, base_iters=6):
    ts = [
        mod.Tenant(
            "base", TEMPLATES["base"], list(PLANS["base"][1]),
            limit=PLANS["base"][0], iterations=base_iters, priority=0.5,
        )
    ]
    for it in items:
        limit, decisions = PLANS[it.template]
        ts.append(
            mod.Tenant(
                it.name, TEMPLATES[it.template], list(decisions), limit=limit,
                iterations=it.iterations, arrival_t=it.arrival_t,
                priority=it.priority,
            )
        )
    return ts


def run_both(make_tenants, **kw):
    """One run per engine with identical config; returns (fast, reference)
    MemoryRuntime instances with their reports attached as ``.report``."""
    out = []
    for mod in (fast, ref):
        rt = mod.MemoryRuntime(
            HW,
            budget=kw.get("budget"),
            channels=kw.get("channels", 2),
            prefetch=kw.get("prefetch", "backsched"),
            renegotiate=kw.get("renegotiate", False),
            replan_size_threshold=SIZE_THRESHOLD,
            link=mod.HostLink.make(*kw["link"]) if kw.get("link") else None,
            contention_aware=kw.get("contention_aware", True),
        )
        rt.report = rt.run(make_tenants(mod))
        out.append(rt)
    return out


# ------------------------------------------------------------- single tenant
@pytest.mark.parametrize("channels", [1, 2, 3, 4])
@pytest.mark.parametrize("prefetch", ["eager", "backsched"])
def test_single_tenant_facade_bit_for_bit(channels, prefetch):
    trace = TEMPLATES["medium"]
    limit, decisions = PLANS["medium"]
    got = fast.simulate_program(trace, decisions, HW, limit,
                                channels=channels, prefetch=prefetch)
    want = ref.simulate_program(trace, decisions, HW, limit,
                                channels=channels, prefetch=prefetch)
    assert got == want


def test_core_simulator_facade_unchanged():
    from repro.core.simulator import simulate_swap_schedule

    trace = TEMPLATES["small"]
    limit, decisions = PLANS["small"]
    got = simulate_swap_schedule(trace, decisions, HW, limit)
    want = ref.simulate_program(trace, decisions, HW, limit,
                                channels=2, prefetch="eager")
    assert got == want


# ------------------------------------------------------------ churn property
@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    channels=st.sampled_from([1, 2, 3]),
    renegotiate=st.sampled_from([False, True]),
    budget_kind=st.sampled_from(["tight", "roomy", "none"]),
)
def test_churn_workloads_bit_for_bit(seed, channels, renegotiate, budget_kind):
    budget = {"tight": BUDGET, "roomy": BUDGET * 4, "none": None}[budget_kind]
    if budget is None and renegotiate:
        renegotiate = False  # renegotiation needs a budget to defend
    items = poisson_workload(
        ["small", "medium"], 6, 50.0, seed=seed, iterations=(1, 3)
    )
    frt, rrt = run_both(
        lambda mod: churn_tenants(mod, items),
        budget=budget, channels=channels, renegotiate=renegotiate,
    )
    assert canon(frt.report) == canon(rrt.report)


def test_eager_prefetch_multi_tenant_bit_for_bit():
    items = poisson_workload(["small", "medium"], 6, 50.0, seed=3, iterations=(1, 3))
    frt, rrt = run_both(
        lambda mod: churn_tenants(mod, items), budget=BUDGET, prefetch="eager"
    )
    assert canon(frt.report) == canon(rrt.report)


def test_departure_churn_bit_for_bit():
    def mk(mod):
        ts = churn_tenants(mod, poisson_workload(
            ["small", "medium"], 4, 80.0, seed=5, iterations=(1, 2)))
        ts[0].departure_t = 0.08  # open-ended base departs mid-horizon
        ts[0].iterations = 1
        return ts

    frt, rrt = run_both(mk, budget=BUDGET, renegotiate=True)
    assert canon(frt.report) == canon(rrt.report)


# --------------------------------------------------------------------- mesh
def mesh_tenants(mod, devices=4):
    """A data-parallel mesh shape built directly from Tenants (no jax):
    one tenant per device, tagged collectives, first device owns blackouts."""
    ts = []
    for i in range(devices):
        name = "small" if i % 2 else "medium"
        trace = TEMPLATES[name]
        limit, decisions = PLANS[name]
        colls = {2: 0.004, trace.num_indices - 2: 0.006}
        ts.append(
            mod.Tenant(
                f"shard{i}", trace, list(decisions), limit=limit,
                iterations=3, device=f"d{i}", collectives=colls,
                collective_owner=(i == 0),
            )
        )
    return ts


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("lanes", [1, 2])
@pytest.mark.parametrize("contention_aware", [True, False])
def test_mesh_contended_link_bit_for_bit(devices, lanes, contention_aware):
    frt, rrt = run_both(
        lambda mod: mesh_tenants(mod, devices),
        link=(HW.link_bw, lanes), contention_aware=contention_aware,
    )
    assert canon(frt.report) == canon(rrt.report)
    # The per-transfer schedules (what repro.dist compares) match too.
    for name in frt.runs:
        assert frt.runs[name].out_events == rrt.runs[name].out_events
        assert frt.runs[name].in_events == rrt.runs[name].in_events


def test_mesh_budgeted_bit_for_bit():
    frt, rrt = run_both(
        lambda mod: mesh_tenants(mod, 4),
        budget=max(FLOORS.values()) * 2, link=(HW.link_bw, 2),
    )
    assert canon(frt.report) == canon(rrt.report)


def mesh_churn_tenants(mod, newcomer_arrival=0.02, devices=4):
    """The data=4 contended mesh plus a late newcomer on shard0's device —
    the shape where renegotiation, collectives, and the shared link all
    interact in one run."""
    ts = mesh_tenants(mod, devices)
    limit, decisions = PLANS["small"]
    ts.append(
        mod.Tenant(
            "late", TEMPLATES["small"], list(decisions), limit=limit,
            iterations=1, device="d0", arrival_t=newcomer_arrival,
            priority=2.0,
        )
    )
    return ts


@pytest.mark.parametrize("newcomer_arrival", [0.005, 0.02])
def test_mesh_resume_contended_data4_byte_identical(newcomer_arrival):
    """resume() coverage on a contended data=4 mesh: a newcomer on d0 forces
    a renegotiation barrier while all four shards contend on the HostLink
    (collective blackouts included) — the suffix replay must still be byte
    identical to the full horizon, and the full horizon to the reference."""
    budget = FLOORS["medium"] + FLOORS["small"] // 2
    frt, rrt = run_both(
        lambda mod: mesh_churn_tenants(mod, newcomer_arrival),
        budget=budget, renegotiate=True, link=(HW.link_bw, 2),
    )
    full = canon(frt.report)
    assert full == canon(rrt.report)
    capturing = fast.MemoryRuntime(
        HW, budget=budget, channels=2, renegotiate=True,
        replan_size_threshold=SIZE_THRESHOLD, capture_snapshots=True,
        link=fast.HostLink.make(HW.link_bw, 2))
    assert canon(capturing.run(mesh_churn_tenants(fast, newcomer_arrival))) == full
    assert frt.report.renegotiations >= 1, "shape must exercise renegotiation"
    assert capturing.barrier_snapshots, "no barrier snapshot captured"
    for snap in capturing.barrier_snapshots:
        assert canon(snap.resume()) == full


# ------------------------------------------------------- engine-only features
def test_record_events_off_same_simulated_report():
    items = poisson_workload(["small", "medium"], 6, 50.0, seed=9, iterations=(1, 3))
    on = fast.MemoryRuntime(HW, budget=BUDGET, channels=2, record_events=True)
    r_on = on.run(churn_tenants(fast, items))
    off = fast.MemoryRuntime(HW, budget=BUDGET, channels=2, record_events=False)
    r_off = off.run(churn_tenants(fast, items))
    assert canon(r_on) == canon(r_off)
    assert all(not r.out_events and not r.in_events for r in off.runs.values())
    assert any(r.out_events or r.in_events for r in on.runs.values())
    # Tail spill is derived from out events; it must survive the gating.
    for name in on.runs:
        assert on.runs[name].sim_result().tail_spill_s == \
            off.runs[name].sim_result().tail_spill_s


def test_engine_counters_in_report():
    items = poisson_workload(["small", "medium"], 4, 50.0, seed=1, iterations=(1, 2))
    rt = fast.MemoryRuntime(HW, budget=BUDGET, channels=2)
    rep = rt.run(churn_tenants(fast, items))
    d = rep.as_dict()
    assert d["engine"]["events"] > 0
    assert d["engine"]["run_wall_s"] > 0
    assert d["engine"]["events_per_s"] > 0
    assert sum(t["events"] for t in d["tenants"]) == d["engine"]["events"]
    # The reference engine reports no engine block — and the canonical
    # simulated view strips it from both, so the dicts stay comparable.
    assert "engine" not in simulated_report_dict(rep)


def test_suffix_replay_byte_identical():
    """resume() on a barrier snapshot must reproduce the full-horizon report
    byte for byte — and capturing snapshots must not change the run."""
    replayed = 0
    for seed in range(6):
        items = poisson_workload(
            ["small", "medium"], 6, 50.0, seed=seed, iterations=(1, 3))
        capturing = fast.MemoryRuntime(
            HW, budget=BUDGET, channels=2, renegotiate=True,
            replan_size_threshold=SIZE_THRESHOLD, capture_snapshots=True)
        full = canon(capturing.run(churn_tenants(fast, items)))
        plain = fast.MemoryRuntime(
            HW, budget=BUDGET, channels=2, renegotiate=True,
            replan_size_threshold=SIZE_THRESHOLD)
        assert canon(plain.run(churn_tenants(fast, items))) == full
        for snap in capturing.barrier_snapshots:
            resumed = snap.resume()
            assert canon(resumed) == full
            replayed += 1
    assert replayed > 0, "no renegotiation barrier fired across the seeds"


def test_snapshot_replays_fewer_events():
    """Suffix-only means the snapshot simulates strictly fewer events than
    the full horizon (that's the whole point of resuming at the barrier)."""
    for seed in range(6):
        items = poisson_workload(
            ["small", "medium"], 6, 50.0, seed=seed, iterations=(1, 3))
        rt = fast.MemoryRuntime(
            HW, budget=BUDGET, channels=2, renegotiate=True,
            replan_size_threshold=SIZE_THRESHOLD, capture_snapshots=True)
        rep = rt.run(churn_tenants(fast, items))
        for snap in rt.barrier_snapshots:
            prefix = snap._events  # events already simulated at the barrier
            assert prefix > 0
            resumed = snap.resume()
            # The cumulative count matches the full run (reports agree), so
            # the resume itself executed only the suffix.
            assert resumed.engine["events"] == rep.engine["events"]
            assert resumed.engine["events"] - prefix < rep.engine["events"]
        if rt.barrier_snapshots:
            return
    pytest.fail("no renegotiation barrier fired across the seeds")
