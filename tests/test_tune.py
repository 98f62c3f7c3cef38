"""repro.tune — ledger-guided runtime tuning (Issue 8).

Pins the three tuners and their engine plumbing:

  * ``LedgerVictimPolicy`` candidate probes are isolated by construction —
    two simultaneous waiters at one decision point can never leak staged
    reservations between sibling probes (the double-counting regression),
    probing never mutates the live engine, and defaults stay bit-identical
    to the frozen reference;
  * ``max_snapshots`` bounds the barrier-snapshot ring without perturbing
    the run, and the surviving snapshots still resume byte-identically;
  * ``tuned_shares`` coordinate descent is monotone, conserves the budget,
    and respects peak caps; ``colocate_programs(budget_split="tuned")``
    is never worse than proportional on SLO-weighted stall;
  * directional ``HostLink`` lane carving: the split heuristic, the lane
    partition itself, and the gated report keys.
"""

from __future__ import annotations

import json

import pytest

from repro.core.autoswap import AutoSwapPlanner
from repro.core.simulator import HardwareSpec
from repro.plan import MemoryProgram
from repro.runtime import (
    FloorGreedyVictim,
    HostLink,
    MemoryRuntime,
    Tenant,
    colocate_programs,
    planned_peak,
    simulated_report_dict,
    synthetic_train_trace,
)
from repro.runtime import _engine_reference as ref
from repro.tune import (
    LedgerVictimPolicy,
    binding_constraint,
    lane_split_from_waits,
    slo_weighted_stall,
    tuned_shares,
)

HW = HardwareSpec("test", peak_flops=1e12, hbm_bw=1e12, link_bw=1e10, efficiency=1.0)
MB = 1 << 20
ST = 1 << 20


def solved_tenant(name, layers=8, frac=0.7, **kw):
    tr = synthetic_train_trace(layers)
    pl = AutoSwapPlanner(tr, HW, size_threshold=ST)
    limit = int(pl.peak_load * frac)
    return Tenant(name, tr, pl.select(limit, "swdoa"), limit=limit, **kw)


def canon(report) -> str:
    return json.dumps(simulated_report_dict(report), sort_keys=True)


def churn_tenants():
    """One long-running low-priority victim + a newcomer that doesn't fit."""
    a = solved_tenant("A", layers=12, frac=0.8, iterations=6, priority=0.5)
    b = solved_tenant("B", layers=6, frac=0.7, iterations=2, arrival_t=0.005)
    budget = planned_peak(a.trace, a.decisions) + \
        planned_peak(b.trace, b.decisions) // 2
    return [a, b], budget


def two_victim_tenants():
    """Two shrinkable victims + two simultaneous waiters — the shape where
    the policy probes candidates across victims at one decision point."""
    lo = solved_tenant("lo", layers=12, frac=0.8, iterations=6, priority=0.5)
    hi = solved_tenant("hi", layers=10, frac=0.8, iterations=6, priority=1.0)
    n1 = solved_tenant("n1", layers=6, frac=0.7, iterations=1,
                       arrival_t=0.005, priority=2.0)
    n2 = solved_tenant("n2", layers=4, frac=0.7, iterations=1,
                       arrival_t=0.005, priority=2.0)
    floors = {t.name: planned_peak(t.trace, t.decisions)
              for t in (lo, hi, n1, n2)}
    budget = floors["lo"] + floors["hi"] + floors["n1"] // 2
    return [lo, hi, n1, n2], budget


def run(tenants, budget, policy=None, **kw):
    rt = MemoryRuntime(HW, budget=budget, channels=2, renegotiate=True,
                       replan_size_threshold=ST, victim_policy=policy, **kw)
    rt.report = rt.run(tenants)
    return rt


# ----------------------------------------------------------- default identity
def test_default_and_explicit_greedy_bit_identical_to_reference():
    """victim_policy=None and an explicit FloorGreedyVictim both reproduce
    the frozen reference engine byte for byte."""
    want = None
    for policy in (None, FloorGreedyVictim()):
        tenants, budget = churn_tenants()
        got = canon(run(tenants, budget, policy).report)
        if want is None:
            rrt = ref.MemoryRuntime(HW, budget=budget, channels=2,
                                    renegotiate=True,
                                    replan_size_threshold=ST)
            want = canon(rrt.run(churn_tenants()[0]))
        assert got == want


# ------------------------------------------------------------ probe isolation
class _RecordingPolicy(LedgerVictimPolicy):
    """Records each candidate's score and watches the live engine for
    probe-time mutations."""

    def __init__(self, reverse=False, **kw):
        super().__init__(**kw)
        self.reverse = reverse
        self.first_scores: dict | None = None

    def candidates(self, engine, head, needed, victims):
        cands = super().candidates(engine, head, needed, victims)
        return list(reversed(cands)) if self.reverse else cands

    def choose(self, engine, head, needed, victims):
        before_promised = dict(engine._promised)
        before_pending = {r.name: r.replan_pending for r in engine._running}
        scores = {}
        for cand in self.candidates(engine, head, needed, victims):
            score, _ = self.probe(engine, cand)
            scores[(cand[0].name, cand[1])] = score
            # Probing must never touch the live engine's staged state.
            assert engine._promised == before_promised
            assert {r.name: r.replan_pending
                    for r in engine._running} == before_pending
        if self.first_scores is None:
            self.first_scores = scores
        return super().choose(engine, head, needed, victims)


def test_sibling_probes_never_observe_each_other():
    """Two simultaneous waiters: probing candidate A then B must score B
    exactly as probing B then A — a probe that leaked its staged
    reservation into a sibling (the double-counting bug) would shift every
    later candidate's simulated future."""
    fwd = _RecordingPolicy(reverse=False)
    rev = _RecordingPolicy(reverse=True)
    reports = []
    for pol in (fwd, rev):
        tenants, budget = two_victim_tenants()
        reports.append(run(tenants, budget, pol).report)
    assert fwd.first_scores, "no candidates were probed"
    assert len(fwd.first_scores) >= 2, "need >= 2 candidates to detect leaks"
    assert fwd.first_scores == rev.first_scores
    # Same scores -> same argmin -> identical staged decision and run.
    assert canon(reports[0]) == canon(reports[1])
    for rep in reports:
        assert rep.overflow_events == 0
        assert all(t.status == "completed" for t in rep.tenants)


def test_ledger_policy_counts_and_decision_log():
    tenants, budget = two_victim_tenants()
    pol = LedgerVictimPolicy()
    rep = run(tenants, budget, pol).report
    assert pol.staged == rep.renegotiations + rep.renegotiations_cancelled
    assert pol.probes >= pol.staged
    assert len(pol.decision_log) == pol.staged
    for entry in pol.decision_log:
        assert entry["candidates"] >= 1
        assert entry["binding_constraint"] != ""
        assert entry["score"] < float("inf")


def test_probes_do_not_pollute_observer():
    """The live ObsRecorder must see the run's own events only — never the
    phantom ops/transfers/renegotiations of candidate probes."""
    from repro.obs import ObsRecorder

    obs_pol, obs_greedy = ObsRecorder(), ObsRecorder()
    tenants, budget = churn_tenants()
    pol = LedgerVictimPolicy()
    rep_pol = run(tenants, budget, pol, obs=obs_pol).report
    tenants, budget = churn_tenants()
    rep_greedy = run(tenants, budget, None, obs=obs_greedy).report
    assert pol.probes > 0
    # Op/transfer streams match the unprobed run's volume exactly (the two
    # runs stage the same victim here, so the horizons are identical).
    assert canon(rep_pol) == canon(rep_greedy)
    assert len(obs_pol.ops) == len(obs_greedy.ops)
    assert len(obs_pol.transfers) == len(obs_greedy.transfers)
    staged_events = [e for e in obs_pol.renegotiations if e[0] == "staged"]
    assert len(staged_events) == pol.staged


# ------------------------------------------------------------- snapshot ring
def staggered_tenants():
    """Two newcomers far enough apart that each forces its own applied
    barrier — a two-snapshot shape for the ring-buffer test."""
    lo = solved_tenant("lo", layers=12, frac=0.8, iterations=6, priority=0.5)
    hi = solved_tenant("hi", layers=10, frac=0.8, iterations=6, priority=1.0)
    n1 = solved_tenant("n1", layers=6, frac=0.7, iterations=2,
                       arrival_t=0.005, priority=2.0)
    n2 = solved_tenant("n2", layers=4, frac=0.7, iterations=2,
                       arrival_t=0.05, priority=2.0)
    floors = {t.name: planned_peak(t.trace, t.decisions)
              for t in (lo, hi, n1, n2)}
    budget = floors["lo"] + floors["hi"] + floors["n1"] // 2
    return [lo, hi, n1, n2], budget


def test_max_snapshots_ring_buffer():
    """The ring keeps the most recent N snapshots, doesn't perturb the run,
    and the survivors still resume byte-identically."""
    tenants, budget = staggered_tenants()
    uncapped = run(tenants, budget, None, capture_snapshots=True)
    full = canon(uncapped.report)
    total = len(uncapped.barrier_snapshots)
    assert total >= 2, "shape must capture at least two barriers"
    tenants, budget = staggered_tenants()
    capped = run(tenants, budget, None, capture_snapshots=True,
                 max_snapshots=1)
    assert canon(capped.report) == full
    assert len(capped.barrier_snapshots) == 1
    # The survivor is the most recent barrier (largest simulated prefix).
    assert capped.barrier_snapshots[0]._events == \
        uncapped.barrier_snapshots[-1]._events
    assert canon(capped.barrier_snapshots[0].resume()) == full


# ---------------------------------------------------------------- objective
def test_slo_weighted_stall_and_binding_constraint():
    tenants, budget = churn_tenants()
    rep = run(tenants, budget, None).report
    stall = slo_weighted_stall(rep)
    want = sum(t.priority * (max(0.0, t.duration_s - t.baseline_s)
                             + t.queue_wait_s) for t in rep.tenants)
    assert stall == pytest.approx(want)
    assert binding_constraint(rep.attribution) in (
        "transfer", "channel_contention", "blackout", "barrier", "residual")
    assert binding_constraint(None) == "none"
    assert binding_constraint({"overhead_s": 1.0, "queue_wait_s": 2.0}) == "none"
    assert binding_constraint({"swap_in_transfer_s": 1.0,
                               "channel_contention_s": 0.2}) == "transfer"
    assert binding_constraint({"link_blackout_s": 3.0,
                               "swap_in_transfer_s": 1.0}) == "blackout"


def test_slo_weighted_stall_infeasible():
    class T:
        status = "unschedulable"
        priority = duration_s = baseline_s = queue_wait_s = 1.0

    class R:
        overflow_events = 0
        tenants = [T()]

    assert slo_weighted_stall(R()) == float("inf")
    R.tenants, R.overflow_events = [], 3
    assert slo_weighted_stall(R()) == float("inf")


# ------------------------------------------------------------- budget tuner
def test_tuned_shares_descends_and_conserves():
    peaks = {"a": 100 * MB, "b": 100 * MB}
    budget = 120 * MB
    target = 90 * MB

    def evaluate(shares):
        return abs(shares["a"] - target) / MB

    res = tuned_shares(peaks, budget, evaluate, min_delta=MB, max_evals=40)
    assert res.tuned_stall <= res.initial_stall
    assert res.improved
    assert sum(res.shares.values()) == budget
    assert all(0 <= res.shares[n] <= peaks[n] for n in peaks)
    assert abs(res.shares["a"] - target) <= 2 * MB
    assert res.evals <= 40 and res.moves
    d = res.as_dict()
    assert d["tuned_stall_s"] == res.tuned_stall
    assert d["initial_shares"] == res.initial_shares


def test_tuned_shares_keeps_start_when_nothing_helps():
    peaks = {"a": 64 * MB, "b": 64 * MB}

    def evaluate(shares):
        return 1.0  # flat objective: no strict improvement anywhere

    res = tuned_shares(peaks, 96 * MB, evaluate, min_delta=MB, max_evals=40)
    assert res.shares == res.initial_shares
    assert res.tuned_stall == res.initial_stall == 1.0
    assert not res.moves


def test_colocate_tuned_split_never_worse():
    progs = {
        "big": MemoryProgram.from_trace(synthetic_train_trace(12)),
        "small": MemoryProgram.from_trace(synthetic_train_trace(4)),
    }
    kw = dict(hw=HW, budget_frac=0.7, channels=2, size_threshold=ST,
              iterations=2, priorities={"big": 2.0, "small": 0.5})
    prop = colocate_programs(progs, **kw)
    tuned = colocate_programs(progs, budget_split="tuned", **kw)
    assert prop.budget_split == "proportional" and prop.split_tuning is None
    assert tuned.budget_split == "tuned" and tuned.split_tuning is not None
    assert sum(tuned.shares.values()) == tuned.budget
    assert tuned.split_tuning["tuned_stall_s"] <= \
        tuned.split_tuning["initial_stall_s"]
    assert slo_weighted_stall(tuned.report) <= \
        slo_weighted_stall(prop.report) + 1e-12
    assert all(t.status == "completed" for t in tuned.report.tenants)
    with pytest.raises(ValueError, match="budget_split"):
        colocate_programs(progs, budget_split="bogus", **kw)


# ------------------------------------------------------------------- lanes
def test_lane_split_from_waits():
    assert lane_split_from_waits(1.0, 1.0, 1) is None       # nothing to carve
    assert lane_split_from_waits(0.0, 0.0, 4) is None       # no evidence
    assert lane_split_from_waits(1.0, 1.0, 4) == 2          # symmetric demand
    assert lane_split_from_waits(3.0, 1.0, 4) == 1          # in-heavy: 1 out
    assert lane_split_from_waits(0.0, 5.0, 4) == 3          # out-heavy, clamped
    assert lane_split_from_waits(5.0, 0.0, 4) == 1          # in keeps >= 1 out
    # Byte fallback when the probe saw no queueing at all.
    assert lane_split_from_waits(0.0, 0.0, 4, bytes_in=3, bytes_out=1) == 1
    assert lane_split_from_waits(0.0, 0.0, 4, bytes_in=0, bytes_out=0) is None


def test_hostlink_directional_partition():
    link = HostLink.make(1e10, 4, out_lanes=1)
    assert link.out_lane_ids == (0,)
    assert link.in_lane_ids == (1, 2, 3)
    assert list(link.lane_ids("out")) == [0]
    assert list(link.lane_ids("in")) == [1, 2, 3]
    shared = HostLink.make(1e10, 4)
    assert shared.out_lane_ids is None
    assert list(shared.lane_ids("in")) == list(range(4))
    # out_lanes is clamped so each direction keeps at least one lane.
    assert HostLink.make(1e10, 2, out_lanes=5).out_lane_ids == (0,)
    assert HostLink.make(1e10, 1, out_lanes=1).out_lane_ids is None


def mesh_pair(mod=None):
    ts = []
    for i, layers in enumerate((8, 8)):
        t = solved_tenant(f"shard{i}", layers=layers, frac=0.6, iterations=3)
        t.device = f"d{i}"
        ts.append(t)
    return ts


def test_directional_link_report_keys_gated():
    """Directional runs report the carve + per-direction counters; default
    shared-pool runs keep the exact legacy link dict (reference identity)."""
    rt = MemoryRuntime(HW, channels=2, link=HostLink.make(1e9, 4))
    rep = rt.run(mesh_pair())
    assert set(rep.link) == {"total_bw", "lanes", "lane_bw", "bytes_moved",
                             "transfers", "blackout_s"}
    assert rt.link.bytes_in + rt.link.bytes_out == rt.link.bytes_moved
    rt2 = MemoryRuntime(HW, channels=2, link=HostLink.make(1e9, 4, out_lanes=2))
    rep2 = rt2.run(mesh_pair())
    assert rep2.link["out_lanes"] == 2 and rep2.link["in_lanes"] == 2
    assert rep2.link["bytes_in"] + rep2.link["bytes_out"] == \
        rep2.link["bytes_moved"]
    assert rep2.link["wait_in_s"] >= 0.0 and rep2.link["wait_out_s"] >= 0.0


def test_run_mesh_directional_probe_and_carve():
    from repro.dist import run_mesh
    from test_dist import _solved_toy

    solved = _solved_toy()
    peak = solved.capture.groups["spmd"].trace.peak_load()
    kw = dict(budget_per_device=peak, iterations=2, link_lanes=4)
    static = run_mesh(solved, HW, **kw)
    directional = run_mesh(solved, HW, lane_split="directional", **kw)
    assert static.lane_split == "static" and static.lane_info is None
    assert directional.lane_split == "directional"
    info = directional.lane_info
    assert info is not None and info["lanes"] == 4
    if info["out_lanes"] is not None:
        assert 1 <= info["out_lanes"] <= 3
        assert directional.report.link["out_lanes"] == info["out_lanes"]
    with pytest.raises(ValueError, match="lane_split"):
        run_mesh(solved, HW, lane_split="bogus", **kw)
