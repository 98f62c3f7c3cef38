"""SmartPool offline-DSA: validity, bounds, baselines, property tests."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.events import Event, EventKind, IterationTrace, VariableInfo, build_trace
from repro.core.baseline_pools import CnMemPool, exact_allocator
from repro.core.smartpool import brute_force_optimal, solve


def make_trace(intervals):
    """intervals: list of (size, alloc, free)."""
    vs = [
        VariableInfo(i, s, a, f, accesses=[a], access_is_write=[True])
        for i, (s, a, f) in enumerate(intervals)
    ]
    end = max(f for _, _, f in intervals)
    return IterationTrace(vs, end)


def assert_valid(trace, plan, alignment=256):
    vs = [v for v in trace.variables if v.size > 0]
    align = lambda x: (x + alignment - 1) // alignment * alignment
    for i in range(len(vs)):
        for j in range(i + 1, len(vs)):
            a, b = vs[i], vs[j]
            if a.overlaps(b):
                a0, a1 = plan.offsets[a.var], plan.offsets[a.var] + align(a.size)
                b0, b1 = plan.offsets[b.var], plan.offsets[b.var] + align(b.size)
                assert a1 <= b0 or b1 <= a0, (a.var, b.var)


def test_disjoint_lifetimes_share_memory():
    tr = make_trace([(1000, 0, 5), (1000, 5, 10), (1000, 10, 15)])
    plan = solve(tr)
    assert plan.footprint == 1024  # all three share one aligned slot
    assert plan.competitive_ratio == 1.0


def test_overlapping_lifetimes_stack():
    tr = make_trace([(1000, 0, 10), (1000, 0, 10), (1000, 0, 10)])
    plan = solve(tr)
    assert plan.footprint == 3 * 1024
    assert_valid(tr, plan)


def test_many_to_one_sharing():
    """A big dead variable's space hosts several small ones (paper §III-C)."""
    tr = make_trace([(10_000, 0, 5)] + [(2_000, 5, 10)] * 4)
    plan = solve(tr)
    assert plan.footprint == 10240  # four 2 KiB vars fit inside the big slot
    assert_valid(tr, plan)


def test_best_fit_vs_first_fit_validity():
    tr = make_trace([(5000, 0, 4), (3000, 2, 8), (1000, 5, 9), (4000, 4, 9), (2500, 1, 3)])
    for method in ("best_fit", "first_fit"):
        plan = solve(tr, method)
        assert_valid(tr, plan)
        assert plan.footprint >= plan.peak_load


def test_footprint_between_peak_and_sum():
    rng = np.random.default_rng(0)
    intervals = [
        (int(rng.integers(100, 10_000)), int(a := rng.integers(0, 50)), int(a + rng.integers(1, 40)))
        for _ in range(60)
    ]
    tr = make_trace(intervals)
    plan = solve(tr)
    assert_valid(tr, plan)
    assert plan.peak_load <= plan.footprint <= sum(((s + 255) // 256) * 256 for s, _, _ in intervals)


def test_matches_brute_force_on_tiny():
    tr = make_trace([(3, 0, 4), (2, 2, 6), (4, 3, 7), (1, 5, 9), (2, 0, 9)])
    plan = solve(tr, alignment=1)
    best = brute_force_optimal(tr, alignment=1)
    assert plan.footprint <= 1.5 * best  # WIC guarantee band for tiny cases


def test_beats_or_ties_cnmem_on_varied_sizes():
    rng = np.random.default_rng(1)
    intervals = []
    t = 0
    for _ in range(100):
        t += int(rng.integers(0, 3))
        intervals.append((int(rng.integers(64, 65536)), t, t + int(rng.integers(1, 60))))
    tr = make_trace(intervals)
    sp = solve(tr)
    cn = CnMemPool().run(tr)
    assert sp.footprint <= cn.footprint * 1.001


def test_exact_allocator_is_peak():
    tr = make_trace([(1000, 0, 5), (2000, 3, 8)])
    st_ = exact_allocator(tr)
    assert st_.footprint == tr.peak_load()
    assert st_.competitive_ratio == 1.0


@settings(max_examples=60, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(1, 100_000),   # size
            st.integers(0, 40),        # alloc
            st.integers(1, 40),        # duration
        ),
        min_size=1,
        max_size=40,
    )
)
def test_property_always_valid_and_bounded(items):
    intervals = [(s, a, a + d) for s, a, d in items]
    tr = make_trace(intervals)
    for method in ("best_fit", "first_fit"):
        plan = solve(tr, method)
        assert_valid(tr, plan)
        assert plan.footprint >= plan.peak_load
        # WIC-style sanity bound: never worse than stacking everything.
        assert plan.footprint <= sum(((s + 255) // 256) * 256 for s, _, _ in intervals)


def test_lookup_table_maps_alloc_index_to_offset():
    tr = make_trace([(1000, 0, 5), (2000, 5, 9)])
    plan = solve(tr)
    for v in tr.variables:
        assert plan.lookup[v.alloc_index] == plan.offsets[v.var]
