"""Solve-time fast path: fast solvers pinned against the frozen references.

The production SmartPool/AutoSwap solvers were rewritten for near-linear
solve time (Issue 3); core/_solver_reference.py keeps verbatim copies of the
originals.  These tests pin:

  * SmartPool placements bit-for-bit, for both fit methods and both query
    engines, on randomized traces;
  * AutoSwap scores (DOA/AOA exactly, WDOA/SWDOA to float tolerance — the
    incremental rescore accumulates O(k*eps) rounding) and selections exactly;
  * the memoized IterationTrace load curve, including invalidation;
  * solve_ms provenance through the pass pipeline and artifacts.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core._solver_reference import ReferenceAutoSwapPlanner, reference_solve
from repro.core.autoswap import AutoSwapPlanner
from repro.core.events import IterationTrace, VariableInfo
from repro.core.simulator import HardwareSpec
from repro.core.smartpool import solve

HW = HardwareSpec("test", peak_flops=1e12, hbm_bw=1e12, link_bw=1e10, efficiency=1.0)


def make_trace(intervals):
    """intervals: list of (size, alloc, free)."""
    vs = [
        VariableInfo(i, s, a, f, accesses=[a], access_is_write=[True])
        for i, (s, a, f) in enumerate(intervals)
    ]
    end = max(f for _, _, f in intervals)
    return IterationTrace(vs, end)


def synth_trace(n_layers=8, act_bytes=8 << 20, weight_bytes=4 << 20):
    """Forward/backward-shaped trace (same shape as tests/test_autoswap.py)."""
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
    tr.op_costs = {i: (1e9, 1e6) for i in range(n_ops)}  # 1 ms per op
    return tr


def assert_plans_identical(ref, fast):
    assert ref.offsets == fast.offsets
    assert ref.footprint == fast.footprint
    assert ref.peak_load == fast.peak_load
    assert ref.lookup == fast.lookup
    assert ref.method == fast.method


# ------------------------------------------------------------- SmartPool pin
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
def test_property_fast_placements_match_reference(items):
    intervals = [(s, a, a + d) for s, a, d in items]
    tr = make_trace(intervals)
    for method in ("best_fit", "first_fit"):
        ref = reference_solve(tr, method)
        for engine in ("event", "bulk", "auto"):
            assert_plans_identical(ref, solve(tr, method, engine=engine))


def test_fast_matches_reference_with_duplicate_allocs_and_weights():
    # many same-alloc variables + whole-iteration weights: stresses both the
    # stab path (same-leaf inserts) and the alloc-window slice.
    intervals = (
        [(4096, 0, 100)] * 3                       # weight-like, full lifetime
        + [(1000 + 13 * i, 5, 5 + i + 1) for i in range(20)]   # same alloc index
        + [(777, 30, 60), (512, 59, 61), (2048, 60, 90)]
    )
    tr = make_trace(intervals)
    for method in ("best_fit", "first_fit"):
        ref = reference_solve(tr, method)
        for engine in ("event", "bulk"):
            assert_plans_identical(ref, solve(tr, method, engine=engine))


def test_fast_matches_reference_zero_and_inverted_lifetimes():
    # Degenerate records (free <= alloc) can appear in malformed device
    # streams; the reference mask is strict on both sides, and the event
    # engine's stab filter must apply alloc_j < free_i, not alloc_j < a_i.
    intervals = [
        (1000, 0, 10), (2000, 0, 10), (500, 2, 8), (266, 3, 3),   # zero-length
        (1536, 4, 9), (266, 5, 1), (700, 1, 0),                   # inverted
        (4096, 0, 12), (128, 6, 7),
    ]
    vs = [VariableInfo(i, s, a, f) for i, (s, a, f) in enumerate(intervals)]
    tr = IterationTrace(vs, 12)
    for method in ("best_fit", "first_fit"):
        ref = reference_solve(tr, method)
        for engine in ("event", "bulk"):
            assert_plans_identical(ref, solve(tr, method, engine=engine))


def test_fast_matches_reference_dense_overlap():
    # everything alive at once: the dense regime the bulk engine targets,
    # and the event engine must still be exact there.
    intervals = [(1024 * (i + 1), 0, 50) for i in range(30)]
    tr = make_trace(intervals)
    for method in ("best_fit", "first_fit"):
        ref = reference_solve(tr, method)
        for engine in ("event", "bulk"):
            assert_plans_identical(ref, solve(tr, method, engine=engine))


def test_unknown_engine_and_method_raise():
    tr = make_trace([(1000, 0, 5)])
    with pytest.raises(ValueError):
        solve(tr, engine="nope")
    for engine in ("event", "bulk", "auto"):
        with pytest.raises(ValueError):
            solve(tr, method="middle_fit", engine=engine)


# -------------------------------------------------------------- AutoSwap pin
def test_swdoa_scores_pinned_against_reference():
    tr = synth_trace(n_layers=10)
    ref = ReferenceAutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    new = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    assert len(ref.candidates) == len(new.candidates)
    for s in ("doa", "aoa"):
        a = [c.scores[s] for c in ref.candidates]
        b = [c.scores[s] for c in new.candidates]
        assert a == b  # identical arithmetic -> exact
    for s in ("wdoa", "swdoa"):
        a = np.array([c.scores[s] for c in ref.candidates])
        b = np.array([c.scores[s] for c in new.candidates])
        assert np.allclose(a, b, rtol=1e-6, atol=1e-12), s


@settings(max_examples=12, deadline=None)
@given(st.integers(2, 12), st.floats(0.45, 0.95))
def test_property_selections_match_reference(n_layers, frac):
    tr = synth_trace(n_layers=n_layers)
    ref = ReferenceAutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    new = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(new.peak_load * frac)
    key = lambda ds: [(d.var, d.size, d.out_after, d.in_before, d.wraps) for d in ds]
    for scorer in ("swdoa", "wdoa", "aoa", "doa"):
        assert key(ref.select(limit, scorer)) == key(new.select(limit, scorer))
    assert ref.load_min() == new.load_min()


def test_weighted_ranking_matches_reference():
    tr = synth_trace(n_layers=6)
    ref = ReferenceAutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    new = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    w = [0.4, 0.1, 0.2, 0.3]
    limit = int(new.peak_load * 0.7)
    key = lambda ds: [(d.var, d.out_after, d.in_before, d.wraps) for d in ds]
    assert key(ref.select(limit, None, w)) == key(new.select(limit, None, w))


def test_max_zero_overhead_reduction_matches_reference():
    tr = synth_trace(n_layers=6)
    ref = ReferenceAutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    new = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    assert ref.max_zero_overhead_reduction(method="swdoa", grid=8) == \
        new.max_zero_overhead_reduction(method="swdoa", grid=8)


def test_select_is_memoized_and_isolated():
    tr = synth_trace()
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(pl.peak_load * 0.7)
    a = pl.select(limit, "swdoa")
    b = pl.select(limit, "swdoa")
    assert a == b and a is not b  # cached value, fresh list per caller
    a.append("sentinel")
    assert pl.select(limit, "swdoa") == b  # caller mutation can't poison cache


# ------------------------------------------------------- load-curve memoizing
def test_load_curve_cached_and_returns_fresh_list():
    tr = make_trace([(1000, 0, 5), (2000, 3, 8)])
    c1 = tr.load_curve()
    arr1 = tr.load_curve_array()
    assert c1 == list(arr1)
    c1[0] = -1  # caller-side mutation (runtime's planned_peak does this)
    assert tr.load_curve()[0] != -1
    assert tr.load_curve_array() is arr1  # memoized


def test_load_curve_invalidated_by_structural_change():
    tr = make_trace([(1000, 0, 5)])
    before = tr.peak_load()
    tr.variables.append(VariableInfo(99, 5000, 0, 5))
    assert tr.peak_load() == before + 5000  # len(variables) guard catches it


def test_load_curve_explicit_invalidation_for_inplace_mutation():
    tr = make_trace([(1000, 0, 5), (2000, 3, 8)])
    assert tr.peak_load() == 3000
    tr.variables[0].size = 11_000  # in-place edit: guard can't see it
    tr.invalidate_cache()
    assert tr.peak_load() == 13_000


# ------------------------------------------------------- solve_ms provenance
def test_passes_record_solve_ms_and_artifact_roundtrip(tmp_path):
    from repro.plan.artifact import PlanCache, dumps_canonical
    from repro.plan.passes import PassContext, Pipeline, PoolPlacement, SwapSelection, TimingAssign
    from repro.plan.program import MemoryProgram, PlanKey

    tr = synth_trace()
    key = PlanKey("synth", "test:solvems", HW.name)
    cache = PlanCache(tmp_path)
    prog = MemoryProgram.from_trace(tr, key=key)
    ctx = PassContext(hw=HW, cache=cache, key=key, size_threshold=1 << 20)
    limit = int(tr.peak_load() * 0.7)
    Pipeline([TimingAssign(), PoolPlacement(("best_fit",)), SwapSelection(limit)]).run(prog, ctx)
    assert "pool:best_fit" in prog.solve_ms
    assert any(k.startswith("swap:swdoa@") for k in prog.solve_ms)
    assert all(v >= 0 for v in prog.solve_ms.values())

    cache.store(prog)
    restored = cache.load(key)
    assert set(restored.solve_ms) == set(prog.solve_ms)
    for k2, v in prog.solve_ms.items():
        assert restored.solve_ms[k2] == pytest.approx(v, abs=1e-3)  # stored rounded
    # Timing is provenance, not plan identity: canonical bytes exclude it.
    assert "solve_ms" not in dumps_canonical(prog)
    assert dumps_canonical(prog) == dumps_canonical(restored)
