"""Tests for the repro.dist subsystem: sharded capture, per-device plans,
mesh-wide execution with shared host-link contention.

Everything except the shard_map child test runs on abstract values (no
multi-device runtime needed); the child test reuses the
``tests/distributed_env.py`` skip classification so sandboxes without
multi-device jax skip with a reason instead of failing.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from distributed_env import run_child_or_skip
from repro.core.simulator import GTX_1080TI, assign_times
from repro.core.trace import trace_step_fn
from repro.dist import (
    MeshSpec,
    capture_sharded_trace,
    collective_seconds,
    run_mesh,
    schedules_differ,
    shard_divisor,
    shard_existing_trace,
    solve_sharded,
)
from repro.dist.program import group_key
from repro.plan import PlanCache, PlanKey, dumps_canonical
from repro.plan.passes import (
    PassContext,
    Pipeline,
    PoolPlacement,
    SwapSelection,
    TimingAssign,
    TraceCapture,
)
from repro.runtime import HostLink

HW = GTX_1080TI


def small_step():
    def step(w, x):
        g = jax.grad(lambda w: ((jax.nn.relu(x @ w)) ** 2).sum())(w)
        return w - 0.01 * g

    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    return step, (w, x)


# ------------------------------------------------------------ mesh + divisors
def test_mesh_spec_parse_and_signature():
    m = MeshSpec.parse("data=4,model=2")
    assert m.num_devices == 8
    assert m.signature() == "data4xmodel2"
    assert MeshSpec.make(data=1).signature() == ""
    with pytest.raises(ValueError):
        MeshSpec.parse("nonsense")


def test_shard_divisor_divisibility_guard():
    m = MeshSpec.make(data=4, model=2)
    assert shard_divisor((32, 64), P("data", None), m) == 4
    assert shard_divisor((32, 64), P("data", "model"), m) == 8
    # 30 % 4 != 0: that dim degrades to replicated, the other still divides.
    assert shard_divisor((30, 64), P("data", "model"), m) == 2
    assert shard_divisor((32, 64), P(None, None), m) == 1


# --------------------------------------------------- 1x1 equivalence (pinned)
def test_1x1_capture_events_byte_identical_to_single_device():
    """On a 1x1 mesh repro.dist capture must reproduce trace_step_fn exactly:
    same variables, sizes, lifetimes, accesses, names, op costs."""
    step, args = small_step()
    ref = trace_step_fn(step, *args, arg_names=["w", "x"])
    cap = capture_sharded_trace(
        step, *args, mesh=MeshSpec.make(data=1), hw=HW,
        in_specs=(P(None, None), P("data", None)), arg_names=["w", "x"],
    )
    got = cap.groups["spmd"].trace
    assert got.num_indices == ref.num_indices
    assert len(got.variables) == len(ref.variables)
    for a, b in zip(ref.variables, got.variables):
        assert (a.var, a.size, a.alloc_index, a.free_index, a.accesses,
                a.access_is_write, a.name) == (
            b.var, b.size, b.alloc_index, b.free_index, b.accesses,
            b.access_is_write, b.name)
    assert got.op_costs == ref.op_costs
    assert not cap.groups["spmd"].collectives
    assert cap.plan_topology() == ""


def test_1x1_solved_plan_byte_identical_to_pipeline():
    step, args = small_step()
    key = PlanKey("toy", "train:t", HW.name)
    cap = capture_sharded_trace(step, *args, mesh=MeshSpec.make(data=1),
                                hw=HW, arg_names=["w", "x"])
    limit = int(cap.groups["spmd"].trace.peak_load() * 0.7)
    solved = solve_sharded(cap, HW, base_key=key, limit=limit, size_threshold=1)
    ctx = PassContext(hw=HW, key=key, size_threshold=1)
    single = Pipeline([
        TraceCapture(step_fn=step, example_args=args, arg_names=["w", "x"]),
        TimingAssign(),
        PoolPlacement(),
        SwapSelection(limit=limit),
    ]).run(None, ctx)
    assert dumps_canonical(solved.programs["spmd"]) == dumps_canonical(single)


# --------------------------------------------------------- sharded semantics
def test_sharded_capture_divides_batch_sharded_sizes():
    step, args = small_step()
    m = MeshSpec.make(data=4)
    cap = capture_sharded_trace(
        step, *args, mesh=m, hw=HW,
        in_specs=(P(None, None), P("data", None)), arg_names=["w", "x"],
    )
    by_name = {}
    for v in cap.groups["spmd"].trace.variables:
        by_name.setdefault(v.name, v)
    # x is batch-sharded 4 ways; w replicated.
    assert by_name["x"].size == 32 * 64 * 4 // 4
    assert by_name["w"].size == 64 * 64 * 4
    # Per-device peak strictly below the replicated peak.
    ref = trace_step_fn(step, *args, arg_names=["w", "x"])
    assert cap.groups["spmd"].trace.peak_load() < ref.peak_load()


def test_collective_tagging_from_jaxpr_psum():
    """Explicit lax.psum eqns in the jaxpr are tagged with durations and
    folded into the timing model via op_extra_s."""

    def traced(x):
        return jax.lax.psum((x * 2.0).sum(axis=1), "data")

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    m = MeshSpec.make(data=4)
    cap = capture_sharded_trace(
        traced, x, mesh=m, hw=HW,
        in_specs=(P("data", None),), arg_names=["x"],
    )
    group = cap.groups["spmd"]
    assert group.collectives, "psum eqn not tagged"
    c = group.collectives[0]
    assert c.kind == "all_reduce" and c.seconds > 0.0
    trace = group.trace
    assert trace.op_extra_s and trace.op_extra_s.get(c.index) == pytest.approx(
        c.seconds
    )


def test_collective_tagging_via_patched_primitive(monkeypatch):
    """The eqn-tagging path itself, independent of jax's axis-env rules:
    treat an ordinary primitive as a collective and check it is tagged,
    sized from its per-shard inputs, and folded into op_times."""
    from repro.dist import capture as capmod

    monkeypatch.setitem(capmod.COLLECTIVE_PRIMS, "sin", "all_reduce")

    def step(x):
        return jnp.sin(x).sum()

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    cap = capture_sharded_trace(
        step, x, mesh=MeshSpec.make(data=4), hw=HW,
        in_specs=(P("data", None),), arg_names=["x"],
    )
    group = cap.groups["spmd"]
    sins = [c for c in group.collectives if c.kind == "all_reduce"]
    assert len(sins) == 1
    c = sins[0]
    assert c.nbytes == 32 * 64 * 4 // 4  # per-shard input bytes
    assert c.seconds == pytest.approx(
        collective_seconds("all_reduce", c.nbytes, 4, HW)
    )
    trace = group.trace
    assert trace.op_extra_s.get(c.index) == pytest.approx(c.seconds)
    # assign_times folds the collective into op_times.
    assign_times(trace, HW)
    with_extra = trace.op_times[-1]
    trace.op_extra_s = None
    trace.op_times = None
    assign_times(trace, HW)
    assert with_extra == pytest.approx(trace.op_times[-1] + c.seconds)


def test_scan_xs_slices_keep_their_own_sharding():
    """Replicated stacked weights scanned over layers must NOT inherit the
    batch-sharded carry's divisor: per-trip weight slices stay full-size."""

    def step(ws, x):
        def body(h, w):
            return jax.nn.relu(h @ w), ()

        h, _ = jax.lax.scan(body, x, ws)
        return h.sum()

    ws = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)  # stacked, replicated
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)      # batch-sharded
    cap = capture_sharded_trace(
        step, ws, x, mesh=MeshSpec.make(data=4), hw=HW,
        in_specs=(P(None, None, None), P("data", None)), arg_names=["ws", "x"],
    )
    slices = [v for v in cap.groups["spmd"].trace.variables
              if v.name.startswith("scan_x[")]
    assert slices, "no xs slices captured"
    assert all(v.size == 64 * 64 * 4 for v in slices)  # full layer, undivided


def test_capture_unroll_matches_plan_pipeline_default():
    """1x1 captures share the single-device PlanKey, so the tracer settings
    must agree with plan.passes.TraceCapture or the same cache name would
    hold two different traces."""
    from repro.dist.capture import _CAPTURE_MAX_SCAN_UNROLL

    assert TraceCapture().max_scan_unroll == _CAPTURE_MAX_SCAN_UNROLL


def test_gradient_sync_scoped_to_data_axes():
    """The gradient all-reduce prices only its participating data axis, not
    the whole mesh."""
    from repro.dist import gradient_sync_collective

    shapes = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    specs = {"w": P(None, None)}
    entry = gradient_sync_collective(shapes, specs, MeshSpec.make(data=4, model=2))
    assert entry == ("all_reduce", 64 * 64 * 4, None, 4)
    assert gradient_sync_collective(shapes, specs, MeshSpec.make(model=2)) is None


def test_mesh_blackout_registered_once_per_logical_collective():
    """N SPMD tenants execute the same mesh-wide collective: the link is
    blacked out once per iteration, not once per device."""
    solved = _solved_toy()
    group = solved.capture.groups["spmd"]
    per_iter = sum(c.seconds for c in group.collectives)
    peak = group.trace.peak_load()
    res = run_mesh(solved, HW, budget_per_device=peak, iterations=2)
    assert res.report.link["blackout_s"] == pytest.approx(2 * per_iter)


def test_collective_seconds_cost_model():
    assert collective_seconds("all_reduce", 1 << 20, 1, HW) == 0.0
    ar = collective_seconds("all_reduce", 1 << 20, 4, HW)
    ag = collective_seconds("all_gather", 1 << 20, 4, HW)
    assert ar > ag > 0.0  # all-reduce moves twice the gather volume


def test_synthesized_collectives_positions():
    step, args = small_step()
    m = MeshSpec.make(data=4)
    cap = capture_sharded_trace(
        step, *args, mesh=m, hw=HW, arg_names=["w", "x"],
        extra_collectives=[("all_reduce", 1 << 20),
                           ("all_gather", 1 << 20, 0.5)],
    )
    group = cap.groups["spmd"]
    tail = group.trace.num_indices - 1
    kinds = {c.kind: c for c in group.collectives}
    assert kinds["all_reduce"].index == tail
    assert 0 < kinds["all_gather"].index < tail


# ------------------------------------------------------- plan keys + caching
def test_plan_key_topology_distinguishes_meshes(tmp_path):
    """A plan solved on a 1-device trace is never served to a sharded step
    (and different meshes never alias) in one PlanCache."""
    step, args = small_step()
    key = PlanKey("toy", "train:t", HW.name)
    cache = PlanCache(tmp_path)
    names = set()
    for axes in ({"data": 1}, {"data": 2}, {"data": 4}):
        cap = capture_sharded_trace(
            step, *args, mesh=MeshSpec.make(**axes), hw=HW,
            in_specs=(P(None, None), P("data", None)), arg_names=["w", "x"],
        )
        solved = solve_sharded(cap, HW, base_key=key, cache=cache)
        names.add(solved.programs["spmd"].key.cache_name())
    assert len(names) == 3
    assert len(cache.keys()) == 3
    # Legacy single-device keys are unchanged by the topology field.
    assert PlanKey("a", "s", "h").cache_name() == PlanKey("a", "s", "h", "").cache_name()
    assert PlanKey("a", "s", "h", "data4").cache_name() != PlanKey("a", "s", "h").cache_name()


def test_partition_spec_signature_in_topology():
    """Same mesh, different input PartitionSpecs -> different plan keys."""
    step, args = small_step()
    m = MeshSpec.make(data=4)
    key = PlanKey("toy", "train:t", HW.name)
    caps = [
        capture_sharded_trace(step, *args, mesh=m, hw=HW,
                              in_specs=specs, arg_names=["w", "x"])
        for specs in [(P(None, None), P("data", None)),
                      (P("data", None), P("data", None))]
    ]
    keys = {group_key(key, c, "spmd").cache_name() for c in caps}
    assert len(keys) == 2


def test_sharded_solve_cache_roundtrip(tmp_path):
    step, args = small_step()
    key = PlanKey("toy", "train:t", HW.name)
    cache = PlanCache(tmp_path)
    m = MeshSpec.make(data=4)

    def capture():
        return capture_sharded_trace(
            step, *args, mesh=m, hw=HW,
            in_specs=(P(None, None), P("data", None)), arg_names=["w", "x"],
        )

    cap = capture()
    limit = int(cap.groups["spmd"].trace.peak_load() * 0.7)
    first = solve_sharded(cap, HW, base_key=key, cache=cache,
                          limit=limit, size_threshold=1)
    assert not first.cache_hits["spmd"]
    second = solve_sharded(capture(), HW, base_key=key, cache=cache,
                           limit=limit, size_threshold=1)
    assert second.cache_hits["spmd"]
    assert dumps_canonical(first.programs["spmd"]) == dumps_canonical(
        second.programs["spmd"]
    )


# ------------------------------------------------------------ mesh execution
def _solved_toy(shards: int = 4, with_collectives: bool = True):
    step, args = small_step()
    extra = []
    if with_collectives:
        extra = [("all_reduce", 64 * 64 * 4), ("all_gather", 64 * 64 * 2, 0.4)]
    cap = capture_sharded_trace(
        step, *args, mesh=MeshSpec.make(data=shards), hw=HW,
        in_specs=(P(None, None), P("data", None)), arg_names=["w", "x"],
        extra_collectives=extra,
    )
    return solve_sharded(cap, HW, limit_frac=0.6, size_threshold=1)


def test_run_mesh_per_device_pools_and_fanout():
    solved = _solved_toy()
    peak = solved.capture.groups["spmd"].trace.peak_load()
    res = run_mesh(solved, HW, budget_per_device=peak, iterations=2)
    rep = res.report
    assert len(rep.tenants) == 4
    assert all(t.status == "completed" for t in rep.tenants)
    assert rep.device_peaks is not None and len(rep.device_peaks) == 4
    # SPMD: every device pool sees the identical peak.
    assert len(set(rep.device_peaks.values())) == 1
    # aggregate = sum over per-device pools.
    assert rep.aggregate_peak == sum(rep.device_peaks.values())
    assert rep.overflow_events == 0


def test_shared_link_contention_changes_schedules_and_never_free():
    solved = _solved_toy()
    peak = solved.capture.groups["spmd"].trace.peak_load()
    kw = dict(budget_per_device=peak, iterations=2)
    free = run_mesh(solved, HW, contended=False, **kw)
    shared = run_mesh(solved, HW, contended=True, link_lanes=2, **kw)
    assert schedules_differ(free, shared)
    assert shared.report.link is not None
    assert shared.report.link["transfers"] > 0
    # Contention can only slow tenants down.
    assert shared.makespan_s >= free.makespan_s - 1e-12


def test_contention_aware_not_worse_than_blind():
    solved = _solved_toy()
    peak = solved.capture.groups["spmd"].trace.peak_load()
    kw = dict(budget_per_device=peak, iterations=3, link_lanes=2)
    aware = run_mesh(solved, HW, contended=True, contention_aware=True, **kw)
    blind = run_mesh(solved, HW, contended=True, contention_aware=False, **kw)
    assert aware.mean_overhead() <= blind.mean_overhead() + 1e-9


def test_collective_blackout_blocks_link():
    """A collective blacks the shared link out: transfers scheduled into the
    blackout are shifted past its end."""
    link = HostLink.make(total_bw=1e9, lanes=1)
    link.add_blackout(1.0, 2.0)
    assert link.next_clear(0.0, 0.5) == 0.0       # fits before
    assert link.next_clear(0.9, 0.5) == 2.0       # overlaps -> after
    assert link.next_clear(1.5, 0.1) == 2.0       # inside -> after
    link.add_blackout(2.0, 2.5)
    assert link.next_clear(1.5, 0.1) == 2.5       # chained blackouts


def test_single_device_runtime_unaffected_by_link_default():
    """Without a HostLink the engine is bit-for-bit the legacy runtime even
    for tenants carrying collective tags (clock advances, no blackouts)."""
    from repro.core._solver_reference import reference_simulate_swap_schedule
    from repro.core.autoswap import AutoSwapPlanner
    from repro.runtime import simulate_program, synthetic_train_trace

    tr = synthetic_train_trace(8)
    pl = AutoSwapPlanner(tr, HW, size_threshold=1 << 20)
    limit = int(pl.peak_load * 0.7)
    dec = pl.select(limit, "swdoa")
    ref = reference_simulate_swap_schedule(tr, dec, HW, limit)
    got = simulate_program(tr, dec, HW, limit, channels=2, prefetch="eager")
    for f in ("baseline_s", "duration_s", "peak_resident", "stalls",
              "delayed_mallocs", "tail_spill_s", "out_events", "in_events"):
        assert getattr(got, f) == getattr(ref, f)


def test_shard_existing_trace_rule_route():
    from repro.runtime import synthetic_train_trace

    tr = synthetic_train_trace(6)
    m = MeshSpec.make(data=4)
    cap = shard_existing_trace(
        tr, m, HW,
        divisor_fn=lambda name, size: 4 if name.startswith("act") else 1,
        extra_collectives=[("all_reduce", 1 << 20)],
    )
    got = cap.groups["spmd"].trace
    by_var = {v.var: v for v in tr.variables}
    for v in got.variables:
        orig = by_var[v.var]
        if orig.name.startswith("act") and orig.size % 4 == 0:
            assert v.size == orig.size // 4
        else:
            assert v.size == orig.size
    assert cap.groups["spmd"].collectives


# ------------------------------------------------- multi-device child (skip)
CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.dist import MeshSpec, capture_sharded_trace

mesh = make_mesh((4,), ("data",))

@partial(jax.shard_map, mesh=mesh, in_specs=(P(None, None), P("data", None)),
         out_specs=P(None, None), check_vma=False)
def step(w, x):
    h = jax.nn.relu(x @ w)
    g = jax.lax.psum(h.T @ h, "data")
    return g

w = jnp.zeros((64, 64), jnp.float32)
x = jnp.zeros((32, 64), jnp.float32)
# The partitioned jaxpr: per-shard block shapes inside, psum tagged.
cap = capture_sharded_trace(
    step, w, x, mesh=MeshSpec.from_mesh(mesh), hw=None or __import__(
        "repro.core.simulator", fromlist=["GTX_1080TI"]).GTX_1080TI,
    arg_names=["w", "x"],
)
group = cap.groups["spmd"]
assert group.trace.num_indices > 0
assert any(c.kind == "all_reduce" for c in group.collectives), group.collectives
print("CHILD_OK")
"""


def test_shard_map_partitioned_jaxpr_capture():
    """Walking the jaxpr of a real shard_map step (child process with forced
    host devices) tags its psum; skips where the sandbox can't force
    multi-device XLA — classified by tests/distributed_env.py."""
    run_child_or_skip(CHILD)
