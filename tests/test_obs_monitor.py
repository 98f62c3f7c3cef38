"""repro.obs.monitor: streaming telemetry, SLOs, diffing (Issue 10).

Invariants pinned here:

  1. The quantile sketch answers within its *self-reported* rank-error
     bound on adversarial streams (sorted, reversed, constant,
     heavy-tailed, random) — property-tested with hypothesis —
     and its state is a pure function of the input stream (deterministic
     compaction and merge).
  2. Window machinery handles the boundary cases: empty windows emit
     nothing, a single sample closes correctly, a sample exactly on a
     tumble boundary opens the next window; sliding sums match a brute
     force over the trailing width.
  3. The monitor is a pure observer: with ``MonitoredRecorder`` armed the
     simulated report stays bit-identical to the frozen
     ``runtime/_engine_reference.py`` across the churn, renegotiation and
     contended-mesh shapes, and alert emission is deterministic.
  4. Exported traces carry the alerts track (pid 5) only for monitored
     runs, pass the extended ``tools/check_trace.py`` validation, and
     every alert names a registered SLO.
  5. ``repro.obs.diffing`` classifies all artifact shapes and ranks the
     regression tables with correct signs.
"""

from __future__ import annotations

import importlib.util
import json
import random
from bisect import bisect_left, bisect_right
from pathlib import Path

import pytest

from repro.core.planner import AutoSwapPlanner
from repro.core.simulator import GTX_1080TI
from repro.obs import (
    Alert,
    ExactDistribution,
    HysteresisBand,
    MonitoredRecorder,
    ObsRecorder,
    QuantileSketch,
    SLOMonitor,
    SlidingWindow,
    TumblingWindow,
    chrome_trace,
    diff_runs,
    load_run,
    parse_slo,
    priority_class,
)
from repro.obs.diffing import view_from_payload
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


TEMPLATES = {
    "small": synthetic_train_trace(4),
    "medium": synthetic_train_trace(6),
    "base": synthetic_train_trace(10),
}
PLANS = {name: solve(tr) for name, tr in TEMPLATES.items()}
FLOORS = {n: planned_peak(TEMPLATES[n], PLANS[n][1]) for n in TEMPLATES}
BUDGET = FLOORS["base"] + (FLOORS["small"] + FLOORS["medium"]) // 2

MONITOR_SLOS = (
    "queue_wait.p99<0.001,short=0.02,long=0.08,min=2,name=tight",
    "queue_wait.p99<100,name=guard",
    "link.out_in_wait_ratio>2,low=1.2,window=0.05,name=asym",
)


def canon(report) -> str:
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


def mesh_tenants(mod, devices=4):
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


def churn_run(mod, obs=None, renegotiate=True):
    items = poisson_workload(
        ["small", "medium"], 6, 50.0, seed=11, iterations=(1, 3),
        priorities=(0.5, 1.0, 2.0),
    )
    kw = {"obs": obs} if obs is not None else {}
    rt = mod.MemoryRuntime(
        HW, budget=BUDGET, channels=2, renegotiate=renegotiate,
        replan_size_threshold=SIZE_THRESHOLD, **kw,
    )
    return rt.run(churn_tenants(mod, items))


def mesh_run(mod, obs=None):
    kw = {"obs": obs} if obs is not None else {}
    rt = mod.MemoryRuntime(
        HW, channels=2, link=mod.HostLink.make(HW.link_bw, 2), **kw,
    )
    return rt.run(mesh_tenants(mod, 4))


def _load_tool(name):
    path = Path(__file__).resolve().parents[1] / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ sketch
def assert_within_bound(values, sketch, quantiles=(0.01, 0.5, 0.95, 0.99)):
    ordered = sorted(values)
    bound = sketch.rank_error_bound()
    for q in quantiles:
        got = sketch.quantile(q)
        target = round(q * (len(ordered) - 1))
        lo = bisect_left(ordered, got)
        hi = bisect_right(ordered, got) - 1
        err = 0 if lo <= target <= hi else min(abs(target - lo), abs(target - hi))
        assert err <= bound + 1, (
            f"q={q}: value {got} at rank distance {err} > bound {bound}")


@pytest.mark.parametrize("shape", ["sorted", "reversed", "constant", "heavy", "random"])
def test_sketch_within_reported_bound_adversarial(shape):
    rng = random.Random(7)
    n = 6000
    if shape == "constant":
        values = [2.5] * n
    elif shape == "heavy":
        values = [rng.paretovariate(1.1) for _ in range(n)]
    else:
        values = [rng.random() for _ in range(n)]
        if shape == "sorted":
            values.sort()
        elif shape == "reversed":
            values.sort(reverse=True)
    sk = QuantileSketch(64)
    sk.extend(values)
    assert sk.count == n
    assert sk.rank_error_bound() > 0  # n >> buffer: it really compacted
    assert_within_bound(values, sk)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(min_value=-1e6, max_value=1e6), min_size=1, max_size=400),
       st.integers(min_value=2, max_value=32))
def test_sketch_property_bound_and_determinism(values, buffer_size):
    a = QuantileSketch(buffer_size)
    b = QuantileSketch(buffer_size)
    a.extend(values)
    b.extend(values)
    # Pure function of the stream: identical state, identical answers.
    assert a.levels == b.levels and a.compactions == b.compactions
    assert a.quantile(0.5) == b.quantile(0.5)
    assert a.min == min(values) and a.max == max(values)
    assert_within_bound(values, a, quantiles=(0.0, 0.25, 0.5, 0.9, 1.0))


def test_sketch_exact_mode_is_exact():
    rng = random.Random(3)
    # n = 501 keeps q*(n-1) integral for the probed quantiles, so the
    # sketch's ceiling-rank and ExactDistribution's round-rank coincide.
    values = [rng.gauss(0, 1) for _ in range(501)]
    sk = QuantileSketch(16, exact=True)
    ex = ExactDistribution()
    sk.extend(values)
    ex.extend(values)
    assert sk.rank_error_bound() == 0
    for q in (0.0, 0.1, 0.5, 0.9, 0.99, 1.0):
        assert sk.quantile(q) == ex.quantile(q)


def test_sketch_merge_deterministic_and_bounded():
    rng = random.Random(5)
    xs = [rng.random() for _ in range(1500)]
    parts = [xs[0:500], xs[500:1000], xs[1000:1500]]

    def merged():
        out = QuantileSketch(32)
        for part in parts:  # fixed, documented order
            piece = QuantileSketch(32)
            piece.extend(part)
            out.merge(piece)
        return out

    m1, m2 = merged(), merged()
    assert m1.levels == m2.levels
    assert m1.count == len(xs)
    assert_within_bound(xs, m1)


def test_sketch_empty_and_single():
    sk = QuantileSketch(8)
    with pytest.raises(ValueError):
        sk.quantile(0.5)
    sk.add(42.0)
    assert sk.quantile(0.0) == sk.quantile(0.5) == sk.quantile(1.0) == 42.0
    assert sk.rank_error_bound() == 0


# ----------------------------------------------------------------- windows
def test_tumbling_window_boundaries():
    w = TumblingWindow(1.0)
    assert w.flush() == []          # empty: nothing ever emitted
    w.observe(0.5, 10.0)            # single sample
    assert w.flush() == [(0.0, 1, 10.0, 10.0, 10.0)]

    w = TumblingWindow(1.0)
    w.observe(0.25, 1.0)
    w.observe(1.0, 2.0)             # exactly on the boundary: next window
    w.observe(1.75, 3.0)
    w.observe(5.5, 4.0)             # windows 2..4 are empty: no entries
    closed = w.flush()
    assert closed == [
        (0.0, 1, 1.0, 1.0, 1.0),
        (1.0, 2, 5.0, 2.0, 3.0),
        (5.0, 1, 4.0, 4.0, 4.0),
    ]


def test_sliding_window_matches_brute_force():
    rng = random.Random(9)
    events = []
    t = 0.0
    for _ in range(300):
        t += rng.expovariate(40.0)
        events.append((t, rng.random()))
    win = SlidingWindow(0.5, resolution=10)
    for i, (ti, vi) in enumerate(events):
        win.add(ti, vi)
        got = win.total()
        # Bucket-quantized trailing edge: covers [t - width - bucket, t].
        exact_lo = sum(v for tt, v in events[:i + 1] if tt > ti - 0.5)
        exact_hi = sum(v for tt, v in events[:i + 1] if tt > ti - 0.5 - 0.05 - 1e-12)
        assert exact_lo - 1e-9 <= got <= exact_hi + 1e-9


def test_hysteresis_band_dead_band():
    band = HysteresisBand(1.5, 3.0)
    assert band.update(2.9) is None        # below hi: nothing
    assert band.update(3.0) == "enter"
    assert band.update(2.0) is None        # inside the dead band: holds
    assert band.update(3.5) is None        # already engaged
    assert band.update(1.5) == "exit"
    assert band.update(1.0) is None        # already out


# --------------------------------------------------------------- SLO specs
def test_parse_slo_quantile_and_options():
    s = parse_slo("queue_wait.p99<0.005,prio=1.0,short=0.01,long=0.04,burn=2,min=5")
    assert (s.stream, s.quantile, s.threshold) == ("queue_wait", 0.99, 0.005)
    assert s.cls == "prio1" and s.short_s == 0.01 and s.long_s == 0.04
    assert s.burn == 2.0 and s.min_count == 5
    s = parse_slo("stall.p95<0.01,cause=swap_in_wait")
    assert s.stream == "stall" and s.cause == "swap_in_wait"
    s = parse_slo("link.out_in_wait_ratio>3,low=1.5,window=0.02")
    assert s.stream == "asymmetry" and s.threshold == 3.0 and s.low == 1.5


def test_parse_slo_rejects_malformed():
    for bad in ("queue_wait.p99", "nope.p99<1", "queue_wait.p0<1",
                "queue_wait.p99<0.005,bogus", "link.asym>2"):
        with pytest.raises(ValueError):
            parse_slo(bad)
    with pytest.raises(ValueError):
        SLOMonitor(["queue_wait.p99<1", "queue_wait.p99<2"])  # duplicate name


def test_burn_rate_fires_and_rearms_deterministically():
    def run():
        mon = SLOMonitor(["queue_wait.p99<0.001,short=0.01,long=0.05,min=4,name=s"])
        t = 0.0
        for i in range(120):
            t += 0.0005
            # One violation burst mid-stream, clean elsewhere.
            wait = 0.01 if 20 <= i < 40 else 0.0
            mon.observe_queue_wait(t, "prio1", wait)
        return mon.alerts

    a1, a2 = run(), run()
    assert a1 == a2                      # deterministic emission
    assert len(a1) == 1                  # fires once, hysteresis holds it
    assert a1[0].kind == "burn_rate" and a1[0].slo == "s"
    ts = [a.t for a in a1]
    assert ts == sorted(ts)


def test_guard_slo_never_false_alarms():
    mon = SLOMonitor(["queue_wait.p99<100,name=guard"])
    t = 0.0
    rng = random.Random(1)
    for _ in range(500):
        t += 0.001
        mon.observe_queue_wait(t, "prio1", rng.random())
    assert mon.alerts == []


def test_asymmetry_alerts_at_blackout_boundaries():
    mon = SLOMonitor(["link.out_in_wait_ratio>2,low=1.2,window=0.1,name=asym"])
    t = 0.0
    for _ in range(40):                    # out-dominated traffic
        t += 0.002
        mon.observe_transfer(t, "out", 0.004)
        mon.observe_transfer(t, "in", 0.0005)
    assert mon.alerts == []                # no boundary yet: no evaluation
    mon.on_blackout_boundary(t)
    assert [a.kind for a in mon.alerts] == ["asymmetry_enter"]
    for _ in range(200):                   # traffic balances out
        t += 0.002
        mon.observe_transfer(t, "in", 0.004)
        mon.observe_transfer(t, "out", 0.004)
    mon.on_blackout_boundary(t)
    assert [a.kind for a in mon.alerts] == ["asymmetry_enter", "asymmetry_exit"]
    assert all(a.slo == "asym" for a in mon.alerts)


# ------------------------------------------------------------------ purity
def test_monitor_is_pure_observer_churn_vs_reference():
    rec = MonitoredRecorder(slos=MONITOR_SLOS)
    assert canon(churn_run(fast, obs=rec)) == canon(churn_run(ref))
    assert rec.admissions and rec.monitor.sketches["queue_wait.all"].count > 0


def test_monitor_is_pure_observer_fifo_churn_vs_reference():
    rec = MonitoredRecorder(slos=MONITOR_SLOS)
    got = canon(churn_run(fast, obs=rec, renegotiate=False))
    assert got == canon(churn_run(ref, renegotiate=False))


def test_monitor_is_pure_observer_mesh_vs_reference():
    rec = MonitoredRecorder(slos=MONITOR_SLOS)
    assert canon(mesh_run(fast, obs=rec)) == canon(mesh_run(ref))
    assert rec.monitor.sketches.get("link.wait_in") is not None


def test_monitor_alert_stream_deterministic_across_runs():
    def alerts():
        rec = MonitoredRecorder(slos=MONITOR_SLOS)
        churn_run(fast, obs=rec)
        return [a.as_dict() for a in rec.alerts]

    assert alerts() == alerts()


def test_admissions_tuple_shape_and_priorities():
    rec = MonitoredRecorder(slos=())
    churn_run(fast, obs=rec)
    assert all(len(t) == 4 for t in rec.admissions)  # schedule_check unpacks 4
    assert rec.priorities["base"] == 0.5
    assert set(rec.priorities) == {name for name, *_ in rec.admissions}
    classes = {priority_class(p) for p in rec.priorities.values()}
    sketch_classes = {k.split(".", 1)[1] for k in rec.monitor.sketches
                      if k.startswith("queue_wait.") and k != "queue_wait.all"}
    assert sketch_classes == classes


def test_plain_recorder_still_accepts_priority_hook():
    rec = ObsRecorder()
    churn_run(fast, obs=rec)              # engine now passes priority
    assert rec.priorities and all(len(t) == 4 for t in rec.admissions)


# ----------------------------------------------------------- trace export
def test_trace_alerts_track_and_check_trace(tmp_path):
    rec = MonitoredRecorder(slos=MONITOR_SLOS)
    report = churn_run(fast, obs=rec)
    trace = chrome_trace(rec, report)
    alerts = [e for e in trace["traceEvents"]
              if e.get("pid") == 5 and e.get("ph") == "i"]
    assert alerts, "monitored churn run should raise at least the tight SLO"
    registered = {s["name"] for s in trace["otherData"]["slos"]}
    assert {a["args"]["slo"] for a in alerts} <= registered
    ts = [a["ts"] for a in alerts]
    assert ts == sorted(ts)
    assert "monitor" in trace["otherData"]
    # metrics got the monitor gauges folded in
    assert any(k.startswith("monitor.queue_wait.all.")
               for k in trace["otherData"]["metrics"])

    path = tmp_path / "monitored.trace.json"
    path.write_text(json.dumps(trace))
    check_trace = _load_tool("check_trace")
    assert check_trace.check_trace(str(path)) == []

    # Corrupting an alert's SLO name must be caught.
    for e in trace["traceEvents"]:
        if e.get("pid") == 5 and e.get("ph") == "i":
            e["args"]["slo"] = "never-registered"
            break
    bad = tmp_path / "bad.trace.json"
    bad.write_text(json.dumps(trace))
    errs = check_trace.check_trace(str(bad))
    assert any("unregistered SLO" in e for e in errs)


def test_plain_recorder_trace_has_no_alerts_track(tmp_path):
    rec = ObsRecorder()
    report = churn_run(fast, obs=rec)
    trace = chrome_trace(rec, report)
    assert not any(e.get("pid") == 5 for e in trace["traceEvents"])
    assert "slos" not in trace["otherData"]
    path = tmp_path / "plain.trace.json"
    path.write_text(json.dumps(trace))
    check_trace = _load_tool("check_trace")
    assert check_trace.check_trace(str(path)) == []


# ---------------------------------------------------------------- diffing
def _report_payload(extra_stall=0.0):
    return {
        "makespan_s": 1.0 + extra_stall,
        "tenants": [
            {"name": "a", "status": "completed", "overhead": 0.1,
             "attribution": {"overhead_s": 0.1 + extra_stall,
                             "swap_in_transfer_s": 0.06 + extra_stall,
                             "channel_contention_s": 0.04,
                             "residual_s": 0.0}},
        ],
    }


def test_diff_runs_ledger_signs_and_ranking():
    a = view_from_payload("a", _report_payload(0.0))
    b = view_from_payload("b", _report_payload(0.05))
    d = diff_runs(a, b)
    by_cause = {r["cause"]: r for r in d["ledger_delta"]}
    assert by_cause["swap_in_transfer_s"]["delta"] == pytest.approx(0.05)
    assert by_cause["channel_contention_s"]["delta"] == 0.0
    assert by_cause["overhead_s"]["informational"]
    # top regression table is ranked by |relative| change; tenant lists are
    # not flattened into scalars, so only makespan_s lands here — the
    # per-cause movement is the ledger_delta's job, asserted above.
    rels = [abs(r["rel"]) for r in d["top_regressions"]]
    assert rels == sorted(rels, reverse=True)
    assert d["top_regressions"][0]["metric"] == "makespan_s"
    assert d["top_regressions"][0]["delta"] == pytest.approx(0.05)


def test_load_run_classifies_all_shapes(tmp_path):
    # report
    rp = tmp_path / "report.json"
    rp.write_text(json.dumps(_report_payload()))
    assert load_run(str(rp)).kind == "report"
    # bench
    bp = tmp_path / "BENCH_x.json"
    bp.write_text(json.dumps({"mode": "full", "cell": {"events_per_s": 5.0},
                              "_meta": {"schema_version": 1}}))
    view = load_run(str(bp))
    assert view.kind == "bench" and view.scalars["cell.events_per_s"] == 5.0
    # trace with monitor summary
    rec = MonitoredRecorder(slos=MONITOR_SLOS)
    report = churn_run(fast, obs=rec)
    tp = tmp_path / "t.trace.json"
    tp.write_text(json.dumps(chrome_trace(rec, report)))
    view = load_run(str(tp))
    assert view.kind == "trace" and view.ledger is not None
    assert view.quantiles and "queue_wait.all" in view.quantiles
    # monitor JSONL (last record wins)
    jp = tmp_path / "m.jsonl"
    rec.metrics.append_jsonl(str(jp), {"monitor": rec.finalize()})
    view = load_run(str(jp))
    assert view.kind == "jsonl" and view.quantiles is not None
    # quantile shift between two monitored runs diffs cleanly
    d = diff_runs(load_run(str(tp)), view)
    assert {r["stream"] for r in d["quantile_shift"]} >= {"queue_wait.all"}


def test_diff_quantile_shift_detects_distribution_move(tmp_path):
    def monitored(budget):
        items = poisson_workload(["small", "medium"], 6, 50.0, seed=11,
                                 iterations=(1, 3))
        rec = MonitoredRecorder(slos=())
        rt = fast.MemoryRuntime(HW, budget=budget, channels=2, obs=rec)
        rt.run(churn_tenants(fast, items))
        rec.finalize()
        return {"quantiles": rec.monitor.quantile_summary()}

    loose = view_from_payload("loose", {"slo": monitored(BUDGET * 4)})
    tight = view_from_payload("tight", {"slo": monitored(BUDGET)})
    d = diff_runs(loose, tight)
    shift = {(r["stream"], r["stat"]): r["delta"] for r in d["quantile_shift"]}
    # Queue waits can only get worse when the budget shrinks 4x.
    assert shift[("queue_wait.all", "p99")] >= 0.0


# ------------------------------------------------------------- CLI surface
def test_recorder_for_upgrades_with_slo_args():
    import argparse

    from repro.obs import add_obs_args, recorder_for

    ap = argparse.ArgumentParser()
    add_obs_args(ap)
    args = ap.parse_args(["--slo", "queue_wait.p99<0.005"])
    rec = recorder_for(args)
    assert isinstance(rec, MonitoredRecorder)
    assert rec.slo_specs[0].threshold == 0.005
    args = ap.parse_args([])
    assert recorder_for(args) is None
    args = ap.parse_args(["--trace-out", "/tmp/x.json"])
    rec = recorder_for(args)
    assert isinstance(rec, ObsRecorder) and not isinstance(rec, MonitoredRecorder)


def test_export_monitor_writes_jsonl(tmp_path):
    import argparse

    from repro.obs import add_obs_args, export_monitor, recorder_for

    out = tmp_path / "monitor.jsonl"
    ap = argparse.ArgumentParser()
    add_obs_args(ap)
    args = ap.parse_args(["--slo", "queue_wait.p99<100,name=guard",
                          "--monitor-out", str(out)])
    rec = recorder_for(args)
    churn_run(fast, obs=rec)
    export_monitor(args, rec)
    lines = out.read_text().splitlines()
    assert len(lines) == 1
    record = json.loads(lines[0])
    assert record["monitor"]["slos"][0]["name"] == "guard"
    assert "queue_wait.all" in record["monitor"]["quantiles"]
    assert record["monitor"]["alerts"] == []  # guard must stay silent
    assert any(k.startswith("monitor.") for k in record["metrics"])


def test_alert_dataclass_roundtrip():
    a = Alert(t=1.5, slo="s", kind="burn_rate", value=2.0, threshold=1.0,
              detail={"cls": "prio1"})
    d = a.as_dict()
    assert d["t"] == 1.5 and d["detail"]["cls"] == "prio1"
    assert json.loads(json.dumps(d)) == d
