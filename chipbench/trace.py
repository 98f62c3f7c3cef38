"""From a profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three lists of ``[name, start_ns, duration_ns]``: the operations that ran on
each device (the "XLA Ops" line of every ``/device:TPU:n`` plane), the
compiled programs (its "XLA Modules" line), and the harness's own host spans
(``jax.profiler.TraceAnnotation``) from the host plane. ``reduce`` works on
those lists alone, so it is tested on a small recorded trace without a chip.

Busy time is the union of the intervals in which an operation ran, per
device, averaged over the devices; the idle share is one minus busy time
over the traced window. An op's time in the breakdown is its self time:
the ops of a loop's body are nested inside the loop's own event. Idle gaps are the spaces between busy intervals,
each named by the innermost host span that covers its midpoint.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def extract(logdir: str, host_spans: set[str]) -> dict:
    """Device ops, modules and the named host spans of the newest trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    out = {"ops": defaultdict(list), "modules": defaultdict(list), "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = plane.name[len(DEVICE_PREFIX):]
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key][dev].extend(
                        [e.name, e.start_ns, e.duration_ns] for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name in host_spans)
    return {"ops": dict(out["ops"]), "modules": dict(out["modules"]), "host": out["host"]}


def self_times(intervals) -> dict:
    """Time of each op name less the ops nested inside it (a loop's body ops
    run inside the loop's own event), summed by name."""
    out = defaultdict(float)
    stack = []  # [end, name, self time] of the events that are open
    for s, e, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][0] <= s:
            _, n, t = stack.pop()
            out[n] += t
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for _, n, t in stack:
        out[n] += t
    return out


def merged(intervals):
    """Union of [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(trace: dict, span: str = "window"):
    """(start_ns, end_ns) of the host span that brackets the traced window."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == span]
    if not spans:
        raise ValueError(f"no {span!r} span in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s, window_s, idle share and the breakdown of a traced window."""
    w0, w1 = window_of(trace)
    window_ns = w1 - w0
    devices = sorted(trace["ops"])
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy_ns, gaps = 0.0, []
    op_time = defaultdict(float)
    for dev in devices:
        ivs = [(max(s, w0), min(s + d, w1), name) for name, s, d in trace["ops"][dev]]
        ivs = [iv for iv in ivs if iv[1] > iv[0]]
        for name, t in self_times(ivs).items():
            op_time[name] += t / len(devices)
        busy = merged((s, e) for s, e, _ in ivs)
        busy_ns += sum(e - s for s, e in busy) / len(devices)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    named = defaultdict(float)
    for s, e in gaps:
        named[_host_span_at(trace["host"], (s + e) / 2)] += (e - s) / len(devices)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": window_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "device_ops": [[n[:120], t * 1e-9] for n, t in ops],
        "idle_gaps": [[_host_span_at(trace["host"], (s + e) / 2), (e - s) * 1e-9]
                      for s, e in longest],
        "idle_by_span": {k: v * 1e-9 for k, v in named.items()},
        "module_s": module_times(trace),
    }


def _host_span_at(host, t) -> str:
    """The innermost harness span (other than the window) that covers t."""
    best = None
    for name, s, d in host:
        if name != "window" and s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside any span"


def module_times(trace: dict) -> dict:
    """Seconds of each run of each compiled program inside the traced window,
    on the first device, by program name."""
    w0, w1 = window_of(trace)
    out = defaultdict(list)
    if trace["modules"]:
        for n, s, d in trace["modules"][sorted(trace["modules"])[0]]:
            if s >= w0 and s + d <= w1:
                out[n].append(d * 1e-9)
    return dict(out)
