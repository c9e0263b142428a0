"""From a profiler trace to busy and idle time, time per device
operation, and what the host was doing in the idle gaps.

`events_from_xplane` reads an .xplane.pb with jax.profiler.ProfileData
into a plain form, {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}; `reduce` works on that form
alone, so it can be checked against a small recorded trace
(testdata/) with no profiler at hand.

Device planes are those named "/device:TPU:<n>". A device is busy while
an event of its operations line ("XLA Ops") runs; module- and
step-level lines span the gaps between operations and are not counted.
The traced window runs from the first operation's start to the last
one's end over all device planes: the tracer's own start-up and
shutdown are not steady state.
"""

from __future__ import annotations

import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
# gaps attributed one by one; the rest go under one name
MAX_GAPS_ATTRIBUTED = 400
TOP = 10


def events_from_xplane(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            lines.append({"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def line_names(path: str) -> dict:
    """Plane -> its lines with event counts: what to look at by hand
    before trusting `events_from_xplane` on a new runtime."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return {p.name: {ln.name: sum(1 for _ in ln.events) for ln in p.lines}
            for p in data.planes}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged: list) -> float:
    return sum(e - s for s, e in merged)


def _subtract(a: list, b: list) -> list:
    """Merged intervals of `a` not covered by merged `b`."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _self_times(evs: list):
    """(name, start, duration, self time), in ns, of every event of one
    line: self time is the duration less that of the events nested in
    it. A `while` op spans the ops of its body; counting both would
    count the body twice. A leaf has self time equal to its duration."""
    stack: list = []    # [name, start, duration, self]
    for name, start, dur in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] + stack[-1][2] <= start:
            yield tuple(stack.pop())
        if stack and start + dur <= stack[-1][1] + stack[-1][2]:
            stack[-1][3] -= dur    # nested; a mere overlap is a sibling
        stack.append([name, start, dur, dur])
    while stack:
        yield tuple(stack.pop())


def _short(name: str) -> str:
    """'%fusion.5 = bf16[8,128]{...} fusion(...)' -> 'fusion.5 bf16[8,128]'."""
    m = re.match(r"^%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:100]


def reduce(trace: dict) -> dict:
    """-> {"window_s", "busy_s" (mean over devices), "busy_s_by_device",
    "device_ops": [[name, s], ...], "idle_gaps": [[what, s], ...],
    "collective_s", "collective_exposed_s" (means over devices),
    "op_events": {name: [count, self seconds]} summed over devices}.
    Time per operation is self time: nested operations are not counted
    in the one that holds them."""
    device_planes = [p for p in trace["planes"]
                     if DEVICE_PLANE.match(p["name"])]
    host_events = [ev for p in trace["planes"]
                   if p["name"].startswith(HOST_PLANE_PREFIX)
                   for ln in p["lines"] for ev in ln["events"]
                   if ev[2] > 0]
    per_device = []
    for p in device_planes:
        evs = [ev for ln in p["lines"] if ln["name"] == OPS_LINE
               for ev in ln["events"] if ev[2] > 0]
        if evs:
            per_device.append((p["name"], evs))
    if not per_device:
        return {"window_s": 0.0, "busy_s": 0.0, "busy_s_by_device": {},
                "device_ops": [], "idle_gaps": [], "collective_s": 0.0,
                "collective_exposed_s": 0.0, "op_events": {}}
    t0 = min(ev[1] for _, evs in per_device for ev in evs)
    t1 = max(ev[1] + ev[2] for _, evs in per_device for ev in evs)

    busy_by_device = {}
    op_time: dict = {}
    coll = coll_exposed = 0.0
    first_gaps: list = []
    for i, (name, evs) in enumerate(per_device):
        merged = _union([[ev[1], ev[1] + ev[2]] for ev in evs])
        busy_by_device[name] = _length(merged) / 1e9
        c_leaf, o_leaf = [], []
        for name_, start, dur, self_ns in _self_times(evs):
            c = op_time.setdefault(_short(name_), [0, 0.0])
            c[0] += 1
            c[1] += self_ns / 1e9
            if self_ns == dur:   # a leaf: nothing nested in it
                (c_leaf if COLLECTIVE.match(name_) else o_leaf).append(
                    [start, start + dur])
        c_int, o_int = _union(c_leaf), _union(o_leaf)
        coll += _length(c_int) / 1e9
        coll_exposed += _length(_subtract(c_int, o_int)) / 1e9
        if i == 0:
            first_gaps = _subtract([[t0, t1]], merged)
    n = len(per_device)

    # what the host was doing in the idle gaps of the first device
    gaps = sorted(first_gaps, key=lambda g: g[0] - g[1])
    what: dict = {}
    h_start = np.array([ev[1] for ev in host_events])
    h_dur = np.array([ev[2] for ev in host_events])
    for s, e in gaps[:MAX_GAPS_ATTRIBUTED]:
        label = "no_host_event"
        if len(host_events):
            over = np.minimum(e, h_start + h_dur) - np.maximum(s, h_start)
            if over.max() > 0:
                # most of the gap; among equals the innermost (shortest)
                most = np.flatnonzero(over >= over.max() - 1.0)
                label = host_events[most[np.argmin(h_dur[most])]][0]
        what[label] = what.get(label, 0.0) + (e - s) / 1e9
    rest = sum(e - s for s, e in gaps[MAX_GAPS_ATTRIBUTED:]) / 1e9
    if rest:
        what["gaps_too_short_to_attribute"] = rest

    def top(d):
        return [[k[:120], v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": sum(busy_by_device.values()) / n,
            "busy_s_by_device": busy_by_device,
            "device_ops": top({k: v[1] / n for k, v in op_time.items()}),
            "idle_gaps": top(what),
            "collective_s": coll / n,
            "collective_exposed_s": coll_exposed / n,
            "op_events": op_time}


def cut(trace: dict, events_per_line: int) -> dict:
    """The plain form cut to the first events of every line: small
    enough to keep as a recorded trace."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": ln["events"][:events_per_line]}
            for ln in p["lines"]]} for p in trace["planes"]]}


def reduce_dir(trace_dir: str) -> dict:
    """The reduction of the newest trace under `trace_dir`, with what a
    reader of a new runtime wants beside it: the lines of every plane,
    the file's size, and the plain form cut to 300 events a line, small
    enough to keep as a recorded trace."""
    import glob
    import os

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {"error": "the profiler wrote no .xplane.pb"}
    path = max(paths, key=os.path.getmtime)
    events = events_from_xplane(path)
    return {**reduce(events), "lines": line_names(path),
            "xplane_bytes": os.path.getsize(path),
            "recorded": cut(events, 300)}
