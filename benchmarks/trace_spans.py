"""From a profiler trace to the program's own terms: device time by
the named scopes the program wrote into its operations, and every idle
gap's owner among the program's host spans.

benchmarks/trace_reduce.py (not changed by this file) answers "busy or
idle, and which runtime call covered the gap". This file reads what the
program itself says:

  * `jax.named_scope` names (models/llama.py, serve/llm.py's step,
    parallel/spmd.py, ops/pallas/flash_attention.py) arrive in each
    device operation's name path, a stat of the event's metadata
    record (PATH_STAT). An operation belongs to the phase (`prefill`,
    `decode`, `loss`, `optimizer`) and to the innermost part (`attn`,
    `mlp`, `flash_fwd`, ...) found in its path; `rematted_computation`
    in the path marks a recomputed forward. An operation with none of
    these is `unscoped`, summed and listed, never dropped.
  * `rayt.*` host spans (`jax.profiler.TraceAnnotation`, opened by the
    serve engine and the train StepRecorder) arrive in the `/host:`
    planes of the same file, on the same clock, with their keyword
    arguments as stats. Each idle gap of the first device is split
    among the spans that cover it, innermost first; what no span covers
    is `rayt.engine.between_spans` when it lies between two engine
    spans at most HANDOFF_MAX_NS apart (the event loop handing the next
    unit of work to an executor thread), else `unowned`.

`events_from_xplane` turns an .xplane.pb into a plain form,
{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns, stats], ...]}]}]} with `stats` a dict (the name path under
"path" for a device operation, the span's own fields for a host span),
and `reduce` works on that form alone, so a hand-made trace and a cut
recorded one (testdata/) check it with no profiler at hand. A trace with
no device plane (the CPU rehearsal) reduces to None.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import struct

from benchmarks.trace_reduce import (DEVICE_PLANE, HOST_PLANE_PREFIX,
                                     OPS_LINE, _length, _self_times,
                                     _short, _subtract, _union)

SPAN_PREFIX = "rayt."
ENGINE_PREFIX = "rayt.engine."
BETWEEN = "rayt.engine.between_spans"
UNOWNED = "unowned"
UNSCOPED = "unscoped"
PHASES = ("prefill", "decode", "loss", "optimizer")
PARTS = ("embed", "attn_qkv", "kv_update", "attn", "attn_out", "mlp",
         "lm_head", "sample", "ce", "lora", "flash_fwd", "flash_bwd_dq",
         "flash_bwd_dkv")
RECOMPUTE = "rematted_computation"
# the stat of a device operation's event metadata that carries its name
# path on this runtime (jax 0.9.0, TPU v5e; looked at by hand, PERF.md)
PATH_STAT = "tf_op"
# two engine spans further apart than this are not a hand-off: the engine
# was waiting for work, and the gap is nobody's
HANDOFF_MAX_NS = 5e6
TOP = 10


# ------------------------------------------------------------------------
# The .xplane.pb, read as protobuf wire format. jax.profiler.ProfileData
# gives an event's own stats but not those of its metadata record, and
# the name path of a device operation sits there (XEventMetadata.stats,
# "tf_op"). The messages are few and flat (tsl/profiler/protobuf/
# xplane.proto: XSpace 1 planes; XPlane 2 name, 3 lines, 4 event_metadata,
# 5 stat_metadata; XLine 2 name, 3 timestamp_ns, 4 events; XEvent 1
# metadata_id, 2 offset_ps, 3 duration_ps, 4 stats; XStat 1 metadata_id, 2
# double, 3 uint64, 4 int64, 5 str, 6 bytes, 7 ref; XEventMetadata 2 name,
# 5 stats; XStatMetadata 2 name), so this reads them with no generated code.
# ------------------------------------------------------------------------
def _fields(buf, pos: int, end: int):
    """(field number, wire type, value) of one message's fields; a
    length-delimited value is its (start, end) in `buf`."""
    while pos < end:
        tag = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            tag |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = tag & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            val = (pos, pos + n)
            pos += n
        elif wire == 1:
            val = (pos, pos + 8)
            pos += 8
        elif wire == 5:
            val = (pos, pos + 4)
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield tag >> 3, wire, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span) -> tuple:
    key, value = 0, (span[1], span[1])
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(buf, span, stat_names: dict) -> tuple:
    name, value = "", None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", bytes(buf[v[0]:v[1]]))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane(buf, span) -> tuple:
    name, lines, event_meta, stat_names = "", [], [], {}
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            event_meta.append(v)
        elif f == 5:
            key, value = _map_entry(buf, v)
            for g, _, w in _fields(buf, *value):
                if g == 2:
                    stat_names[key] = _text(buf, w)
    return name, lines, event_meta, stat_names


def events_from_xplane(path: str) -> dict:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for f, _, v in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, line_spans, meta_spans, stat_names = _plane(buf, v)
        device = bool(DEVICE_PLANE.match(name))
        if not device and not name.startswith(HOST_PLANE_PREFIX):
            continue
        meta = {}       # id -> (event name, its metadata's stats' spans)
        for span in meta_spans:
            key, value = _map_entry(buf, span)
            ev_name, stats = "", []
            for g, _, w in _fields(buf, *value):
                if g == 2:
                    ev_name = _text(buf, w)
                elif g == 5:
                    stats.append(w)
            if device:
                own = dict(_stat(buf, st, stat_names) for st in stats)
                meta[key] = (ev_name, {"path": own.get(PATH_STAT, "")})
            elif ev_name.startswith(SPAN_PREFIX):
                meta[key] = (ev_name, None)
        lines = []
        for span in line_spans:
            line_name, t_line, event_spans = "", 0, []
            for g, _, w in _fields(buf, *span):
                if g == 2:
                    line_name = _text(buf, w)
                elif g == 3:
                    t_line = w
                elif g == 4:
                    event_spans.append(w)
            if device and line_name != OPS_LINE:
                continue
            events = []
            for es in event_spans:
                mid = off = dur = 0
                stats = []
                for g, _, w in _fields(buf, *es):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        off = w
                    elif g == 3:
                        dur = w
                    elif g == 4 and not device:
                        stats.append(w)
                if mid not in meta:
                    continue
                ev_name, ev_stats = meta[mid]
                if ev_stats is None:
                    ev_stats = dict(_stat(buf, st, stat_names)
                                    for st in stats)
                events.append([ev_name, t_line + off / 1e3, dur / 1e3,
                               ev_stats])
            if events:
                lines.append({"name": line_name, "events": events})
        planes.append({"name": name, "lines": lines})
    return {"planes": planes}


def metadata_stats(path: str, n: int = 3) -> dict:
    """Device plane -> the stats of its first `n` event metadata records:
    what to look at by hand before trusting PATH_STAT on a new runtime."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for f, _, v in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, _, meta_spans, stat_names = _plane(buf, v)
        if DEVICE_PLANE.match(name):
            out[name] = [
                [_stat(buf, w, stat_names)
                 for g, _, w in _fields(buf, *_map_entry(buf, span)[1])
                 if g == 5] for span in meta_spans[:n]]
    return out


def _core(component: str) -> str:
    """'transpose(jvp(attn))' -> 'attn': jax wraps a scope's name in the
    transformations it was traced under."""
    return re.sub(r"^(?:[\w.\-]+\()+|\)+$", "", component)


@functools.lru_cache(maxsize=None)   # a trace holds a few hundred paths
def scope_of(path: str) -> tuple:
    """(phase or None, innermost part or None, recomputed?) of a name
    path such as 'jit(step)/decode/while/body/closed_call/attn/mul'."""
    phase = part = None
    cores = [_core(c) for c in path.split("/")[:-1]]
    for c in cores:
        if phase is None and c in PHASES:
            phase = c
        if c in PARTS:
            part = c
    return phase, part, RECOMPUTE in cores


def scope_key(phase, part) -> str:
    if phase and part:
        return f"{phase}/{part}"
    return phase or part or UNSCOPED


def _span_self_intervals(lines: list) -> list:
    """[(name, duration, [self intervals]), ...] of every span of the
    host lines: a span's own interval less those of the spans nested in
    it on the same line (a line is one thread)."""
    out = []
    for evs in lines:
        evs = sorted(evs, key=lambda e: (e[1], -e[2]))
        for i, (name, start, dur, _) in enumerate(evs):
            end = start + dur
            inner = []
            for name2, s2, d2, _ in evs[i + 1:]:
                if s2 >= end:
                    break
                inner.append([s2, min(end, s2 + d2)])
            out.append((name, dur,
                        _subtract([[start, end]], _union(inner))))
    return out


def reduce(trace: dict):
    """-> None when the trace holds no device operation, else
    {"window_s", "busy_s" (first device), "scope_s": {key: s} and
    "phase_s": {phase: s} (self time, mean over devices, keys as
    `scope_key` gives them), "recompute_s", "loose_ops": {key: {short
    name: s}} of the operations in no part (a phase's own, and
    `unscoped`), "unscoped_ops": the largest of those as [[name, s]],
    "idle_s": {owner: s} (first device; sums to window_s - busy_s),
    "spans": {name: [count, total s, self s]}, "fields": {name: [stats,
    ...]} of the spans that start in the window, "anchor": {"t_host",
    "trace_ns"} from the first rayt.engine.decode_dispatch, or None,
    "window_ns": [t0, t1], "decode_ns": merged intervals of phase
    `decode` on the first device, "has_scopes", "has_spans"}."""
    per_device = []
    for p in trace["planes"]:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        evs = [ev for ln in p["lines"] if ln["name"] == OPS_LINE
               for ev in ln["events"] if ev[2] > 0]
        if evs:
            per_device.append(evs)
    if not per_device:
        return None
    host_lines = [[ev for ev in ln["events"]
                   if ev[0].startswith(SPAN_PREFIX)]
                  for p in trace["planes"]
                  if p["name"].startswith(HOST_PLANE_PREFIX)
                  for ln in p["lines"]]
    host_lines = [evs for evs in host_lines if evs]
    t0 = min(ev[1] for evs in per_device for ev in evs)
    t1 = max(ev[1] + ev[2] for evs in per_device for ev in evs)
    n = len(per_device)

    # ---- device time by scope
    scope_s: dict = {}
    phase_s: dict = {}
    loose: dict = {}
    recompute = 0.0
    decode_ns: list = []
    for i, evs in enumerate(per_device):
        # _self_times takes a name as it comes: the event's index here
        for k, start, dur, self_ns in _self_times(
                [[k, ev[1], ev[2]] for k, ev in enumerate(evs)]):
            name = evs[k][0]
            phase, part, again = scope_of(evs[k][3].get("path", ""))
            key = scope_key(phase, part)
            scope_s[key] = scope_s.get(key, 0.0) + self_ns / 1e9 / n
            if phase:
                phase_s[phase] = phase_s.get(phase, 0.0) + self_ns / 1e9 / n
            if again:
                recompute += self_ns / 1e9 / n
            if part is None:
                ops = loose.setdefault(key, {})
                short = _short(name)
                ops[short] = ops.get(short, 0.0) + self_ns / 1e9 / n
            if i == 0 and phase == "decode" and self_ns == dur:
                decode_ns.append([start, start + dur])
    busy = _union([[ev[1], ev[1] + ev[2]] for ev in per_device[0]])
    gaps = _subtract([[t0, t1]], busy)

    # ---- idle gaps by owner
    idle: dict = {}
    spans: dict = {}
    gap_ends = [g[1] for g in gaps]
    for name, dur, own in _span_self_intervals(host_lines):
        c = spans.setdefault(name, [0, 0.0, 0.0])
        c[0] += 1
        c[1] += dur / 1e9
        c[2] += _length(own) / 1e9
        # the device leaves tens of thousands of gaps: only those that
        # can meet this span are held against it
        near = gaps[bisect.bisect_right(gap_ends, own[0][0]):
                    bisect.bisect_left(gap_ends, own[-1][1]) + 1] \
            if own else []
        under = _length(intersect(near, own)) / 1e9
        if under:
            idle[name] = idle.get(name, 0.0) + under
    covered = _union([[ev[1], ev[1] + ev[2]] for evs in host_lines
                      for ev in evs])
    engine = _union([[ev[1], ev[1] + ev[2]] for evs in host_lines
                     for ev in evs if ev[0].startswith(ENGINE_PREFIX)])
    handoff = [[a[1], b[0]] for a, b in zip(engine, engine[1:])
               if b[0] - a[1] <= HANDOFF_MAX_NS]
    left = _subtract(gaps, covered)
    between = _length(intersect(left, handoff)) / 1e9
    if between:
        idle[BETWEEN] = between
    idle[UNOWNED] = max(0.0, _length(left) / 1e9 - between)

    fields: dict = {}
    anchor = None
    for evs in host_lines:
        for name, start, dur, stats in evs:
            if t0 <= start <= t1:
                fields.setdefault(name, []).append(
                    {**stats, "start_ns": start, "duration_ns": dur})
            if (name == ENGINE_PREFIX + "decode_dispatch"
                    and "t_host" in stats
                    and (anchor is None or start < anchor["trace_ns"])):
                anchor = {"t_host": float(stats["t_host"]),
                          "trace_ns": start}
    return {"window_s": (t1 - t0) / 1e9, "busy_s": _length(busy) / 1e9,
            "window_ns": [t0, t1], "scope_s": scope_s, "phase_s": phase_s,
            "recompute_s": recompute, "loose_ops": loose,
            "unscoped_ops": [[k[:120], v] for k, v in sorted(
                loose.get(UNSCOPED, {}).items(),
                key=lambda kv: -kv[1])[:TOP]],
            "idle_s": idle, "spans": spans, "fields": fields,
            "anchor": anchor, "decode_ns": _union(decode_ns),
            "has_scopes": any(k != UNSCOPED for k in scope_s),
            "has_spans": bool(spans)}


def intersect(a: list, b: list) -> list:
    """Merged intervals of `a` that merged `b` covers."""
    return _subtract(a, _subtract(a, b))


def to_trace_ns(anchor: dict, t_host: float) -> float:
    """A perf_counter reading of the traced host (any process of it: on
    Linux it is CLOCK_MONOTONIC) on the trace's time axis."""
    return anchor["trace_ns"] + (t_host - anchor["t_host"]) * 1e9


def cut(trace: dict, t0_ns: float, t1_ns: float) -> dict:
    """The plain form cut to the events that lie wholly in [t0, t1),
    times counted from t0 and device operations under their short
    names: small enough to keep as a recorded trace. Cut where the
    device is idle (at the start of a rayt.engine.decode_dispatch), or a
    `while` loses its body and its self time is wrong."""
    planes = []
    for p in trace["planes"]:
        device = bool(DEVICE_PLANE.match(p["name"]))
        lines = []
        for ln in p["lines"]:
            events = [[_short(ev[0]) if device else ev[0],
                       round(ev[1] - t0_ns, 1), round(ev[2], 1), ev[3]]
                      for ev in ln["events"]
                      if t0_ns <= ev[1] and ev[1] + ev[2] <= t1_ns]
            if events:
                lines.append({"name": ln["name"], "events": events})
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def newest_xplane(trace_dir: str):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def reduce_dir(trace_dir: str):
    """The reduction of the newest trace under `trace_dir`, or None when
    there is no trace or it holds no device operation."""
    path = newest_xplane(trace_dir)
    return None if path is None else reduce(events_from_xplane(path))
