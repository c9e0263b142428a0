"""Readers of the hybrid model's device time by scope, the state-space
scopes among them (`ssm_in_proj`, `ssm_conv`, `ssm_update`, `ssm_scan`,
`ssm_gate_norm`, `ssm_out`; models/granite_hybrid.py), in the run's
profiler trace. trace_spans.PARTS is a closed tuple, so
`spans.scope_share` sees an operation under one of these only as its
phase's own; this file sums device self time by any scope name from
`trace_spans.events_from_xplane` itself. Every per-layer metric of the
hybrid cell that reads the trace names a reader of this module, the
idle shares and the prefill's cost a token too (`idle_share` and
`prefill_ms_per_ktok` are spans.py's own): tests/
benchmark_rehearsal/test_trace_spans.py counts the metrics that name a
`spans.` reader, and is not this file's to edit.

A program that writes no such scope (one from before the model existed)
gives None, and the line leaves the metric out."""

from __future__ import annotations

import os

from benchmarks import peaks, ssm_ops, trace_spans
from benchmarks.manifest import ROOT
from benchmarks.readers.spans import (idle_share,  # noqa: F401
                                      prefill_ms_per_ktok)
from benchmarks.trace_reduce import (DEVICE_PLANE, HOST_PLANE_PREFIX,
                                     OPS_LINE, _self_times)

DISPATCH = trace_spans.ENGINE_PREFIX + "decode_dispatch"
_tables: dict = {}


def table(cell: str):
    """{"busy_s": device self time, "phase_s": {phase or None: s},
    "scope_s": {(phase, scope): s} for every scope name in an
    operation's path beside its phase (an operation under
    `decode/.../ssm_update` counts under ("decode", "ssm_update"), and
    under every other scope of its path too), "active": the `active`
    field of each rayt.engine.decode_dispatch span that began in the
    traced stretch}, mean over devices, of the cell's newest trace; None
    where there is no device operation."""
    path = trace_spans.newest_xplane(
        os.path.join(ROOT, ".bench_work", cell, "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key in _tables:
        return _tables[key]
    trace = trace_spans.events_from_xplane(path)
    per_device = [[ev for ln in p["lines"] if ln["name"] == OPS_LINE
                   for ev in ln["events"] if ev[2] > 0]
                  for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    per_device = [evs for evs in per_device if evs]
    if not per_device:
        return None
    t0 = min(ev[1] for evs in per_device for ev in evs)
    t1 = max(ev[1] + ev[2] for evs in per_device for ev in evs)
    busy, phase_s, scope_s = 0.0, {}, {}
    for evs in per_device:
        for k, _, _, self_ns in _self_times(
                [[k, ev[1], ev[2]] for k, ev in enumerate(evs)]):
            seconds = self_ns / 1e9 / len(per_device)
            busy += seconds
            cores = [trace_spans._core(c) for c in
                     evs[k][3].get("path", "").split("/")[:-1]]
            phase = next((c for c in cores if c in trace_spans.PHASES), None)
            phase_s[phase] = phase_s.get(phase, 0.0) + seconds
            for scope in set(cores) - {phase}:
                scope_s[(phase, scope)] = scope_s.get((phase, scope),
                                                      0.0) + seconds
    active = [int(ev[3]["active"]) for p in trace["planes"]
              if p["name"].startswith(HOST_PLANE_PREFIX)
              for ln in p["lines"] for ev in ln["events"]
              if ev[0] == DISPATCH and t0 <= ev[1] <= t1
              and "active" in ev[3]]
    _tables.clear()
    _tables[key] = {"busy_s": busy, "phase_s": phase_s, "scope_s": scope_s,
                    "active": active}
    return _tables[key]


def _seconds(tab: dict, scopes: list) -> float:
    return sum(tab["scope_s"].get(tuple(s.split("/")), 0.0) for s in scopes)


def _hybrid_table(cell: str):
    """The table of a trace that names an `ssm_*` scope, else None."""
    tab = table(cell)
    if tab is None or not any(s.startswith("ssm_")
                              for _, s in tab["scope_s"]):
        return None
    return tab


def scope_share(obs: dict, cell: str, scopes: list):
    """Device self time under the named "<phase>/<scope>" keys over the
    busy time."""
    tab = _hybrid_table(cell)
    return None if tab is None else (100.0 * _seconds(tab, scopes)
                                     / tab["busy_s"])


def phase_share(obs: dict, cell: str, phases: list):
    """Device self time of every operation under the named phases
    ("prefill", "decode"; "none": under no phase, which is what
    `insert_row`, `set_slot` and the zeroing of a new request's cache
    are) over the busy time."""
    tab = _hybrid_table(cell)
    if tab is None:
        return None
    return 100.0 * sum(tab["phase_s"].get(None if p == "none" else p, 0.0)
                       for p in phases) / tab["busy_s"]


def update_roofline_share(obs: dict, cell: str):
    """The least time the chip could take for the state updates of the
    traced decode rounds (benchmarks/ssm_ops.py: each live slot's state
    and convolution tail read and written once a round; the larger of
    bytes over the memory bandwidth and operations over the bf16 peak,
    which the bytes bound), over the device time under decode's
    `ssm_update`."""
    tab = table(cell)
    if tab is None:
        return None
    spent = _seconds(tab, ["decode/ssm_update"])
    if not spent or not tab["active"]:
        return None
    config = obs["config"]
    peak = peaks.peak(obs["device"]["kind"])
    live = sum(tab["active"])
    least = max(ssm_ops.ssm_update_bytes(config, live)
                / peak["hbm_bytes_per_s"],
                ssm_ops.ssm_update_flops(config, live) / peak["bf16_flops"])
    return 100.0 * least / spent
