"""Readers of the program's own names in the run's profiler trace
(benchmarks/trace_spans.py): device time by named scope, idle gaps by
the `rayt.*` host span that covers them, and the request records laid
on the trace's clock. Each metric file names its cell, whose trace lies
under .bench_work/<cell>/trace.

A program that writes no such span or scope (one from before they
existed) gives None, and the line leaves the metric out."""

from __future__ import annotations

import os
import re

from benchmarks import attention_ops, peaks, trace_spans
from benchmarks.manifest import ROOT

_reductions: dict = {}


def reduction(cell: str):
    """The cell's newest trace, reduced once per process."""
    path = trace_spans.newest_xplane(
        os.path.join(ROOT, ".bench_work", cell, "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _reductions:
        _reductions.clear()
        _reductions[key] = trace_spans.reduce(
            trace_spans.events_from_xplane(path))
    return _reductions[key]


def _with_spans(cell: str):
    red = reduction(cell)
    return red if red and red["has_spans"] else None


def _with_scopes(cell: str):
    red = reduction(cell)
    return red if red and red["has_scopes"] else None


def idle_share(obs: dict, cell: str, owners: list):
    """Idle seconds of the first device under the named owners (span
    names, `rayt.engine.between_spans`, `unowned`), over the traced
    stretch. Over all owners these sum to the cell's device idle share."""
    red = _with_spans(cell)
    if red is None:
        return None
    return 100.0 * sum(red["idle_s"].get(o, 0.0)
                       for o in owners) / red["window_s"]


def _busy(red: dict) -> float:
    return sum(red["scope_s"].values())


def scope_share(obs: dict, cell: str, scopes: list, ops_like: dict = None):
    """Device self time under the named scope keys ("decode/attn",
    "optimizer", "unscoped", ...; a key ending in "/" takes every part
    of that phase and the phase's own operations), over the busy time.
    `ops_like` {key: pattern} adds, from a key that names no part (a
    phase's own operations, or `unscoped`), those whose short name
    matches the pattern. The keys of one trace sum to 100%."""
    red = _with_scopes(cell)
    if red is None:
        return None
    total = 0.0
    for key, seconds in red["scope_s"].items():
        if any(key == s or (s.endswith("/") and
                            (key + "/").startswith(s)) for s in scopes):
            total += seconds
    for key, pattern in (ops_like or {}).items():
        total += sum(s for name, s in red["loose_ops"].get(key, {}).items()
                     if re.search(pattern, name))
    return 100.0 * total / _busy(red)


def recompute_share(obs: dict, cell: str):
    """Device self time of the operations whose name path holds
    `rematted_computation` (the forward pass run again inside the
    backward under remat), over the busy time."""
    red = _with_scopes(cell)
    return None if red is None else 100.0 * red["recompute_s"] / _busy(red)


def prefill_ms_per_ktok(obs: dict, cell: str):
    """Device milliseconds of phase `prefill` per thousand prompt-slot
    tokens prefilled: the `chunk` fields of the rayt.engine.prefill_chunk
    spans that began in the traced stretch (left padding inside a chunk
    is computed like any token and is counted)."""
    red = _with_scopes(cell)
    if red is None:
        return None
    tokens = sum(int(f.get("chunk", 0)) for f in red["fields"].get(
        "rayt.engine.prefill_chunk", ()))
    if not tokens:
        return None
    return red["phase_s"].get("prefill", 0.0) * 1e3 / (tokens / 1e3)


def flash_roofline_share(obs: dict, cell: str):
    """The least time the chip could take for the attention the traced
    steps require (benchmarks/attention_ops.py: causal half counted
    once, no recomputation; the larger of operations over the bf16 peak
    and bytes over the memory bandwidth, per chip), over the device time
    under the three flash kernels' scopes. Compute-bound at these
    shapes."""
    red = _with_scopes(cell)
    if red is None or not obs.get("traced"):
        return None
    flash_s = sum(s for k, s in red["scope_s"].items()
                  if k.rsplit("/", 1)[-1].startswith("flash_"))
    if not flash_s:
        return None
    job, config, chips = obs["job"], obs["config"], obs["chips"]
    steps = obs["traced"]["steps"]
    peak = peaks.peak(obs["device"]["kind"])
    least = max(
        attention_ops.causal_attention_train_flops(
            config, job["batch_size"], job["seq_len"]) / peak["bf16_flops"],
        attention_ops.causal_attention_train_bytes(
            config, job["batch_size"], job["seq_len"])
        / peak["hbm_bytes_per_s"]) * steps / chips
    return 100.0 * least / flash_s


def ttft_decode_interleave_share(obs: dict, cell: str):
    """Over the requests whose admission-to-first-token interval (the
    record's t_admit and t_first, placed on the trace's clock by the
    anchor) meets the traced stretch: device time in phase `decode`
    inside those intervals over their length. What a waiting prompt pays
    for the decode steps that run between its chunks."""
    red = _with_scopes(cell)
    if red is None or not red["anchor"]:
        return None
    t0, t1 = red["window_ns"]
    waits = []
    for rec in (obs.get("records") or {}).values():
        eng = (rec or {}).get("engine") or {}
        if eng.get("t_admit") is None or eng.get("t_first") is None:
            continue
        a = max(t0, trace_spans.to_trace_ns(red["anchor"], eng["t_admit"]))
        b = min(t1, trace_spans.to_trace_ns(red["anchor"], eng["t_first"]))
        if b > a:
            waits.append([a, b])
    if not waits:
        return None
    inside = sum(trace_spans._length(trace_spans.intersect(
        [w], red["decode_ns"])) for w in waits)
    return 100.0 * inside / sum(b - a for a, b in waits)
