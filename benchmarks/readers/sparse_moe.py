"""Readers of the `dots3_note` model's device time by scope (`mla_q`,
`mla_kv`, `index_score`, `index_topk`, `sparse_attn`, `window_attn`,
`attn_gate_out`, `moe_router`, `moe_experts`, `moe_shared`;
models/dots3_note.py) and of what its decode steps required, in the
run's profiler trace. The sums of device self time by any scope name are
readers/ssm.py's `table`; the span fields are readers/spans.py's
`reduction`. What is this file's own: it answers only for a trace that
names a `moe_*` scope (a program from before the model existed gives
None, and the line leaves the metric out), and the three roofline
shares, whose bytes and operations are benchmarks/sparse_moe_ops.py's.
`idle_share` and `prefill_ms_per_ktok` are spans.py's own, named here so
that every metric of the cell that reads the trace names this module
(tests/benchmark_rehearsal/test_trace_spans.py counts the metrics that
name a `spans.` reader); `itl_p90_ms` is the end-to-end metric's reader,
for a cell that reports it as a per-layer metric and is not judged by it.
"""

from __future__ import annotations

from benchmarks import peaks, sparse_moe_ops, trace_spans
from benchmarks.readers import spans, ssm
from benchmarks.readers.spans import (idle_share,  # noqa: F401
                                      prefill_ms_per_ktok)
from benchmarks.serve_cell import itl_p90_ms  # noqa: F401

EMIT = trace_spans.ENGINE_PREFIX + "emit"
DISPATCH = trace_spans.ENGINE_PREFIX + "decode_dispatch"


def _table(cell: str):
    """ssm.table of a trace that names a `moe_*` scope, else None."""
    tab = ssm.table(cell)
    if tab is None or not any(s.startswith("moe_")
                              for _, s in tab["scope_s"]):
        return None
    return tab


def scope_share(obs: dict, cell: str, scopes: list):
    """Device self time under the named "<phase>/<scope>" keys over the
    busy time."""
    tab = _table(cell)
    return None if tab is None else (100.0 * ssm._seconds(tab, scopes)
                                     / tab["busy_s"])


def phase_share(obs: dict, cell: str, phases: list):
    """Device self time of every operation under the named phases
    ("prefill", "decode"; "none": under no phase, which is what
    `insert_row`, `set_slot` and the zeroing of a new request's cache
    are) over the busy time."""
    tab = _table(cell)
    if tab is None:
        return None
    return 100.0 * sum(tab["phase_s"].get(None if p == "none" else p, 0.0)
                       for p in phases) / tab["busy_s"]


def _field_sum(cell: str, span: str, field: str):
    """Sum of `field` over the spans named `span` that began in the
    traced stretch; None where no such span carries it."""
    red = spans.reduction(cell)
    vals = [int(f[field]) for f in (red or {}).get("fields", {}).get(span, ())
            if field in f]
    return sum(vals) if vals else None


def _roofline(obs: dict, cell: str, scope: str, count, bytes_fn, flops_fn):
    """The larger of bytes over the memory bandwidth and operations over
    the bf16 peak for `count` units of required work, over the device
    time under decode's `scope`."""
    tab = _table(cell)
    if tab is None or not count:
        return None
    spent = ssm._seconds(tab, ["decode/" + scope])
    if not spent:
        return None
    peak = peaks.peak(obs["device"]["kind"])
    least = max(bytes_fn(obs["config"], count) / peak["hbm_bytes_per_s"],
                flops_fn(obs["config"], count) / peak["bf16_flops"])
    return 100.0 * least / spent


def experts_roofline_share(obs: dict, cell: str):
    """Each held expert that a token of the traced decode rounds chose,
    read once (`experts_hit` of the rayt.engine.emit spans), or the
    operations of the pairs computed (`expert_rows`) where they bound,
    over the device time under decode's `moe_experts`. The emit span of
    a step is written when its tokens are read, one round after the
    step ran: the stretch's two edges each miss or add one round."""
    hit = _field_sum(cell, EMIT, "experts_hit")
    rows = _field_sum(cell, EMIT, "expert_rows") or 0
    return _roofline(
        obs, cell, "moe_experts", hit, sparse_moe_ops.expert_bytes,
        lambda config, _: sparse_moe_ops.expert_flops(config, rows))


def index_score_roofline_share(obs: dict, cell: str):
    """One index key read for each live position of each full layer
    (`decode_index_positions_scored` of the decode_dispatch spans), over
    the device time under decode's `index_score`."""
    return _roofline(
        obs, cell, "index_score",
        _field_sum(cell, DISPATCH, "decode_index_positions_scored"),
        sparse_moe_ops.index_score_bytes, sparse_moe_ops.index_score_flops)


def sparse_attn_roofline_share(obs: dict, cell: str):
    """One cached row read and attended to for each selected position of
    each full layer (`decode_latent_positions_attended` of the
    decode_dispatch spans), over the device time under decode's
    `sparse_attn`."""
    return _roofline(
        obs, cell, "sparse_attn",
        _field_sum(cell, DISPATCH, "decode_latent_positions_attended"),
        sparse_moe_ops.sparse_attn_bytes, sparse_moe_ops.sparse_attn_flops)
