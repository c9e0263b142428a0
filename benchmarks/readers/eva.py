"""Readers of the EvaByte model's device time by scope
(`eva_window_attn`, `eva_chunk_attn`, `eva_summarise` beside llama's
`attn_qkv`, `attn_out`, `mlp`, `lm_head`; models/evabyte.py) and of what
its steps required, in the run's profiler trace. The sums of device self
time by any scope name are readers/ssm.py's `table`; the span fields are
readers/spans.py's `reduction`. What is this file's own: it answers only
for a trace that names an `eva_*` scope (a program from before the model
existed gives None, and the line leaves the metric out), the three
shares of a peak, whose bytes and operations are benchmarks/eva_ops.py's,
and the cache's read excess. `idle_share` and `prefill_ms_per_ktok` are
spans.py's own, `itl_p90_ms` the end-to-end metric's reader and
`programs_in_window` startup.py's, named here so that every metric of
the cell that reads the trace or the log names this module
(tests/benchmark_rehearsal/test_trace_spans.py counts the metrics that
name a `spans.` reader).
"""

from __future__ import annotations

from benchmarks import eva_ops, peaks, trace_spans
from benchmarks.readers import sparse_moe, ssm
from benchmarks.readers.spans import (idle_share,  # noqa: F401
                                      prefill_ms_per_ktok)
from benchmarks.readers.startup import programs_in_window  # noqa: F401
from benchmarks.serve_cell import itl_p90_ms  # noqa: F401
from benchmarks.serve_cell import serve_tokens_per_s

DISPATCH = trace_spans.ENGINE_PREFIX + "decode_dispatch"
CHUNK = trace_spans.ENGINE_PREFIX + "prefill_chunk"
ATTN = ("eva_window_attn", "eva_chunk_attn")


def _table(cell: str):
    """ssm.table of a trace that names an `eva_*` scope, else None."""
    tab = ssm.table(cell)
    if tab is None or not any(s.startswith("eva_")
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
    ("prefill", "decode"; "none": under no phase: `insert_row`,
    `set_slot`, the zeroing of a request's prefill cache) over the busy
    time."""
    tab = _table(cell)
    if tab is None:
        return None
    return 100.0 * sum(tab["phase_s"].get(None if p == "none" else p, 0.0)
                       for p in phases) / tab["busy_s"]


def _fields(cell: str, span: str, names: list):
    """Sum over `names` of the fields of the spans named `span` that
    began in the traced stretch; None where no such span carries them."""
    sums = [sparse_moe._field_sum(cell, span, n) for n in names]
    return None if any(s is None for s in sums) else sum(sums)


def _spent(cell: str, phase: str):
    tab = _table(cell)
    return None if tab is None else ssm._seconds(
        tab, [phase + "/" + scope for scope in ATTN])


def decode_attn_roofline_share(obs: dict, cell: str):
    """The least time to read one key and one value over all heads for
    each window position and each summary the traced decode rounds'
    queries attend to (`decode_window_positions_live` +
    `decode_summaries_live` of the decode_dispatch spans), at the memory
    bandwidth (the operations, 2 a byte read, bound nothing), over the
    device time under decode's two attention scopes."""
    rows = _fields(cell, DISPATCH, ["decode_window_positions_live",
                                    "decode_summaries_live"])
    spent = _spent(cell, "decode")
    if not rows or not spent:
        return None
    peak = peaks.peak(obs["device"]["kind"])
    least = max(eva_ops.decode_attn_bytes(obs["config"], rows)
                / peak["hbm_bytes_per_s"],
                eva_ops.attn_flops(obs["config"], rows) / peak["bf16_flops"])
    return 100.0 * least / spent


def prefill_attn_roofline_share(obs: dict, cell: str):
    """The least time to score and weigh every pair of query and visible
    key of the traced chunks (`prefill_window_keys_visible` +
    `prefill_summaries_visible` of the prefill_chunk spans) at the bf16
    peak, over the device time under prefill's two attention scopes."""
    pairs = _fields(cell, CHUNK, ["prefill_window_keys_visible",
                                  "prefill_summaries_visible"])
    spent = _spent(cell, "prefill")
    if not pairs or not spent:
        return None
    peak = peaks.peak(obs["device"]["kind"])
    return 100.0 * (eva_ops.attn_flops(obs["config"], pairs)
                    / peak["bf16_flops"]) / spent


def cache_read_excess(obs: dict, cell: str):
    """Window positions and summaries the traced decode rounds were
    asked to read (`_read`: the kernel's blocks, or both parts whole
    where nothing bounds the read) over those their queries attend to
    (`_live`): 1 where a step reads what it needs and no more."""
    read = _fields(cell, DISPATCH, ["decode_window_positions_read",
                                    "decode_summaries_read"])
    live = _fields(cell, DISPATCH, ["decode_window_positions_live",
                                    "decode_summaries_live"])
    return read / live if read and live else None


def mfu(obs: dict, cell: str):
    """The operations the window's bytes require (two a matmul weight a
    byte, and the attention of one cycle of the traffic's shapes:
    eva_ops.attn_flops_per_token) at the rate the window served them,
    over the chip's bf16 peak. Only of a program that names the model's
    scopes."""
    if _table(cell) is None:
        return None
    config = obs["config"]
    per_token = (2.0 * eva_ops.matmul_params(config)
                 + eva_ops.attn_flops_per_token(config,
                                                obs["traffic"]["shapes"]))
    return (100.0 * per_token * serve_tokens_per_s(obs)
            / peaks.peak(obs["device"]["kind"])["bf16_flops"])
