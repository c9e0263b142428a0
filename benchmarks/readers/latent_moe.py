"""Readers of the `kimi_k2` model's two per-layer metrics in the run's
profiler trace: the whole step's share of the chip's peak and the decode
step's dense latent attention against its roofline. The sums of device
self time by any scope name are readers/ssm.py's `table`, the span
fields readers/sparse_moe.py's `_field_sum`; the bytes and operations
are benchmarks/latent_moe_ops.py's. Both answer only for a trace that
names an `mla_decode_attn` or `mla_prefill_attn` scope
(models/kimi_k2.py): a program from before the model existed gives
None, and the line leaves the metric out.
"""

from __future__ import annotations

from benchmarks import latent_moe_ops, peaks, trace_spans
from benchmarks.readers import sparse_moe, ssm
from benchmarks.serve_cell import serve_tokens_per_s

DISPATCH = trace_spans.ENGINE_PREFIX + "decode_dispatch"
SCOPES = ("mla_decode_attn", "mla_prefill_attn")


def _table(cell: str):
    """ssm.table of a trace that names one of the model's attention
    scopes, else None."""
    tab = ssm.table(cell)
    if tab is None or not any(s in SCOPES for _, s in tab["scope_s"]):
        return None
    return tab


def mfu(obs: dict, cell: str):
    """The operations the window's tokens require (two a matrix weight a
    token meets, the expected share of the held experts among them, and
    the attention of one cycle of the traffic's shapes:
    latent_moe_ops.flops_per_token) at the rate the window served them,
    over the chip's bf16 peak."""
    if _table(cell) is None:
        return None
    per_token = latent_moe_ops.flops_per_token(obs["config"],
                                               obs["traffic"]["shapes"])
    return (100.0 * per_token * serve_tokens_per_s(obs)
            / peaks.peak(obs["device"]["kind"])["bf16_flops"])


def decode_attn_roofline_share(obs: dict, cell: str):
    """The least time to read one cached row and do its heads'
    operations for each live position of each layer of the traced decode
    rounds (`live_positions` of the decode_dispatch spans x the layers:
    every layer attends densely), the larger of bytes over the memory
    bandwidth and operations over the bf16 peak, over the device time
    under decode's `mla_decode_attn`. It reads the same work whatever
    implements the step."""
    tab = _table(cell)
    live = sparse_moe._field_sum(cell, DISPATCH, "live_positions")
    if tab is None or not live:
        return None
    spent = ssm._seconds(tab, ["decode/mla_decode_attn"])
    if not spent:
        return None
    config = obs["config"]
    positions = live * config["num_hidden_layers"]
    peak = peaks.peak(obs["device"]["kind"])
    least = max(latent_moe_ops.decode_attn_bytes(config, positions)
                / peak["hbm_bytes_per_s"],
                latent_moe_ops.decode_attn_flops(config, positions)
                / peak["bf16_flops"])
    return 100.0 * least / spent
