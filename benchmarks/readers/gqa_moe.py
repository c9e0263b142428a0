"""The readers that are the `laguna` model's own: the whole step's share
of the chip's peak, and its attention of two kinds against its roofline,
a decode step's and a chunk's (scopes `full_attn`, `window_attn`;
models/laguna.py). The bytes and operations are benchmarks/gqa_moe_ops.
py's. The held experts' roofline share is readers/sparse_moe.py's (the
scope and the span fields are dots3's), the shares by scope
readers/spans.py's `path_share`, the cache's read excess and the tiles'
fill its `field_ratio`, named in the metric files.

A program that writes no such scope or field gives None, and the line
leaves the metric out."""

from __future__ import annotations

from benchmarks import gqa_moe_ops, peaks, trace_spans
from benchmarks.readers import spans
from benchmarks.serve_cell import serve_tokens_per_s

DISPATCH = trace_spans.ENGINE_PREFIX + "decode_dispatch"
CHUNK = trace_spans.ENGINE_PREFIX + "prefill_chunk"
ATTN = ("full_attn", "window_attn")


def _spent(obs: dict, phase: str):
    tab = spans.table(obs.get("cell"))
    return None if tab is None else spans.path_seconds(
        tab, [phase + "/" + scope for scope in ATTN])


def mfu(obs: dict):
    """The operations the window's tokens require (two a matrix weight a
    token meets, the expected share of the held experts among them, and
    the attention of both kinds over one cycle of the traffic's shapes:
    gqa_moe_ops.flops_per_token) at the rate the window served them,
    over the chip's bf16 peak. Only of a program whose trace names the
    model's attention scopes."""
    if not _spent(obs, "decode") and not _spent(obs, "prefill"):
        return None
    per_token = gqa_moe_ops.flops_per_token(obs["config"],
                                            obs["traffic"]["shapes"])
    return (100.0 * per_token * serve_tokens_per_s(obs)
            / peaks.peak(obs["device"]["kind"])["bf16_flops"])


def decode_attn_roofline_share(obs: dict):
    """The least time to read K and V of each position the traced decode
    rounds' queries attend to, each full layer's whole range and each
    sliding layer's visible ring rows, once
    (`decode_full_positions_attended` + `decode_window_positions_attended`
    of the decode_dispatch spans, summed over the layers by the
    program), at the memory bandwidth (the operations, 6 or 9 heads a
    kv head, bound nothing), over the device time under decode's two
    attention scopes."""
    full, window = (spans.field_sum(obs, DISPATCH, [field]) for field in (
        "decode_full_positions_attended", "decode_window_positions_attended"))
    spent = _spent(obs, "decode")
    if not full or not window or not spent:
        return None
    config, peak = obs["config"], peaks.peak(obs["device"]["kind"])
    least = max(gqa_moe_ops.decode_attn_bytes(config, full + window)
                / peak["hbm_bytes_per_s"],
                gqa_moe_ops.attn_flops(config, full, window)
                / peak["bf16_flops"])
    return 100.0 * least / spent


def prefill_attn_roofline_share(obs: dict):
    """The least time to score and weigh every pair of query and visible
    key of the traced chunks, for each query head of the layer's kind
    (`prefill_full_keys_visible`, `prefill_window_keys_visible` of the
    prefill_chunk spans, summed over the layers by the program), at the
    bf16 peak, over the device time under prefill's two attention
    scopes."""
    full = spans.field_sum(obs, CHUNK, ["prefill_full_keys_visible"])
    window = spans.field_sum(obs, CHUNK, ["prefill_window_keys_visible"])
    spent = _spent(obs, "prefill")
    if not full or not window or not spent:
        return None
    peak = peaks.peak(obs["device"]["kind"])
    return 100.0 * (gqa_moe_ops.attn_flops(obs["config"], full, window)
                    / peak["bf16_flops"]) / spent
