"""The arithmetic of the three per-model reader modules PR 60 retired
(benchmarks/readers/eva.py, latent_moe.py, gqa_moe.py) and the arguments
of the three `*_cache_read_excess` metric files that went with them,
kept as they stood so that test_manifest_entries.py can hold the one
reader that took their place (readers/model.py) to the same number,
digit for digit, on each model's hand-made run. Nothing here is read by
the benchmark."""

from __future__ import annotations

from benchmarks import eva_ops, gqa_moe_ops, latent_moe_ops, peaks
from benchmarks.readers import spans
from benchmarks.serve_cell import serve_tokens_per_s

DISPATCH = "rayt.engine.decode_dispatch"
CHUNK = "rayt.engine.prefill_chunk"


def _spent(obs: dict, scopes: list):
    tab = spans.table(obs.get("cell"))
    return None if tab is None else spans.path_seconds(tab, scopes)


def _peak(obs: dict) -> dict:
    return peaks.peak(obs["device"]["kind"])


def _mfu(obs: dict, per_token: float):
    return 100.0 * per_token * serve_tokens_per_s(obs) \
        / _peak(obs)["bf16_flops"]


def _eva_spent(obs, phase):
    return _spent(obs, [phase + "/" + s
                        for s in ("eva_window_attn", "eva_chunk_attn")])


def eva_mfu(obs: dict):
    if not _eva_spent(obs, "decode") and not _eva_spent(obs, "prefill"):
        return None
    config = obs["config"]
    return _mfu(obs, 2.0 * eva_ops.matmul_params(config)
                + eva_ops.attn_flops_per_token(config,
                                               obs["traffic"]["shapes"]))


def eva_decode_attn_roofline_share(obs: dict):
    rows = spans.field_sum(obs, DISPATCH, ["decode_window_positions_live",
                                           "decode_summaries_live"])
    spent = _eva_spent(obs, "decode")
    if not rows or not spent:
        return None
    peak = _peak(obs)
    least = max(eva_ops.decode_attn_bytes(obs["config"], rows)
                / peak["hbm_bytes_per_s"],
                eva_ops.attn_flops(obs["config"], rows) / peak["bf16_flops"])
    return 100.0 * least / spent


def eva_prefill_attn_roofline_share(obs: dict):
    pairs = spans.field_sum(obs, CHUNK, ["prefill_window_keys_visible",
                                         "prefill_summaries_visible"])
    spent = _eva_spent(obs, "prefill")
    if not pairs or not spent:
        return None
    return 100.0 * (eva_ops.attn_flops(obs["config"], pairs)
                    / _peak(obs)["bf16_flops"]) / spent


KIMI_DECODE, KIMI_PREFILL = "decode/mla_decode_attn", \
    "prefill/mla_prefill_attn"


def latent_moe_mfu(obs: dict):
    if not _spent(obs, [KIMI_DECODE]) and not _spent(obs, [KIMI_PREFILL]):
        return None
    return _mfu(obs, latent_moe_ops.flops_per_token(
        obs["config"], obs["traffic"]["shapes"]))


def latent_moe_decode_attn_roofline_share(obs: dict):
    live = spans.field_sum(obs, DISPATCH, ["live_positions"])
    spent = _spent(obs, [KIMI_DECODE])
    if not live or not spent:
        return None
    config = obs["config"]
    positions = live * config["num_hidden_layers"]
    peak = _peak(obs)
    least = max(latent_moe_ops.decode_attn_bytes(config, positions)
                / peak["hbm_bytes_per_s"],
                latent_moe_ops.decode_attn_flops(config, positions)
                / peak["bf16_flops"])
    return 100.0 * least / spent


def latent_moe_prefill_attn_roofline_share(obs: dict):
    pairs = spans.field_sum(obs, CHUNK, ["prefill_latent_keys_visible"])
    spent = _spent(obs, [KIMI_PREFILL])
    if not pairs or not spent:
        return None
    return 100.0 * (latent_moe_ops.attn_flops(obs["config"], pairs)
                    / _peak(obs)["bf16_flops"]) / spent


def _gqa_spent(obs, phase):
    return _spent(obs, [phase + "/" + s
                        for s in ("full_attn", "window_attn")])


def gqa_moe_mfu(obs: dict):
    if not _gqa_spent(obs, "decode") and not _gqa_spent(obs, "prefill"):
        return None
    return _mfu(obs, gqa_moe_ops.flops_per_token(
        obs["config"], obs["traffic"]["shapes"]))


def gqa_moe_decode_attn_roofline_share(obs: dict):
    full, window = (spans.field_sum(obs, DISPATCH, [field]) for field in (
        "decode_full_positions_attended", "decode_window_positions_attended"))
    spent = _gqa_spent(obs, "decode")
    if not full or not window or not spent:
        return None
    config, peak = obs["config"], _peak(obs)
    least = max(gqa_moe_ops.decode_attn_bytes(config, full + window)
                / peak["hbm_bytes_per_s"],
                gqa_moe_ops.attn_flops(config, full, window)
                / peak["bf16_flops"])
    return 100.0 * least / spent


def gqa_moe_prefill_attn_roofline_share(obs: dict):
    full = spans.field_sum(obs, CHUNK, ["prefill_full_keys_visible"])
    window = spans.field_sum(obs, CHUNK, ["prefill_window_keys_visible"])
    spent = _gqa_spent(obs, "prefill")
    if not full or not window or not spent:
        return None
    return 100.0 * (gqa_moe_ops.attn_flops(obs["config"], full, window)
                    / _peak(obs)["bf16_flops"]) / spent


def _excess(over: list, under: list):
    return lambda obs: spans.field_ratio(obs, DISPATCH, over, under)


eva_cache_read_excess = _excess(
    ["decode_window_positions_read", "decode_summaries_read"],
    ["decode_window_positions_live", "decode_summaries_live"])
latent_moe_cache_read_excess = _excess(["decode_latent_positions_read"],
                                       ["decode_latent_positions_live"])
gqa_moe_cache_read_excess = _excess(
    ["decode_full_positions_read", "decode_window_positions_read"],
    ["decode_full_positions_attended", "decode_window_positions_attended"])

# old reader module -> the cell it read
CELLS = {"eva": "EvaByte.bytes-longdoc-closed",
         "latent_moe": "Kimi-K2.6.docqa-closed",
         "gqa_moe": "Laguna-S-2.1.codectx-closed"}
FUNCTIONS = ("mfu", "decode_attn_roofline_share",
             "prefill_attn_roofline_share", "cache_read_excess")
