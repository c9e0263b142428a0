"""The readers of a model's own yardsticks, one for every model: the
whole step's share of the chip's peak, its attention against its
roofline in a decode step and in a chunk, and what a decode step was
asked to read of its cache over what its queries attend to. Each finds
the cell's arithmetic from the run: the configuration file's "model" key
names the helper, and a configuration with none is found by the driver
its traffic file names; that module's YARDSTICKS
(benchmarks/yardsticks.py) names the operations, bytes, scopes and span
fields. No metric file names a model, and a later model edits nothing
here.

A cell whose module names no yardstick of a kind, a module that is not
there, and a program that writes no such scope or field all give None,
and the line leaves the metric out."""

from __future__ import annotations

import importlib

from benchmarks import peaks
from benchmarks.readers import spans
from benchmarks.serve_cell import serve_tokens_per_s
from benchmarks.yardsticks import CHUNK, DISPATCH


def yardsticks(obs: dict):
    """The Yardsticks of the run's cell, or None."""
    name = (obs.get("config") or {}).get("model") or \
        (obs.get("traffic") or {}).get("driver")
    if not name:
        return None
    try:
        module = importlib.import_module("benchmarks." + name)
    except ImportError:
        return None
    return getattr(module, "YARDSTICKS", None)


def _spent(obs: dict, y, phase: str):
    """Device seconds under the model's attention scopes of `phase`."""
    tab = spans.table(obs.get("cell"))
    return None if tab is None else spans.path_seconds(
        tab, y.phase_scopes(phase))


def _sums(obs: dict, span: str, groups: tuple):
    """Each group of fields summed over the traced stretch's spans; None
    where a group is missing or counts nothing."""
    sums = [spans.field_sum(obs, span, list(group)) for group in groups]
    return sums if sums and all(sums) else None


def mfu(obs: dict):
    """The operations the window's tokens require (the model's
    `flops_per_token`: two a matrix weight a token meets, the expected
    share of held experts among them, and its attention over one cycle
    of the traffic's shapes) at the rate the window served them, over
    the chip's bf16 peak. Only of a program whose trace names the
    model's attention scopes."""
    y = yardsticks(obs)
    if y is None or y.flops_per_token is None:
        return None
    if not _spent(obs, y, "decode") and not _spent(obs, y, "prefill"):
        return None
    per_token = y.flops_per_token(obs["config"], obs["traffic"])
    return (100.0 * per_token * serve_tokens_per_s(obs)
            / peaks.peak(obs["device"]["kind"])["bf16_flops"])


def decode_attn_roofline_share(obs: dict):
    """The least time to read, and to do the operations of, what the
    traced decode rounds' queries attend to (the model's
    `decode_attended` fields of the decode_dispatch spans through its
    `decode_attn_work`: the larger of bytes over the memory bandwidth
    and operations over the bf16 peak), over the device time under
    decode's attention scopes. It reads the same work whatever
    implements the step."""
    y = yardsticks(obs)
    if y is None or y.decode_attn_work is None:
        return None
    sums = _sums(obs, DISPATCH, y.decode_attended)
    spent = _spent(obs, y, "decode")
    if not sums or not spent:
        return None
    moved, flops = y.decode_attn_work(obs["config"], *sums)
    peak = peaks.peak(obs["device"]["kind"])
    least = max(moved / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"])
    return 100.0 * least / spent


def prefill_attn_roofline_share(obs: dict):
    """The least time to score and weigh every pair of query and visible
    key of the traced chunks (the model's `prefill_visible` fields of
    the prefill_chunk spans through its `prefill_attn_flops`) at the
    bf16 peak, over the device time under prefill's attention scopes."""
    y = yardsticks(obs)
    if y is None or y.prefill_attn_flops is None:
        return None
    sums = _sums(obs, CHUNK, y.prefill_visible)
    spent = _spent(obs, y, "prefill")
    if not sums or not spent:
        return None
    peak = peaks.peak(obs["device"]["kind"])
    return 100.0 * (y.prefill_attn_flops(obs["config"], *sums)
                    / peak["bf16_flops"]) / spent


def cache_read_excess(obs: dict):
    """What the traced decode rounds were asked to read of the cache
    over what their queries attend to (the model's `cache_read` fields
    of the decode_dispatch spans): 1 where a step reads what it needs."""
    y = yardsticks(obs)
    if y is None or not y.cache_read:
        return None
    over, under = y.cache_read
    return spans.field_ratio(obs, DISPATCH, list(over), list(under))
