"""Readers of the program's request records (`engine` section, host-clock
spans of the replica's process) and of LLMEngine.stats() and
device_report() taken at the window's two ends."""

from __future__ import annotations

import numpy as np


def _engine_records(obs: dict) -> list:
    """(stream, engine section) of the requests finished in the window."""
    out = []
    for s in obs["streams"]:
        rec = (obs.get("records") or {}).get(s.request_id)
        eng = (rec or {}).get("engine")
        if eng:
            out.append((s, eng))
    return out


def _median_ms(obs, key):
    vals = [e[key] for _, e in _engine_records(obs) if e.get(key) is not None]
    return float(np.median(vals) * 1e3) if vals else None


def queue_p50_ms(obs: dict):
    return _median_ms(obs, "queue_s")


def tpot_p50_ms(obs: dict):
    return _median_ms(obs, "tpot_s")


def batch_occupancy(obs: dict):
    """Mean share of decode slots busy, over the decode steps of the
    window's requests."""
    pairs = [(e["occupancy_mean"], e["decode_steps"])
             for _, e in _engine_records(obs)
             if e.get("occupancy_mean") is not None]
    if not pairs:
        return None
    return 100.0 * sum(o * n for o, n in pairs) / sum(n for _, n in pairs)


def _delta(obs: dict, key: str) -> float:
    return obs["after"]["stats"][key] - obs["before"]["stats"][key]


def prefill_step_share(obs: dict):
    """Prefill calls over decode rounds in the window: on which mode the
    gap's 90th percentile sits. (`prefills` also counts the completion of
    a chunked prompt, so a chunked request counts one call too many.)"""
    rounds = _delta(obs, "batches")
    if not rounds:
        return None
    return 100.0 * (_delta(obs, "prefill_chunks")
                    + _delta(obs, "prefills")) / rounds


def decode_rounds_per_chunk(obs: dict):
    """Decode steps over prefill chunk calls in the window. The engine
    runs one chunk per round, so near 1 every chunk of a long prompt is
    followed by a whole decode step over the full cache."""
    chunks = _delta(obs, "prefill_chunks")
    return _delta(obs, "batches") / chunks if chunks else None


def compiles_in_window(obs: dict):
    """Programs the replica's process asked XLA for, compiled or fetched
    from the persistent cache, between the window's two ends (the
    engine's own `step_programs` count is on the info line)."""
    return obs["after"]["programs"] - obs["before"]["programs"]
