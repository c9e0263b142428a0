"""Readers of the engine loop's own account of its time: the flat
whole-number counters of `LLMEngine.stats()` in microseconds
(`loop_us`, `host_us_*`, `loop_stall_us`; serve/llm.py `host_time`),
taken at the window's two ends. They are counted in every run, traced
or not. A program without them (the parent of the PR that brought them)
gives None, and the line leaves the metric out."""

from __future__ import annotations

from benchmarks.readers.engine import _delta


def counter_share_of_window(obs: dict, counter: str):
    """100 x what the named counter of microseconds counted between the
    window's two ends, over the window's seconds."""
    micros = _delta(obs, counter)
    if micros is None or "window" not in obs:
        return None
    t0, t1 = obs["window"]
    return 100.0 * micros / 1e6 / (t1 - t0)
