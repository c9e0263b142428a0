"""Readers of the trace reduction (benchmarks/trace_reduce.py) and of
the device's memory counters."""

from __future__ import annotations


def _trace(obs: dict):
    tr = obs.get("trace")
    return tr if tr and tr.get("window_s") else None


def idle_share(obs: dict):
    tr = _trace(obs)
    return None if tr is None else 100.0 * (1.0 - tr["busy_s"]
                                            / tr["window_s"])


def collective_exposed_share(obs: dict):
    """Time in collective operations while no other operation runs on
    that device, over the traced window; mean over devices."""
    tr = _trace(obs)
    return None if tr is None else (100.0 * tr["collective_exposed_s"]
                                    / tr["window_s"])


def peak_hbm_gb(obs: dict):
    """peak_bytes_in_use + peak_bytes_reserved of the fullest device."""
    return obs["memory_peak_bytes"] / 1e9
