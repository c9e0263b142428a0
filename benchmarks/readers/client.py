"""Readers of the load generator's own stamps (host clock, this
process), joined to the program's request records by request id only."""

from __future__ import annotations

import numpy as np

from benchmarks.serve_cell import judged_ttft_s


def _first_in_window(obs):
    t0, t1 = obs["window"]
    return [s for s in obs["streams"] if s.t and t0 <= s.t[0] <= t1]


def lateness_p99_ms(obs: dict):
    """How late the generator sent, against when each request was due."""
    t0, t1 = obs["window"]
    late = [s.sent - s.due for s in obs["streams"]
            if s.sent is not None and t0 <= s.due <= t1]
    return float(np.percentile(late, 99) * 1e3) if late else None


def ttft_p50_ms(obs: dict):
    """Median time to first token, from when the request was due."""
    ttft = [s.t[0] - s.due for s in _first_in_window(obs)]
    return float(np.median(ttft) * 1e3) if ttft else None


def ttft_percentile_ms(obs: dict, q: float):
    """The q-th percentile of the time to first token, from when each
    request was due, over the requests due inside the window. One that
    erred or missed ttft_limit_s counts as slower than any other
    (`serve_cell.judged_ttft_s`); None where the percentile falls among
    those."""
    ttft = sorted(judged_ttft_s(obs))
    if not ttft:
        return None
    # numpy's linear rule, by hand: it gives nan beside an inf
    k = q / 100.0 * (len(ttft) - 1)
    lo = int(k)
    value = ttft[lo]
    if k > lo:
        value += (ttft[lo + 1] - ttft[lo]) * (k - lo)
    return value * 1e3 if np.isfinite(value) else None


def proxy_overhead_p50_ms(obs: dict):
    """Client's time to first token (from the send) minus the same
    request's engine-side `ttft_s` from its record. Two durations of one
    request, each on one process's clock: no clock is shared."""
    diffs = []
    for s in obs["streams"]:
        rec = (obs.get("records") or {}).get(s.request_id)
        eng = (rec or {}).get("engine") or {}
        if s.t and eng.get("ttft_s") is not None:
            diffs.append((s.t[0] - s.sent) - eng["ttft_s"])
    return float(np.median(diffs) * 1e3) if diffs else None


def tokens_per_s_at_first_token(obs: dict):
    """The cell's rate with each prompt counted whole at the instant of
    its first token: serve_tokens_per_s without the interpolation of the
    prompt counter at the window's edges. It steps by one prompt with
    where the edges fall."""
    t0, t1 = obs["window"]
    tokens = sum(len(s.req.tokens) for s in _first_in_window(obs))
    tokens += sum(1 for s in obs["streams"] for x in s.t if t0 <= x <= t1)
    return tokens / (t1 - t0) if tokens else None
