"""The one traffic generator. A traffic mix is a data file
(traffic/<name>.json); this module turns it and a seed into requests.

What repeats from seed to seed, by construction:

* the multiset of shapes. Request i of a stratum takes its prompt and
  output lengths at the evenly spaced quantiles (i + 1/2) / n of the
  file's distributions; the seed only shuffles the order (prompts and
  outputs apart) and draws the token ids. Every seed offers the same
  total of prompt tokens, of output tokens and the same count per bucket.
* the count of arrivals. An open loop sends exactly round(rate x length)
  requests in the lead-in and in the window, each at sorted uniform
  draws: a Poisson process given its count.

A closed loop hands its clients the requests of a cycle of `cycle`
shapes, shuffled anew each cycle, so any `cycle` consecutive requests
carry the same work.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float            # open loop: offset from the load's start
    tokens: list
    max_new_tokens: int
    stratum: str            # "lead" | "window" | "cycle"


def quantile_lengths(spec: dict, n: int) -> list:
    """n lengths at evenly spaced quantiles of `spec`, clipped to
    [min, max]. dist: "lognormal" (median, sigma) or "uniform"."""
    qs = [(i + 0.5) / n for i in range(n)]
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        vals = [lo + q * (hi - lo) for q in qs]
    elif spec["dist"] == "lognormal":
        nd = NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
                for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(hi, max(lo, round(v)))) for v in vals]


def _stratum(rng, spec: dict, vocab: int, n: int, t0: float, t1: float,
             name: str, first_index: int, partway: bool = False) -> list:
    prompts = quantile_lengths(spec["prompt_len"], n)
    outputs = quantile_lengths(spec["output_len"], n)
    if partway:  # request i is the fraction (i + 1/2) / n through its output
        outputs = [max(1, round(o * (n - i - 0.5) / n))
                   for i, o in enumerate(outputs)]
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    dues = np.sort(rng.uniform(t0, t1, size=n))
    return [Request(index=first_index + i, due_s=float(dues[i]),
                    tokens=rng.integers(1, vocab, size=prompts[i]).tolist(),
                    max_new_tokens=outputs[i], stratum=name)
            for i in range(n)]


def open_loop(spec: dict, vocab: int, seed: int, seconds: float) -> list:
    """Requests for lead_s of lead-in and `seconds` of window, sorted by
    due time. Counts are fixed by the rate; see the module docstring."""
    rng = np.random.default_rng([int(seed), 1])
    lead = float(spec["lead_s"])
    n_lead = round(spec["rate_per_s"] * lead)
    n_win = round(spec["rate_per_s"] * seconds)
    # the engine starts full: `inflight_at_start` requests at time 0,
    # each partway through its output (evenly spaced fractions), stand
    # for those a steady state would already hold, so the lead-in need
    # not last a whole request lifetime
    n0 = int(spec.get("inflight_at_start", 0))
    reqs = _stratum(rng, spec, vocab, n0, 0.0, 0.0, "lead", 0,
                    partway=True)
    reqs += _stratum(rng, spec, vocab, n_lead, 0.0, lead, "lead", n0)
    reqs += _stratum(rng, spec, vocab, n_win, lead, lead + seconds,
                     "window", n0 + n_lead)
    return reqs


def closed_loop(spec: dict, vocab: int, seed: int):
    """Endless iterator of requests for a closed loop (due_s unused)."""
    rng = np.random.default_rng([int(seed), 2])
    n = int(spec["cycle"])
    index = 0
    while True:
        for r in _stratum(rng, spec, vocab, n, 0.0, 0.0, "cycle", index):
            yield r
        index += n


def bucket_of(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def shape_summary(reqs: list, buckets) -> dict:
    """What a seed may not change: totals and counts per bucket."""
    counts: dict = {}
    for r in reqs:
        b = bucket_of(len(r.tokens), buckets)
        counts[b] = counts.get(b, 0) + 1
    return {"requests": len(reqs),
            "prompt_tokens": sum(len(r.tokens) for r in reqs),
            "output_tokens": sum(r.max_new_tokens for r in reqs),
            "per_bucket": dict(sorted(counts.items()))}
