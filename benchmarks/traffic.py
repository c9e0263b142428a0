"""The one traffic generator. A traffic mix is a data file
(traffic/<name>.json); this module turns it and a seed into requests.

What repeats from seed to seed, by construction:

* the multiset of shapes. Request i of a stratum takes its prompt and
  output lengths at the evenly spaced quantiles (i + 1/2) / n of the
  file's distributions, and a shuffle pairs them (prompts and outputs
  apart). Every seed offers the same total of prompt tokens, of output
  tokens and the same count per bucket.
* an open loop's whole schedule. `round(rate x seconds)` arrivals at
  sorted uniform draws (a Poisson process given its count) on a ring
  `seconds` long, each with its shapes, are drawn once from the file's
  `schedule_seed`. The seed picks the point of the ring at which the
  window starts, and draws the token ids. The window is one whole turn,
  so every seed's window holds the same requests at the same distances
  from each other, and what runs over its end is what ran into its
  start; the lead-in plays the arc that precedes the starting point.
  With a schedule of its own for every seed the window's work swung
  with the draw (PERF.md, PR 27): how many requests overlapped decided
  the gaps' tail more than the program did.

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


def _shapes_and_dues(rng, spec: dict, n: int, t0: float, t1: float,
                     partway: bool = False):
    """n prompt lengths, n output lengths, each shuffled apart, and n
    sorted uniform arrival times in [t0, t1)."""
    prompts = quantile_lengths(spec["prompt_len"], n)
    outputs = quantile_lengths(spec["output_len"], n)
    if partway:  # request i is the fraction (i + 1/2) / n through its output
        outputs = [max(1, round(o * (n - i - 0.5) / n))
                   for i, o in enumerate(outputs)]
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return prompts, outputs, np.sort(rng.uniform(t0, t1, size=n))


def _stratum(rng, spec: dict, vocab: int, n: int, t0: float, t1: float,
             name: str, first_index: int, partway: bool = False) -> list:
    prompts, outputs, dues = _shapes_and_dues(rng, spec, n, t0, t1, partway)
    return [Request(index=first_index + i, due_s=float(dues[i]),
                    tokens=rng.integers(1, vocab, size=prompts[i]).tolist(),
                    max_new_tokens=outputs[i], stratum=name)
            for i in range(n)]


def open_loop(spec: dict, vocab: int, seed: int, seconds: float) -> list:
    """Requests for lead_s of lead-in and `seconds` of window, sorted by
    due time: one turn of the file's ring and the arc that precedes it;
    see the module docstring."""
    rng = np.random.default_rng([int(seed), 1])
    lead = float(spec["lead_s"])
    # the engine starts full: `inflight_at_start` requests at time 0,
    # each partway through its output (evenly spaced fractions), stand
    # for those a steady state would already hold, so the lead-in need
    # not last a whole request lifetime
    reqs = _stratum(rng, spec, vocab, int(spec.get("inflight_at_start", 0)),
                    0.0, 0.0, "lead", 0, partway=True)
    ring = _shapes_and_dues(
        np.random.default_rng([int(spec["schedule_seed"]), 4]), spec,
        round(spec["rate_per_s"] * seconds), 0.0, seconds)
    phase = rng.uniform(0.0, seconds)
    for prompt_len, max_new_tokens, at in zip(*ring):
        due = lead + (float(at) - phase) % seconds    # inside the window
        while due >= 0.0:                  # and each turn before it
            reqs.append(Request(
                index=0, due_s=due, max_new_tokens=max_new_tokens,
                tokens=rng.integers(1, vocab, size=prompt_len).tolist(),
                stratum="window" if due >= lead else "lead"))
            due -= seconds
    reqs.sort(key=lambda r: r.due_s)
    for i, r in enumerate(reqs):
        r.index = i
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


def cycle_lengths(spec: dict) -> tuple:
    """(prompt lengths, output lengths) of one cycle of a closed loop:
    what every cycle holds, whatever the seed. The pairing of the two is
    shuffled anew in each cycle."""
    n = int(spec["cycle"])
    return (quantile_lengths(spec["prompt_len"], n),
            quantile_lengths(spec["output_len"], n))


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
