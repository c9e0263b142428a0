"""Shuffle-envelope perf gate (slow-marked so tier-1 stays fast).

Floors the `shuffle_gb_per_s` leg: the pipelined exchange shuffle
(data/exchange.py) must clear an absolute GB/s floor AND beat the old
barrier executor (per-row dict sharding, reduce-waits-for-every-map) on
the same leg (`tools/envelope_bench.measure_shuffle`).
"""

from __future__ import annotations

import os
import signal
import sys

import pytest

pytestmark = pytest.mark.slow

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

# an idle CPU host of this class read: pipelined 0.036 GiB/s at 128MiB,
# the per-row barrier path 0.002 — the floor sits ~2.5x below the
# pipelined reading, an order of magnitude above a
# reintroduced per-row path, and clears CI noise
PIPELINED_FLOOR_GIB_S = 0.015


def test_shuffle_gb_per_s_floor_and_beats_barrier():
    signal.alarm(600)  # tier-1 SIGALRM budget is sized for fast tests
    from envelope_bench import measure_shuffle

    import ray_tpu as rt

    rt.init(num_cpus=4)
    try:
        row = measure_shuffle(rt, mib=128, legacy_mib=16)
    finally:
        rt.shutdown()
    pipelined = row["pipelined"]["gib_per_s"]
    barrier = row["barrier_rows"]["gib_per_s"]
    assert pipelined >= PIPELINED_FLOOR_GIB_S, row
    # the acceptance criterion: the pipelined path beats the old
    # barrier executor on the same leg, at EQUAL dataset size
    assert row["pipelined_at_barrier_size"]["gib_per_s"] > barrier, row
    # and reduce-side folds demonstrably ran while maps were still
    # outstanding (8 blocks, fold_min=4, window 8)
    assert row["reduce_folds_before_maps_done"] > 0, row
