"""Scale-envelope push (VERDICT weak #3): 10-30x the sandbox envelope on
one core — >=160 virtual nodes, >=640 actors, >=500 placement groups —
asserting CORRECTNESS (everything registers/answers/places) and BOUNDED
MEMORY of delta resource sync (GCS RSS per heartbeating node) and the
hybrid scheduler (driver RSS per actor/PG).

Slow-marked: the legs are dominated by process spawn on a 1-core box
(each virtual node is a real node_main subprocess).
"""

from __future__ import annotations

import os
import signal
import sys

import pytest

pytestmark = pytest.mark.slow

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

NODES = int(os.environ.get("RAYT_SCALE_NODES", "160"))
ACTORS = int(os.environ.get("RAYT_SCALE_ACTORS", "640"))
PGS = int(os.environ.get("RAYT_SCALE_PGS", "500"))


@pytest.fixture(scope="module")
def scale_cluster():
    # the conftest SIGALRM budget (180s) is sized for tier-1 tests; this
    # module legitimately runs for tens of minutes on one core
    signal.alarm(0)
    # serialized spawn on 1 core: late members of a 640-actor fleet wait
    # minutes for their turn — measure capacity, not spawn latency
    os.environ.setdefault("RAYT_WORKER_STARTUP_TIMEOUT_S", "1800")
    os.environ.setdefault("RAYT_ACTOR_CREATION_PUSH_TIMEOUT_S", "2400")
    os.environ.setdefault("RAYT_LEASE_TIMEOUT_S", "600")

    import ray_tpu as rt
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_resources={"CPU": 4.0})
    try:
        yield cluster, rt
    finally:
        cluster.shutdown()


def _alarm(seconds: int):
    signal.alarm(seconds)


def test_scale_nodes_register_with_bounded_gcs_memory(scale_cluster):
    from envelope_bench import rss_kb

    cluster, rt = scale_cluster
    _alarm(1800)
    head_rss0 = rss_kb(cluster.head_proc.pid)
    for _ in range(NODES - 1):
        cluster.add_node(num_cpus=2, startup_timeout_s=120.0)
    view = cluster._cluster_view()
    alive = sum(1 for v in view.values() if v.get("alive"))
    assert alive >= NODES, f"only {alive}/{NODES} nodes alive"
    import time

    time.sleep(3.0)  # several delta-sync rounds at full cluster size
    per_node_kb = (rss_kb(cluster.head_proc.pid) - head_rss0) / NODES
    # delta resource sync must not hoard per-node history: the GCS pays
    # a node table entry + resource view per node, far under 2MB each
    assert per_node_kb < 2048, f"GCS grew {per_node_kb:.0f}KB per node"


def test_scale_actor_fleet_all_answer(scale_cluster):
    from envelope_bench import rss_kb

    cluster, rt = scale_cluster
    _alarm(2400)
    cluster.connect()

    @rt.remote(num_cpus=0.01)
    class Trivial:
        def ping(self):
            return 1

    rss0 = rss_kb()
    actors = [Trivial.remote() for _ in range(ACTORS)]
    assert all(rt.get([a.ping.remote() for a in actors], timeout=2000))
    per_actor_kb = (rss_kb() - rss0) / ACTORS
    for a in actors:
        rt.kill(a)
    # driver-side actor bookkeeping (handles, submitter state) stays
    # small per actor; worker processes live in their own RSS
    assert per_actor_kb < 512, f"driver grew {per_actor_kb:.0f}KB/actor"


def test_scale_placement_groups_reserve_and_release(scale_cluster):
    from envelope_bench import rss_kb

    cluster, rt = scale_cluster
    _alarm(1800)
    rss0 = rss_kb()
    pgs = [rt.placement_group([{"CPU": 0.01}], strategy="PACK")
           for _ in range(PGS)]
    assert all(pg.placement for pg in pgs), "unplaced PGs in storm"
    per_pg_kb = (rss_kb() - rss0) / PGS
    for pg in pgs:
        rt.remove_placement_group(pg)
    assert per_pg_kb < 256, f"driver grew {per_pg_kb:.0f}KB/PG"
    # hybrid scheduler correctness after the storm: resources released
    @rt.remote(num_cpus=1)
    def probe():
        return os.getpid()

    assert rt.get(probe.remote(), timeout=120) > 0
