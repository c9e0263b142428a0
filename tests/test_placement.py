"""Placement-plane tests (core/placement.py): topology labels, the
measured-cost greedy placer (PACK/SPREAD/SLICE_PACK), ordered gang
admission (two concurrent gangs at partial capacity never deadlock and
never leak a partial reservation), per-job fair-share quotas, and the
end-to-end placement-quality metric — a gang placed through the plane
compiles its DAG edges onto the preferred (non-DCN) channel kinds."""

import asyncio
import threading
import time

import pytest

import ray_tpu as rt
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core.placement import (GangAdmission, PlacementPlane,
                                    QuotaManager, preferred_kind_summary,
                                    topology_labels)


def _view(total, avail, alive=True, labels=None):
    return {"total": total, "available": avail, "alive": alive,
            "labels": labels or {}, "address": None}


# ------------------------------------------------------------ pure units
def test_topology_labels_env_wins_then_head_resource_inference():
    # explicit env knobs take precedence
    labels = topology_labels({"TPU-v5p-16-head": 1.0},
                             env={"RAYT_ICI_SLICE": "s9",
                                  "RAYT_DCN_LOCALITY": "rack-3"})
    assert labels == {"ici-slice": "s9", "dcn-locality": "rack-3"}
    # otherwise the slice-head custom resource names the slice
    labels = topology_labels({"TPU-v5p-16-head": 1.0, "CPU": 8.0}, env={})
    assert labels == {"ici-slice": "TPU-v5p-16"}
    # neither: unlabeled (anonymous slice)
    assert topology_labels({"CPU": 8.0}, env={}) == {}


def test_preferred_kind_summary_counts_dcn_fallbacks():
    s = preferred_kind_summary([
        {"transport": "shm", "device": False},
        {"transport": "dcn", "device": False},
        {"transport": "shm", "device": True},
        {"transport": "dcn", "device": True},
    ])
    assert s["preferred"] == ["shm", "shm", "device", "device"]
    assert s["matched"] == 2 and s["total"] == 4
    assert s["ratio"] == pytest.approx(0.5)
    assert preferred_kind_summary([])["ratio"] is None


def test_quota_manager_weighted_shares_floor_and_dilution():
    qm = QuotaManager(resource="CPU")
    qm.set_quota("a", weight=3.0)
    qm.set_quota("b", weight=1.0, floor=5.0)
    view = qm.view(cluster_total=16.0, active_jobs=["a", "b"],
                   usage={"a": {"CPU": 2.0}})
    assert view["a"]["share"] == pytest.approx(12.0)
    assert view["a"]["used"] == pytest.approx(2.0)
    # floor lifts b above its weighted 4.0
    assert view["b"]["share"] == pytest.approx(5.0)
    # an active UNQUOTA'D job dilutes shares (default weight 1) but
    # never appears in the enforcement view
    view = qm.view(cluster_total=16.0, active_jobs=["a", "b", "c"],
                   usage={})
    assert set(view) == {"a", "b"}
    assert view["a"]["share"] == pytest.approx(3.0 / 5.0 * 16.0)
    # weight<=0, floor<=0 removes the quota
    qm.set_quota("a", 0.0, 0.0)
    assert "a" not in qm.quotas


def test_placer_pack_spread_and_strict_all_or_nothing():
    views = {
        "n1": _view({"CPU": 4}, {"CPU": 4}),
        "n2": _view({"CPU": 4}, {"CPU": 4}),
        "dead": _view({"CPU": 8}, {"CPU": 8}, alive=False),
        "drain": _view({"CPU": 8}, {"CPU": 8},
                       labels={"draining": "1"}),
    }
    plane = PlacementPlane(views_fn=lambda: views)
    # PACK reuses one node while it fits; dead/draining never placed
    got = plane.place_bundles([{"CPU": 2}] * 2, "PACK")
    assert got is not None and len(set(got)) == 1
    assert set(got) <= {"n1", "n2"}
    # STRICT_PACK refuses a gang that cannot fit one node
    assert plane.place_bundles([{"CPU": 3}] * 2, "STRICT_PACK") is None
    # SPREAD lands one bundle per node
    got = plane.place_bundles([{"CPU": 2}] * 2, "SPREAD")
    assert sorted(got) == ["n1", "n2"]
    # STRICT_SPREAD is all-or-nothing past the node count
    assert plane.place_bundles([{"CPU": 1}] * 3,
                               "STRICT_SPREAD") is None
    # whole-gang atomicity: an unplaceable gang returns None, never a
    # partial list
    assert plane.place_bundles([{"CPU": 4}, {"CPU": 5}], "PACK") is None


def test_placer_cost_order_prefers_quiet_nodes():
    views = {
        "busy": _view({"CPU": 8}, {"CPU": 8}),
        "quiet": _view({"CPU": 8}, {"CPU": 8}),
    }
    pending = {"busy": 7, "quiet": 0}
    plane = PlacementPlane(views_fn=lambda: views,
                           pending_fn=lambda h: pending[h])
    assert plane.place_bundles([{"CPU": 1}], "PACK") == ["quiet"]


def test_slice_pack_keeps_gang_inside_one_slice():
    views = {
        "a1": _view({"CPU": 2}, {"CPU": 2}, labels={"ici-slice": "A"}),
        "a2": _view({"CPU": 2}, {"CPU": 2}, labels={"ici-slice": "A"}),
        "b1": _view({"CPU": 4}, {"CPU": 4}, labels={"ici-slice": "B"}),
    }
    plane = PlacementPlane(views_fn=lambda: views)
    # 4 CPUs fit slice A only across BOTH hosts (multi-host is fine) or
    # slice B on one; every valid answer stays within one slice
    got = plane.place_bundles([{"CPU": 1}] * 4, "SLICE_PACK")
    slices = {views[h]["labels"]["ici-slice"] for h in got}
    assert len(slices) == 1
    # a gang too big for any single slice is refused whole
    assert plane.place_bundles([{"CPU": 1}] * 5, "SLICE_PACK") is None
    # unlabeled clusters degrade to PACK (one shared anonymous slice)
    anon = {"x": _view({"CPU": 2}, {"CPU": 2}),
            "y": _view({"CPU": 2}, {"CPU": 2})}
    plane2 = PlacementPlane(views_fn=lambda: anon)
    assert len(plane2.place_bundles([{"CPU": 1}] * 4,
                                    "SLICE_PACK")) == 4


def test_gang_admission_is_fifo_and_exclusive():
    order = []

    async def gang(adm, name, hold_s):
        async with adm.admit(name):
            order.append(("enter", name))
            await asyncio.sleep(hold_s)
            order.append(("exit", name))

    async def main():
        adm = GangAdmission()
        t1 = asyncio.create_task(gang(adm, "g1", 0.05))
        await asyncio.sleep(0.01)   # g1 holds the window first
        t2 = asyncio.create_task(gang(adm, "g2", 0.0))
        await asyncio.gather(t1, t2)
        return adm

    adm = asyncio.run(main())
    # windows never overlap, and arrival order is admission order
    assert order == [("enter", "g1"), ("exit", "g1"),
                     ("enter", "g2"), ("exit", "g2")]
    assert adm.stats()["admitted"] == 2


# ------------------------------------------------------------ end-to-end
@pytest.fixture(scope="module")
def plane_cluster():
    # head (the driver's node): 4 CPUs, anonymous slice; node B: 2 CPUs
    # in a DIFFERENT labeled slice — SLICE_PACK must never mix them, and
    # B is deliberately SMALLER than every gang below so the plane's
    # choice of the head is deterministic (no cost-order coin flips).
    # "blue" pins baseline actors to node B deterministically.
    cluster = Cluster(head_resources={"CPU": 4.0})
    node_b = cluster.add_node(num_cpus=2, resources={"blue": 4.0},
                              labels={"ici-slice": "remote"})
    cluster.connect()
    try:
        yield cluster, node_b
    finally:
        cluster.shutdown()


def test_node_manager_advertises_topology_labels(plane_cluster):
    _, node_b = plane_cluster
    from ray_tpu import state_api

    st = state_api.placement_state()
    assert st["slices"].get("remote") == [node_b.node_id_hex]
    # the head rides the anonymous slice
    assert len(st["slices"].get("", [])) == 1
    assert st["cluster_total"] == pytest.approx(6.0)


def test_concurrent_gangs_all_or_nothing(plane_cluster):
    """Two gangs each needing >half the 2-node cluster race: exactly one
    reserves; the loser either fails whole or completes AFTER the winner
    releases — and no partial reservation is ever leaked."""
    results = {}

    def reserve(name):
        try:
            results[name] = rt.placement_group(
                [{"CPU": 2.0}] * 2, strategy="PACK", timeout=4.0)
        except TimeoutError:
            results[name] = None

    threads = [threading.Thread(target=reserve, args=(n,))
               for n in ("g1", "g2")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    winners = [n for n, pg in results.items() if pg is not None]
    assert len(winners) == 1, f"expected exactly one winner: {results}"
    loser = "g2" if winners == ["g1"] else "g1"

    # the loser backed off WHOLE: releasing the winner must free the
    # full 6 CPUs, and the loser's retry then fits
    rt.remove_placement_group(results[winners[0]])
    pg = rt.placement_group([{"CPU": 2.0}] * 2, strategy="PACK",
                            timeout=30.0)
    assert len(pg.placement) == 2
    rt.remove_placement_group(pg)
    del loser

    # nothing leaked: every CPU is available again
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        avail = rt.available_resources()
        if avail.get("CPU", 0.0) == pytest.approx(6.0):
            return
        time.sleep(0.2)
    raise AssertionError(
        f"leaked reservation: available={rt.available_resources()}")


def test_plane_placed_dag_compiles_preferred_kinds(plane_cluster):
    """The acceptance gate: a gang that fits one slice, placed through
    the plane, compiles >=90% of its DAG edges onto the preferred
    channel kind; the same DAG over a scattered baseline placement
    measurably pays the DCN fallback."""
    from ray_tpu.core.common import NodeAffinitySchedulingStrategy
    from ray_tpu._internal.ids import NodeID

    @rt.remote(num_cpus=1)
    class Stage:
        def step(self, x):
            return x + 1

    from ray_tpu.dag import InputNode

    def ratio_for(actors):
        with InputNode() as inp:
            out = inp
            for a in actors:
                out = a.step.bind(out)
        dag = out.experimental_compile()
        try:
            assert dag.execute(0).get(timeout=90) == len(actors)
            return dag.preferred_kind_ratio
        finally:
            dag.teardown()
            for a in actors:
                try:
                    rt.kill(a)
                except Exception:
                    pass

    # BASELINE: scatter the pipeline across both nodes ("blue" pins one
    # stage onto node B) — its edges pay the DCN fallback
    scattered = [Stage.remote(),
                 Stage.options(resources={"blue": 1.0}).remote(),
                 Stage.remote()]
    base_ratio = ratio_for(scattered)
    assert base_ratio < 0.9, f"baseline unexpectedly co-located: " \
                             f"{base_ratio}"

    # PLANE: the gang fits one slice; SLICE_PACK advises a single-slice
    # placement and soft affinity pins the actors there. The baseline's
    # actors were killed a moment ago and the plane learns of their freed
    # CPUs with the nodes' next resource report: until then the gang does
    # not fit "right now", so ask again
    advised, deadline = None, time.monotonic() + 30
    while advised is None and time.monotonic() < deadline:
        advised = rt.place_gang([{"CPU": 1.0}] * 3, "SLICE_PACK")
        if advised is None:
            time.sleep(0.2)
    assert advised is not None and len(set(advised)) == 1
    placed = [Stage.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(
            NodeID(bytes.fromhex(h)), soft=True)).remote()
        for h in advised]
    plane_ratio = ratio_for(placed)
    assert plane_ratio >= 0.9, \
        f"plane placement ratio {plane_ratio} < 0.9 (baseline " \
        f"{base_ratio})"
    assert plane_ratio > base_ratio


def test_job_quota_surfaces_and_work_conservation(plane_cluster):
    """A quota'd job with a tiny share still runs alone (enforcement is
    work-conserving: throttling needs a competing tenant), and the
    ledger shows up in cluster_status / placement_state / the GCS
    snapshot path."""
    from ray_tpu import state_api

    rt.set_job_quota(weight=0.001, floor=0.5)
    try:
        @rt.remote(num_cpus=1)
        def burst(i):
            return i * 2

        # far past the 0.5-CPU share — with no other tenant every lease
        # must still be granted
        assert rt.get([burst.remote(i) for i in range(8)],
                      timeout=120) == [i * 2 for i in range(8)]

        job_hex = rt.get_runtime_context().get_job_id()
        status = state_api.cluster_status()
        q = status["quotas"].get(job_hex)
        assert q is not None
        assert q["floor"] == pytest.approx(0.5)
        # the ONLY participant owns the whole weighted pool regardless
        # of its tiny weight — shares divide among active tenants
        assert q["share"] == pytest.approx(6.0)
        st = state_api.placement_state()
        assert job_hex in st["quotas"]
    finally:
        rt.set_job_quota(weight=0.0, floor=0.0)   # remove
    assert rt.get_runtime_context()  # cluster still healthy


# ------------------------------------------------- slow: envelope gate
@pytest.mark.slow
def test_multi_tenant_floor_gate():
    """`tools/envelope_bench.measure_placement` as a gate: three
    concurrent tenant drivers — quota'd serve + train hold their
    throughput floors while an unfloored shuffle tenant bursts, and the
    train gang's DAG compiles onto preferred channel kinds. The leg
    itself asserts the floors; this test asserts the leg and its
    throttle evidence."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from envelope_bench import measure_placement

    cluster = Cluster(head_resources={"CPU": 4.0})
    cluster.add_node(num_cpus=2, labels={"ici-slice": "bench-slice"})
    cluster.connect()
    try:
        out = measure_placement(rt, cluster, seconds=8.0)
    finally:
        cluster.shutdown()
    # the floored tenants held their floors (asserted inside the leg);
    # the plane recorded the tenants' quotas while they ran
    assert len(out["quotas_mid_run"]) >= 2, out["quotas_mid_run"]
    assert out["serve"]["per_s"] > 0 and out["train"]["per_s"] > 0
    ratio = out["preferred_kind_ratio"]
    assert ratio is not None and ratio >= 0.9, out["train"]
