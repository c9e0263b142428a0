"""Drivers for the envelope, shuffle, placement and chaos tests
(`tests/test_scale_envelope.py`, `test_shuffle_envelope.py`,
`test_placement.py`, `test_chaos.py`).

Each `measure_*` function drives one drill on the cluster the test hands
it and returns a dict of counts and host-clock readings for the test to
assert on; `rss_kb` reads a process's resident memory. Nothing is written
to disk, and no number from here is a chip metric.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def rss_kb(pid: int = 0) -> int:
    """VmRSS of `pid` (default: this process) in KB; 0 if unreadable."""
    try:
        with open(f"/proc/{pid or os.getpid()}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


def measure_shuffle(rt, *, mib: int = 128, legacy_mib: int = 32,
                    blocks: int = 8, timeout: float = 1200.0) -> dict:
    """Data-plane shuffle bandwidth: a columnar dataset random_shuffled
    through the pipelined exchange (data/exchange.py — columnar
    partition kernels, streaming reduce folds) vs the pre-exchange
    BARRIER executor (per-row dict sharding, every reduce waiting on
    every map). The legacy leg runs at a smaller size — its per-row
    path is orders of magnitude slower and the GB/s rate is what's
    compared. Bytes counted once through the exchange (map+reduce)."""
    import random

    from ray_tpu.data.block import NumpyBlock, concat_blocks, iter_rows
    from ray_tpu.data.executor import StreamingExecutor

    def mk_refs(total_mib: int):
        rows = total_mib * (1 << 20) // 8 // blocks  # one float64 column
        refs = [rt.put(NumpyBlock(
            {"v": np.random.default_rng(i).random(rows)}))
            for i in range(blocks)]
        return refs, rows * blocks * 8

    def drain(refs):
        ready, _ = rt.wait(refs, num_returns=len(refs), timeout=timeout)
        assert len(ready) == len(refs), "shuffle did not complete"

    ex = StreamingExecutor()
    refs, nbytes = mk_refs(mib)
    t0 = time.monotonic()
    drain(ex.random_shuffle(refs, seed=1))
    dt = time.monotonic() - t0
    pipelined = nbytes / (1 << 30) / dt
    stats = ex.last_exchange

    # pipelined AT THE BARRIER LEG'S SIZE: rates aren't size-invariant
    # (fixed task overheads dominate small runs), so the recorded
    # speedup compares equal datasets
    refs, nbytes_small = mk_refs(legacy_mib)
    t0 = time.monotonic()
    drain(ex.random_shuffle(refs, seed=1))
    pipelined_small = nbytes_small / (1 << 30) / (time.monotonic() - t0)

    # the old barrier executor, verbatim shape: rows shard one dict at a
    # time, and every reduce task depends on EVERY map task's output
    def shard(block, n, seed):
        rng = random.Random(seed)
        shards = [[] for _ in range(n)]
        for row in iter_rows(block):
            shards[rng.randrange(n)].append(row)
        return shards

    def reduce_shards(seed, *shards):
        out = concat_blocks(shards)
        random.Random(seed).shuffle(out)
        return out

    refs, nbytes_legacy = mk_refs(legacy_mib)
    n = len(refs)
    shard_task = rt.remote(num_cpus=1, num_returns=n)(shard)
    reduce_task = rt.remote(num_cpus=1)(reduce_shards)
    t0 = time.monotonic()
    parts = []
    for i, ref in enumerate(refs):
        res = shard_task.remote(ref, n, 1 + i)
        parts.append(res if isinstance(res, list) else [res])
    drain([reduce_task.remote(10_001 + j, *[p[j] for p in parts])
           for j in range(n)])
    dt_legacy = time.monotonic() - t0
    barrier = nbytes_legacy / (1 << 30) / dt_legacy

    return {
        "blocks": blocks,
        "pipelined": {"mib": mib, "gib_per_s": round(pipelined, 3)},
        "pipelined_at_barrier_size": {
            "mib": legacy_mib, "gib_per_s": round(pipelined_small, 3)},
        "barrier_rows": {"mib": legacy_mib,
                         "gib_per_s": round(barrier, 3)},
        # same-size comparison (cross-size ratios flatter the big run)
        "speedup_same_size": round(pipelined_small / barrier, 1)
            if barrier else None,
        # folds only launch while the map side is unfinished, so this
        # count is reduce work that ran before all maps completed
        "reduce_folds_before_maps_done": stats.folds if stats else 0,
    }


# ------------------------------------------------------ placement leg
# Multi-tenant fair-share drill (placement plane, core/placement.py):
# three DRIVERS — each its own job, hence its own quota — run
# concurrently: a serve-shaped tenant (small latency-sensitive tasks,
# quota floor), a train-shaped tenant (gang placed through the plane,
# compiled-DAG ticks, quota floor), and an unfloored shuffle tenant
# bursting wide task waves. The gate: the floored tenants keep making
# progress while the burst saturates the cluster, and the train gang's
# DAG compiles onto preferred (non-DCN) channel kinds.

_SERVE_TENANT = """
import json, sys, time
import ray_tpu as rt
addr, T = sys.argv[1], float(sys.argv[2])
rt.init(address=addr)
rt.set_job_quota(weight=2.0, floor=1.0)
@rt.remote(num_cpus=0.5)
def handle(i):
    time.sleep(0.02)
    return i
t0 = time.monotonic(); done = 0
while time.monotonic() - t0 < T:
    done += len(rt.get([handle.remote(i) for i in range(2)],
                       timeout=300))
print(json.dumps({"done": done,
                  "wall_s": round(time.monotonic() - t0, 2),
                  "job": rt.get_runtime_context().get_job_id()}))
rt.shutdown()
"""

_TRAIN_TENANT = """
import json, sys, time
import ray_tpu as rt
from ray_tpu.dag import InputNode
from ray_tpu.core.common import NodeAffinitySchedulingStrategy
from ray_tpu._internal.ids import NodeID
addr, T = sys.argv[1], float(sys.argv[2])
rt.init(address=addr)
rt.set_job_quota(weight=2.0, floor=1.0)
@rt.remote(num_cpus=1)
class Stage:
    def step(self, x):
        return x + 1
advised = rt.place_gang([{"CPU": 1.0}] * 2, "SLICE_PACK") or []
opts = [{"scheduling_strategy": NodeAffinitySchedulingStrategy(
             NodeID(bytes.fromhex(h)), soft=True)} for h in advised]
if len(opts) != 2:
    opts = [{}, {}]
a = Stage.options(**opts[0]).remote()
b = Stage.options(**opts[1]).remote()
with InputNode() as inp:
    out = b.step.bind(a.step.bind(inp))
dag = out.experimental_compile()
t0 = time.monotonic(); ticks = 0
while time.monotonic() - t0 < T:
    assert dag.execute(ticks).get(timeout=300) == ticks + 2
    ticks += 1
print(json.dumps({"ticks": ticks,
                  "wall_s": round(time.monotonic() - t0, 2),
                  "advised_one_node": len(set(advised)) == 1,
                  "preferred_kind_ratio": dag.preferred_kind_ratio,
                  "job": rt.get_runtime_context().get_job_id()}))
dag.teardown()
rt.shutdown()
"""

_SHUFFLE_TENANT = """
import json, sys, time
import ray_tpu as rt
addr, T = sys.argv[1], float(sys.argv[2])
rt.init(address=addr)
rt.set_job_quota(weight=0.25)   # burst tenant: low weight, NO floor
@rt.remote(num_cpus=1)
def chunk(i):
    time.sleep(0.05)
    return i
t0 = time.monotonic(); done = 0
while time.monotonic() - t0 < T:
    done += len(rt.get([chunk.remote(i) for i in range(12)],
                       timeout=600))
print(json.dumps({"done": done,
                  "wall_s": round(time.monotonic() - t0, 2),
                  "job": rt.get_runtime_context().get_job_id()}))
rt.shutdown()
"""


def measure_placement(rt, cluster, *, seconds: float = 8.0) -> dict:
    """Multi-tenant placement-plane leg: serve + train + shuffle drivers
    (distinct jobs -> distinct quotas) concurrent on a small labeled
    cluster. Records per-tenant throughput, the quota ledger observed
    mid-run, cumulative quota-throttle verdicts, and the train DAG's
    preferred-channel-kind fraction."""
    import subprocess

    from ray_tpu import state_api

    # a labeled slice so SLICE_PACK has real topology to group by (the
    # earlier legs' nodes are unlabeled -> one anonymous slice)
    view = cluster._cluster_view()
    if not any((v.get("labels") or {}).get("ici-slice")
               for v in view.values()):
        cluster.add_node(num_cpus=2,
                         labels={"ici-slice": "bench-slice"})

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    env.setdefault("JAX_PLATFORMS", "cpu")
    def spawn(script):
        return subprocess.Popen(
            [sys.executable, "-c", script, cluster.address,
             str(seconds)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)

    # train first: its gang placement + DAG compile run against an idle
    # cluster (the measured-cost order is then deterministic — pending
    # depth from an already-running burst would shove the gang off the
    # driver's node and the preferred-kind fraction would measure the
    # race, not the placer)
    procs = {"train": spawn(_TRAIN_TENANT)}
    time.sleep(2.0)
    procs["serve"] = spawn(_SERVE_TENANT)
    procs["shuffle"] = spawn(_SHUFFLE_TENANT)

    # poll the plane WHILE tenants run: job-finish scrubs a job's quota
    # + throttle ledger, so the mid-run view is the evidence
    quotas_seen: dict = {}
    throttled_seen: dict = {}
    deadline = time.monotonic() + seconds + 120.0
    while any(p.poll() is None for p in procs.values()) \
            and time.monotonic() < deadline:
        try:
            st = state_api.placement_state()
            for j, q in (st.get("quotas") or {}).items():
                quotas_seen[j] = q
            for j, n in (st.get("quota_throttled") or {}).items():
                throttled_seen[j] = max(throttled_seen.get(j, 0), n)
        except Exception:
            pass
        time.sleep(0.5)

    tenants = {}
    for name, p in procs.items():
        out, _ = p.communicate(timeout=60)
        assert p.returncode == 0, f"{name} tenant driver failed"
        tenants[name] = json.loads(out.strip().splitlines()[-1])

    # floors: the quota'd serve/train tenants kept making progress while
    # the burst saturated the cluster
    assert tenants["serve"]["done"] >= 2 * seconds, tenants["serve"]
    assert tenants["train"]["ticks"] >= seconds / 2, tenants["train"]
    assert tenants["shuffle"]["done"] > 0, tenants["shuffle"]

    per_s = {n: round(
        (t.get("done", t.get("ticks", 0))) / t.get("wall_s", seconds),
        2) for n, t in tenants.items()}
    return {
        "seconds": seconds,
        "serve": {**tenants["serve"], "per_s": per_s["serve"]},
        "train": {**tenants["train"], "per_s": per_s["train"]},
        "shuffle": {**tenants["shuffle"], "per_s": per_s["shuffle"]},
        "preferred_kind_ratio":
            tenants["train"].get("preferred_kind_ratio"),
        "quotas_mid_run": quotas_seen,
        "quota_throttled": throttled_seen,
    }


# ---------------------------------------------------------- chaos legs
# Recovery SLOs under injected faults (tools/chaos.py; ref analog: the
# nightly chaos suites — kill things on a cadence under load, assert
# the workload's recovery envelope, not just survival).

def measure_chaos_tasks(rt, cluster, *, tasks: int = 40) -> dict:
    """SLO: every submitted task completes despite a sudden node loss
    mid-flight (task retries + lineage re-execution of lost objects)."""
    from chaos import ChaosMonkey

    node = cluster.add_node(num_cpus=2)

    @rt.remote(num_cpus=0.5, scheduling_strategy="SPREAD")
    def work(i):
        time.sleep(0.3)
        return i

    monkey = ChaosMonkey(cluster)
    refs = [work.remote(i) for i in range(tasks)]
    monkey.at(0.5, monkey.kill_worker_node,
              cluster.worker_nodes.index(node)).start()
    t0 = time.monotonic()
    got = rt.get(refs, timeout=300)
    wall = time.monotonic() - t0
    monkey.stop()
    assert sorted(got) == list(range(tasks)), got
    assert all(e["ok"] for e in monkey.log), monkey.log
    return {"tasks": tasks, "completed": len(got), "nodes_killed": 1,
            "wall_s": round(wall, 2)}


def measure_chaos_dag(rt, *, ticks: int = 10,
                      kill_at_tick: int = 3) -> dict:
    """SLO: a compiled-DAG ring runner killed mid-tick — the driver
    detects the death, recompiles the ring and resumes (epoch bump);
    every tick's result still arrives (in-flight ticks re-run from the
    driver's retained inputs)."""
    from chaos import ChaosMonkey

    from ray_tpu.dag import InputNode
    from ray_tpu.dag.recovery import RecoverableDag

    @rt.remote(num_cpus=0.1, max_restarts=-1)
    class Stage:
        def step(self, x):
            return x + 1

    a, b = Stage.remote(), Stage.remote()

    def compile_fn(epoch=0, recovered_from=""):
        with InputNode() as inp:
            out = b.step.bind(a.step.bind(inp))
        return out.experimental_compile(
            epoch=epoch, recovered_from=recovered_from)

    dag = RecoverableDag(compile_fn, name="chaos-ring")
    monkey = ChaosMonkey()
    results = []
    t0 = time.monotonic()
    for i in range(ticks):
        ref = dag.execute(i)
        if i == kill_at_tick:
            monkey.kill_actor(a)   # synchronous mid-tick injection
        results.append(ref.get(timeout=180))
    wall = time.monotonic() - t0
    recoveries, epoch = dag.recoveries, dag.epoch
    dag.teardown()
    for h in (a, b):
        rt.kill(h)
    assert results == [i + 2 for i in range(ticks)], results
    assert recoveries >= 1, "runner death went undetected"
    return {"ticks": ticks, "ticks_lost": 0, "recoveries": recoveries,
            "epoch": epoch,
            # teardown -> restart -> recompile -> resume wall time, as
            # measured by the recovery engine itself (timing the kill
            # tick's get() undercounts: pipelining may have buffered it)
            "recovery_s": round(dag.last_recovery_s, 2),
            "wall_s": round(wall, 2)}


def measure_chaos_serve(rt, *, load_s: float = 8.0,
                        drivers: int = 2) -> dict:
    """SLO: serve controller killed under load — ZERO failed requests
    (handles keep routing on their last table, then self-heal the
    controller, which restores its checkpoint and adopts the live
    replicas instead of cold-starting new ones)."""
    import threading

    from chaos import ChaosMonkey

    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    def echo(x):
        return x

    handle = serve.run(echo.bind(), name="chaos_app")
    assert handle.remote(0).result(timeout=30) == 0
    before = set()
    with handle._router.lock:
        before = {r._actor_id.hex() for r in handle._replicas}

    stats = {"ok": 0, "fail": 0}
    stop = threading.Event()

    def drive():
        i = 0
        while not stop.is_set():
            try:
                assert handle.remote(i).result(timeout=60) == i
                stats["ok"] += 1
            except Exception:
                stats["fail"] += 1
            i += 1

    threads = [threading.Thread(target=drive, daemon=True)
               for _ in range(drivers)]
    for t in threads:
        t.start()
    try:
        monkey = ChaosMonkey()
        time.sleep(1.0)
        t_kill = time.monotonic()
        monkey.kill_serve_controller()
        restored_s = None
        deadline = time.monotonic() + load_s
        while time.monotonic() < deadline:
            if restored_s is None:
                try:
                    c = serve._controller(create=False)
                    rt.get(c.list_applications.remote(), timeout=5)
                    restored_s = time.monotonic() - t_kill
                except Exception:
                    pass
            time.sleep(0.25)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    with handle._router.lock:
        after = {r._actor_id.hex() for r in handle._replicas}
    serve.shutdown()
    assert stats["fail"] == 0, stats
    assert restored_s is not None, "controller never came back"
    return {"requests": stats["ok"], "failed": stats["fail"],
            "controller_restored_s": round(restored_s, 2),
            "replicas_adopted": len(before & after),
            "replicas": len(before)}
