"""Chaos suite: fault-injection recovery drills (tools/chaos.py).

Every test here kills something real — a worker node, a DAG ring
runner, the serve controller, the head — and asserts the RECOVERY SLO,
not mere survival: tasks re-execute via lineage, compiled DAGs
recompile-and-resume with zero lost ticks, serve rides a controller or
head bounce with zero failed requests and adopted (not cold-started)
replicas.

Slow+chaos marked: excluded from the tier-1 `-m "not slow"` run but
each leg fits the tier-1 per-test budget, so `pytest -m chaos` is a
usable local gate. The drills are the `measure_chaos_*` drivers of
tools/envelope_bench."""

from __future__ import annotations

import os
import sys
import time

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))


@pytest.fixture
def fast_recovery(monkeypatch):
    """Shrink detection cadences so recovery drills finish in seconds
    (probe liveness every 1s instead of 5s; fast stall attribution)."""
    monkeypatch.setenv("RAYT_DAG_RECOVERY_PROBE_S", "1.0")
    monkeypatch.setenv("RAYT_DAG_STALL_GRACE_S", "1.0")
    monkeypatch.setenv("RAYT_DAG_STATE_REPORT_INTERVAL_S", "0.25")
    from ray_tpu._internal import config as cfg_mod

    old = cfg_mod._config
    cfg_mod.set_config(cfg_mod.load_config())
    yield
    cfg_mod._config = old


@pytest.fixture
def chaos_cluster(fast_recovery):
    import ray_tpu

    ray_tpu.init(num_cpus=4)
    try:
        yield ray_tpu
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------ worker-kill smoke
def test_worker_kill_tasks_reexecute(fast_recovery):
    """Sudden node loss under a task load: every task still completes
    (retries + lineage re-execution) — the envelope leg's smoke twin."""
    import ray_tpu as rt
    from envelope_bench import measure_chaos_tasks
    from ray_tpu.cluster_utils import Cluster

    with Cluster(head_resources={"CPU": 4.0}) as cluster:
        cluster.connect()
        out = measure_chaos_tasks(rt, cluster, tasks=20)
    assert out["completed"] == 20
    assert out["nodes_killed"] == 1


def test_lineage_reexecution_on_node_death(fast_recovery, tmp_path):
    """Satellite: the node holding a shm object's ONLY copy dies while
    the driver holds just the ObjectRef — rt.get must re-execute the
    producer from retained lineage (core_worker _maybe_recover_object
    path), observed via an execution-count marker file."""
    import numpy as np

    import ray_tpu as rt
    from chaos import ChaosMonkey
    from ray_tpu.cluster_utils import Cluster

    marker = str(tmp_path / "runs")
    with Cluster(head_resources={"CPU": 2.0}) as cluster:
        node_b = cluster.add_node(num_cpus=2, resources={"red": 2.0})
        cluster.connect()

        @rt.remote(num_cpus=1, resources={"red": 1.0}, max_retries=2)
        def make(path):
            with open(path, "a") as f:
                f.write("x")
            return np.full(1 << 20, 3, dtype=np.uint8)

        ref = make.remote(marker)
        # wait WITHOUT get: a get would pull a copy into the head
        # node's store and defeat the all-copies-lost scenario
        ready, _ = rt.wait([ref], num_returns=1, timeout=90)
        assert ready
        assert open(marker).read() == "x"
        monkey = ChaosMonkey(cluster)
        monkey.kill_worker_node(cluster.worker_nodes.index(node_b))
        cluster.add_node(num_cpus=2, resources={"red": 2.0})
        arr = rt.get(ref, timeout=120)
        assert int(arr[0]) == 3 and arr.size == (1 << 20)
        assert open(marker).read() == "xx"  # producer really re-ran


# ------------------------------------------------------ runner-kill smoke
def test_runner_kill_dag_recovers(chaos_cluster):
    """A ring runner killed mid-tick: the RecoverableDag detects it,
    recompiles and resumes — every tick's result arrives exactly once
    (the epoch stamp discards stale pre-failure frames)."""
    import ray_tpu as rt
    from envelope_bench import measure_chaos_dag

    out = measure_chaos_dag(rt, ticks=8, kill_at_tick=2)
    assert out["recoveries"] >= 1
    assert out["ticks_lost"] == 0
    assert out["epoch"] >= 1


def test_dag_recovery_respawns_unrestartable_runner(chaos_cluster):
    """An actor with NO restarts left dies terminally: the default
    policy would fail, but a recover_cb that respawns a replacement
    from the spec rebuilds the ring over the new actor."""
    import ray_tpu as rt
    from ray_tpu.dag import InputNode
    from ray_tpu.dag.recovery import RecoverableDag

    @rt.remote(num_cpus=0.1)            # max_restarts=0: death is final
    class Stage:
        def step(self, x):
            return x * 10

    actors = [Stage.remote()]

    def compile_fn(epoch=0, recovered_from=""):
        with InputNode() as inp:
            out = actors[0].step.bind(inp)
        return out.experimental_compile(
            epoch=epoch, recovered_from=recovered_from)

    def recover_cb(failed):
        actors[0] = Stage.remote()      # respawn from the spec

    dag = RecoverableDag(compile_fn, recover_cb=recover_cb,
                         name="respawn")
    try:
        assert dag.execute(1).get(timeout=60) == 10
        rt.kill(actors[0], no_restart=True)
        assert dag.execute(2).get(timeout=90) == 20
        assert dag.recoveries == 1
        assert dag.epoch == 1
    finally:
        dag.teardown()


# --------------------------------------------------- IMPALA mid-tick E2E
def test_impala_kill_runner_mid_tick_keeps_learning(chaos_cluster):
    """Acceptance E2E: compiled-DAG IMPALA loses an env runner mid-tick,
    detects the dead peer, recompiles, resumes — and still LEARNS, with
    no fallback off the channel-DAG plane."""
    from chaos import ChaosMonkey
    from ray_tpu.dag.channel_exec import ChannelCompiledDAG
    from ray_tpu.rl import IMPALAConfig

    algo = IMPALAConfig(
        env="CartPole-v1", num_env_runners=2, num_envs_per_runner=8,
        rollout_fragment_length=64, train_batch_size=512, vf_coeff=0.25,
        lr=1e-3, entropy_coeff=0.01, seed=1).build()
    try:
        assert isinstance(algo._dag.dag, ChannelCompiledDAG)
        algo.train()                    # warmup (jit compile)
        monkey = ChaosMonkey()
        monkey.at(0.3, monkey.kill_actor,
                  algo._runners._actors[0]).start()
        best = 0.0
        for _ in range(40):
            result = algo.train()
            best = max(best, result["episode_return_mean"])
            if best >= 80.0 and algo._dag.recoveries >= 1:
                break
        monkey.stop()
        assert all(e["ok"] for e in monkey.log), monkey.log
        assert algo._dag.recoveries >= 1, "runner death went undetected"
        assert isinstance(algo._dag.dag, ChannelCompiledDAG), \
            "IMPALA fell back off the compiled-DAG plane"
        assert best >= 80.0, f"IMPALA stopped learning: best={best}"
    finally:
        algo.stop()


# ------------------------------------------------- serve controller E2E
def test_serve_controller_bounce_zero_request_failures(chaos_cluster):
    """Acceptance E2E: the controller dies under load — zero admitted
    requests fail (handles route on their last table, self-heal the
    controller, which restores its checkpoint and ADOPTS the live
    replicas instead of cold-starting a new fleet)."""
    import ray_tpu as rt
    from envelope_bench import measure_chaos_serve

    out = measure_chaos_serve(rt, load_s=6.0)
    assert out["failed"] == 0, out
    assert out["requests"] > 0
    assert out["replicas_adopted"] == out["replicas"], \
        "restored controller cold-started replicas instead of adopting"


def test_impala_preemption_notice_drains_runner_node(
        fast_recovery, monkeypatch, tmp_path):
    """Acceptance E2E: a preemption notice lands mid-IMPALA — the node
    manager self-initiates a drain, the ring runners on the doomed node
    fail over make-before-break, the RecoverableDag recompiles over the
    migrated actors, and training keeps learning with zero lost ticks
    (every train() call returns a result; no fallback off the
    channel-DAG plane)."""
    import json

    import ray_tpu as rt
    from ray_tpu import state_api
    from ray_tpu._internal import config as cfg_mod
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.dag.channel_exec import ChannelCompiledDAG
    from ray_tpu.rl import IMPALAConfig

    monkeypatch.setenv("RAYT_PREEMPTION_NOTICE_FILE",
                       str(tmp_path / "notice-{node_id}"))
    monkeypatch.setenv("RAYT_PREEMPTION_POLL_INTERVAL_S", "0.2")
    cfg_mod.set_config(cfg_mod.load_config())

    with Cluster(head_resources={"CPU": 6.0}) as cluster:
        node_b = cluster.add_node(num_cpus=6)
        cluster.connect()
        algo = IMPALAConfig(
            env="CartPole-v1", num_env_runners=2, num_envs_per_runner=8,
            rollout_fragment_length=64, train_batch_size=512,
            vf_coeff=0.25, lr=1e-3, entropy_coeff=0.01, seed=1).build()
        try:
            assert isinstance(algo._dag.dag, ChannelCompiledDAG)
            algo.train()                # warmup (jit compile)
            # aim the notice at a node hosting a RUNNER (restartable ->
            # the drain migrates it); prefer the worker node, which the
            # learner (max_restarts=0, left in place) tends not to share
            runner_ids = {a._actor_id.hex()
                          for a in algo._runners._actors}
            rows = [a for a in state_api.list_actors(state="ALIVE")
                    if a["actor_id"] in runner_ids]
            nodes = {a["node_id"] for a in rows if a["node_id"]}
            assert nodes, "no live runners found"
            victim = (node_b.node_id_hex
                      if node_b.node_id_hex in nodes else nodes.pop())
            with open(str(tmp_path / f"notice-{victim}"), "w") as f:
                json.dump({"deadline_s": 60.0,
                           "reason": "maintenance event"}, f)
            best = 0.0
            for _ in range(40):
                result = algo.train()   # zero lost ticks: every call
                assert result is not None   # returns a real result
                best = max(best, result["episode_return_mean"])
                if best >= 80.0 and algo._dag.recoveries >= 1:
                    break
            rec = state_api.drain_status().get(victim)
            assert rec is not None, "notice never became a drain"
            assert rec["state"] in ("DRAINING", "DRAINED"), rec
            assert rec["reason"] == "maintenance event"
            assert algo._dag.recoveries >= 1, \
                "drain migration never reached the DAG"
            assert isinstance(algo._dag.dag, ChannelCompiledDAG), \
                "IMPALA fell back off the compiled-DAG plane"
            assert best >= 80.0, f"IMPALA stopped learning: best={best}"
            # the migrated runners really left the doomed node
            rows = [a for a in state_api.list_actors(state="ALIVE")
                    if a["actor_id"] in runner_ids]
            assert rows and all(a["node_id"] != victim for a in rows), \
                rows
        finally:
            algo.stop()


def test_serve_survives_head_bounce(fast_recovery, tmp_path):
    """Handles ride a HEAD bounce: the GCS restarts from its snapshot,
    the client reconnect fires the handle's on_reconnect hook (full
    table resync), and requests flow again with the same replicas."""
    import ray_tpu as rt
    from chaos import ChaosMonkey
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(gcs_only_head=True,
                      persist_path=str(tmp_path / "gcs.snap"))
    cluster.add_node(num_cpus=4)
    cluster.connect()
    try:
        @serve.deployment(num_replicas=2)
        def echo(x):
            return x

        handle = serve.run(echo.bind(), name="ha")
        assert handle.remote(1).result(timeout=60) == 1
        time.sleep(0.5)                # snapshot flush (100ms debounce)
        monkey = ChaosMonkey(cluster)
        monkey.bounce_head(down_s=0.5)
        time.sleep(2.5)                # node re-register + reconnect
        for i in range(5):
            assert handle.remote(i).result(timeout=60) == i
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()
