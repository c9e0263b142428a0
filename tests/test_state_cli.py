"""State API + CLI tests (ref analogs: python/ray/tests/test_state_api.py,
`ray status/list/microbenchmark`)."""

import json
import subprocess
import sys

import pytest


def test_state_api_lists(local_cluster):
    import ray_tpu as rt
    from ray_tpu import state_api

    @rt.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    rt.get(a.ping.remote())

    nodes = state_api.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]
    assert nodes[0]["resources"]["TPU"] == 8.0

    actors = state_api.list_actors()
    assert any(x["class_name"] == "A" and x["state"] == "ALIVE"
               for x in actors)

    workers = state_api.list_workers()
    assert any(w.get("actor_id") for w in workers)

    jobs = state_api.list_jobs()
    assert len(jobs) >= 1

    s = state_api.summary()
    assert s["nodes_alive"] == 1
    assert s["actors_by_state"].get("ALIVE", 0) >= 1
    rt.kill(a)


def test_state_api_placement_groups(local_cluster):
    import ray_tpu as rt
    from ray_tpu import state_api

    pg = rt.placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    pgs = state_api.list_placement_groups()
    assert len(pgs) == 1
    assert pgs[0]["strategy"] == "PACK"
    rt.remove_placement_group(pg)
    assert state_api.list_placement_groups() == []


def test_cli_start_status_stop(tmp_path):
    env = dict(__import__("os").environ)
    env["PYTHONPATH"] = "/root/repo"

    def cli(*args, timeout=90):
        return subprocess.run(
            [sys.executable, "-m", "ray_tpu", *args],
            capture_output=True, text=True, env=env, timeout=timeout)

    r = cli("start", "--head", "--num-cpus", "2")
    try:
        assert r.returncode == 0, r.stderr
        assert "address:" in r.stdout
        address = [ln.split()[-1] for ln in r.stdout.splitlines()
                   if "address:" in ln][0]

        r = cli("status", "--address", address)
        assert r.returncode == 0, r.stderr
        assert "nodes: 1/1" in r.stdout

        r = cli("list", "nodes", "--address", address)
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout)[0]["alive"] is True

        # task state API plumbing (empty cluster: no tasks ran yet)
        r = cli("list", "tasks", "--address", address)
        assert r.returncode == 0, r.stderr
        out = json.loads(r.stdout)
        assert out["tasks"] == [] and out["total"] == 0

        r = cli("summary", "tasks", "--address", address)
        assert r.returncode == 0, r.stderr
        assert "0 tasks stored" in r.stdout

        # object state API plumbing (empty cluster: no objects yet)
        r = cli("list", "objects", "--address", address)
        assert r.returncode == 0, r.stderr
        out = json.loads(r.stdout)
        assert out["objects"] == [] and out["total"] == 0

        r = cli("memory", "--address", address)
        assert r.returncode == 0, r.stderr
        assert "0 objects" in r.stdout

        # dag state API plumbing (empty cluster: no DAGs compiled yet)
        r = cli("list", "dags", "--address", address)
        assert r.returncode == 0, r.stderr
        out = json.loads(r.stdout)
        assert out["dags"] == [] and out["total"] == 0

        # cluster event log plumbing: the head's own registration is
        # already an event; severity filter drops INFO
        r = cli("list", "events", "--severity", "INFO",
                "--address", address)
        assert r.returncode == 0, r.stderr
        out = json.loads(r.stdout)
        assert any(e["kind"] == "node_registered" for e in out["events"])
        assert all(e["severity"] != "DEBUG" for e in out["events"])

        # enriched status: node table with heartbeat age + pending
        r = cli("status", "--address", address)
        assert r.returncode == 0, r.stderr
        assert "nodes:" in r.stdout and "hb-age" in r.stdout
        assert "ALIVE" in r.stdout

        # why-pending plumbing (no such task)
        r = cli("why-pending", "deadbeef", "--address", address)
        assert r.returncode == 0, r.stderr
        assert "no task record matches" in r.stdout
    finally:
        r = cli("stop")
        assert r.returncode == 0, r.stderr


def test_cli_task_summary_rendering_live(local_cluster, capsys):
    """`rayt summary tasks` rendering against a live cluster: per-name
    state counts plus the sched-vs-exec latency split columns."""
    import time

    import ray_tpu as rt
    from ray_tpu import state_api
    from ray_tpu.scripts.cli import _print_task_summary

    @rt.remote
    def cli_traced(x):
        return x

    assert rt.get([cli_traced.remote(i) for i in range(2)]) == [0, 1]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        s = state_api.summarize_tasks()
        e = s["by_name"].get("cli_traced")
        if e and e["states"].get("FINISHED") == 2 \
                and e["exec_time_mean_s"] is not None:
            break
        time.sleep(0.3)
    _print_task_summary(s)
    out = capsys.readouterr().out
    assert "2 tasks stored" in out.splitlines()[0]
    assert "sched_mean" in out and "exec_mean" in out
    assert any("cli_traced" in ln and "FINISHED=2" in ln
               for ln in out.splitlines()), out


def test_cli_microbenchmark():
    env = dict(__import__("os").environ)
    env["PYTHONPATH"] = "/root/repo"
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu", "microbenchmark",
         "--duration", "0.3", "--num-cpus", "4"],
        capture_output=True, text=True, env=env, timeout=240)
    assert r.returncode == 0, r.stderr
    assert "tasks_per_second" in r.stdout
    assert "put_get_gigabytes_per_second" in r.stdout


def test_task_event_timeline(local_cluster, tmp_path):
    """Executed tasks land in the GCS event ring and export as a Chrome
    trace (ref analogs: task_event_buffer.cc, `ray timeline`)."""
    import json
    import time

    import ray_tpu as rt
    from ray_tpu import state_api

    @rt.remote
    def traced_work(x):
        return x + 1

    @rt.remote(num_cpus=0)
    class TracedActor:
        def method(self):
            return "m"

    assert rt.get([traced_work.remote(i) for i in range(3)]) == [1, 2, 3]
    a = TracedActor.remote()
    assert rt.get(a.method.remote()) == "m"

    events = []
    for _ in range(40):  # flush loop ships events every ~1s
        events = state_api.task_events()
        names = {e["name"] for e in events}
        if "traced_work" in names and "method" in names:
            break
        time.sleep(0.25)
    names = {e["name"] for e in events}
    assert "traced_work" in names and "method" in names
    kinds = {e["kind"] for e in events}
    assert "task" in kinds and "actor_task" in kinds

    out = str(tmp_path / "trace.json")
    n = state_api.export_timeline(out)
    assert n >= 4
    with open(out) as f:
        trace = json.load(f)
    assert trace["traceEvents"][0]["ph"] == "X"
    assert any(ev["name"] == "traced_work" for ev in trace["traceEvents"])


def test_memory_report_lists_shm_objects(local_cluster):
    """`rayt memory` analog (ref: `ray memory`): shm objects appear with
    sizes and spill/pin flags."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu import state_api

    refs = [rt.put(np.zeros(300_000, np.uint8)) for _ in range(3)]
    s = state_api.memory_summary()
    assert s["num_objects"] >= 3
    assert s["total_bytes"] >= 3 * 300_000
    assert all({"object_id", "size", "spilled", "pinned",
                "node_id"} <= set(o) for o in s["objects"])
    del refs


def test_stack_dump_reaches_workers(local_cluster):
    """`rayt stack` analog: cooperative all-thread dumps from live
    workers (ref: `ray stack` py-spy path, scripts.py:1934)."""
    import time as _t

    import ray_tpu as rt
    from ray_tpu import state_api

    @rt.remote(num_cpus=0)
    class Sleeper:
        def nap(self, t):
            _t.sleep(t)
            return "ok"

    s = Sleeper.remote()
    assert rt.get(s.nap.remote(0), timeout=60) == "ok"  # actor is up
    ref = s.nap.remote(3.0)
    _t.sleep(0.5)
    dumps = state_api.dump_stacks()
    assert dumps, "no worker dumps"
    text = "\n".join(t["stack"] for d in dumps for t in d["threads"])
    assert "nap" in text  # the in-flight actor method is visible
    assert rt.get(ref, timeout=30) == "ok"


def test_profile_worker_cpu_and_memory(local_cluster):
    """On-demand worker profiling (VERDICT r5 missing #8; ref analog:
    dashboard profile_manager py-spy/memray attach): sample a busy
    actor's stacks and memory live over RPC."""
    import ray_tpu as rt
    from ray_tpu import state_api
    from ray_tpu._internal import profiler

    @rt.remote
    class Busy:
        def __init__(self):
            import threading

            def spin():
                while True:
                    self._burn()

            t = threading.Thread(target=spin, name="burner", daemon=True)
            t.start()

        def _burn(self):
            s = 0
            for i in range(5000):
                s += i * i
            return s

        def aid(self):
            from ray_tpu.core.object_ref import get_core_worker

            return get_core_worker().actor_id.hex()

    b = Busy.remote()
    aid = rt.get(b.aid.remote(), timeout=60)

    result = state_api.profile_worker(aid, mode="cpu", duration_s=1.0,
                                      interval_s=0.01)
    assert result["num_samples"] > 10
    collapsed = profiler.render_collapsed(result)
    assert "_burn" in collapsed  # the hot function is visible
    top = profiler.render_top(result)
    assert "samples over" in top

    mem = state_api.profile_worker(aid, mode="memory", duration_s=0.5)
    assert mem["type"] == "memory_window"
    assert isinstance(mem["top_allocations"], list)


def test_every_config_field_is_read():
    """Each `Config` field is a `RAYT_<NAME>` a user can set, so each is
    read somewhere under ray_tpu/ outside config.py: as an attribute, or
    by its quoted name (the `_system_config` dicts and `getattr` calls).
    A name left only in a comment does not count."""
    import dataclasses
    import pathlib
    import re

    import ray_tpu
    from ray_tpu._internal.config import Config

    root = pathlib.Path(ray_tpu.__file__).parent
    source = "\n".join(
        p.read_text() for p in sorted(root.rglob("*.py"))
        if p != root / "_internal" / "config.py")
    unread = [f.name for f in dataclasses.fields(Config)
              if not re.search(rf"""\.{f.name}\b|["']{f.name}["']""", source)]
    assert not unread, f"config fields nothing reads: {unread}"
