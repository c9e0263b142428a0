"""One process per chip, decided by the lease (node_manager._spawn_worker).

A worker may leave the CPU if and only if its task or actor was granted
TPU > 0. The sandbox has no chip, so these assert on what a worker is
handed — the JAX_PLATFORMS it would give jax — and on what jax does with
it, not on a device.
"""

import asyncio
import errno
import os
import subprocess
import sys
import time
import types

import pytest

import ray_tpu as rt
from ray_tpu import state_api
from ray_tpu._internal import accelerators
from ray_tpu._internal.spawn import (COMPILE_CACHE_ENV, child_env,
                                     compile_cache_dir, jax_platforms_env)
from ray_tpu.core import node_manager
from ray_tpu.core.node_manager import NodeManager, _holds_tpu


class Probe:
    def view(self):
        return {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
                "jax_imported": "jax" in sys.modules, "pid": os.getpid()}

    def touch_jax(self):
        import jax

        return jax.devices()[0].platform


def _view():
    return Probe().view()


@pytest.fixture(scope="module")
def one_chip_node():
    """A node that advertises one chip and whose operator set no
    JAX_PLATFORMS: the situation on a TPU VM."""
    mp = pytest.MonkeyPatch()
    mp.delenv("JAX_PLATFORMS")
    rt.init(num_cpus=4, resources={"TPU": 1})
    try:
        yield
    finally:
        rt.shutdown()
        mp.undo()


def _tpu_workers():
    return [w for w in state_api.list_workers() if w.get("tpu")]


def _gone(pid, timeout=20.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return True
        time.sleep(0.1)
    return False


@pytest.mark.parametrize("node,leased,want", [
    (None, False, "cpu"),          # no lease: pinned, whatever the node says
    ("tpu,cpu", False, "cpu"),
    (None, True, "tpu,cpu"),       # nothing set: named, so a failure raises
    ("", True, "tpu,cpu"),
    ("tpu", True, "tpu"),          # the operator's setting is kept
    ("cpu", True, "cpu"),          # an explicit CPU platform (the tests)
])
def test_platform_handed_to_a_worker(node, leased, want):
    assert jax_platforms_env(node, leased) == want


@pytest.mark.parametrize("demand,holds", [
    ({"CPU": 1.0}, False),
    ({"CPU": 1.0, "TPU": 1.0}, True),
    ({"TPU": 0.0}, False),
    ({"TPU_pg_ab12_0": 4.0}, True),          # through a placement group
    ({"TPU-v5litepod-4-head": 1.0}, False),  # a slice's coordinator
])
def test_which_demands_hold_a_chip(demand, holds):
    assert _holds_tpu(demand) is holds


@pytest.mark.parametrize("refused,held,want", [
    ((), (), []),                  # every group opens: the chips are free
    (("1",), (), ["1"]),           # refused and nobody has it: in teardown
    (("0", "1"), ("1",), ["0"]),   # refused and somebody has it: theirs
    (("0",), ("0",), []),
], ids=["free", "released", "one-held", "held"])
def test_groups_refused_with_no_holder_are_being_released(
        tmp_path, monkeypatch, refused, held, want):
    """`chips_being_released`: a vfio group that answers EBUSY while no
    process has it open is a killed owner's, still torn down by the
    kernel (what the four-chip train cell's next run met: PERF.md,
    PR 39). Files stand in for the groups; this process holds `held`."""
    for name in ("0", "1", "vfio"):
        (tmp_path / name).write_bytes(b"")
    mine = [os.open(tmp_path / name, os.O_RDWR) for name in held]
    real = os.open

    def open_(path, flags, *a, **kw):
        if os.path.basename(path) in refused:
            raise OSError(errno.EBUSY, "Device or resource busy", str(path))
        return real(path, flags, *a, **kw)

    monkeypatch.setattr(accelerators.os, "open", open_)
    try:
        assert accelerators.chips_being_released(str(tmp_path)) == [
            str(tmp_path / name) for name in want]
    finally:
        for fd in mine:
            os.close(fd)
    # a host with no such directory has no group to wait for
    assert accelerators.chips_being_released(str(tmp_path / "none")) == []


@pytest.mark.parametrize("tpu,busy_polls,polled", [
    (True, 2, 3),         # waits while they are busy, then starts
    (True, None, None),   # busy for half its time: starts all the same
    (False, None, 0),     # no lease on chips: never asks
], ids=["waits", "gives-up-waiting", "cpu-worker"])
def test_a_chip_worker_starts_once_the_chips_are_free(
        monkeypatch, tpu, busy_polls, polled):
    """A group that stays busy is held from where this node cannot see
    (another container's process): the wait has an end, the worker is
    started with the rest of the time, and jax says what it finds."""
    polls = []

    def being_released():
        polls.append(time.monotonic())
        busy = busy_polls is None or len(polls) <= busy_polls
        return ["/dev/vfio/0"] if busy else []

    monkeypatch.setattr(accelerators, "chips_being_released", being_released)
    spawned = types.SimpleNamespace(info=object(), conn=object(), busy=False)
    started = []
    nm = types.SimpleNamespace(
        _try_claim_idle=lambda tpu: None, _unregistered=[],
        _spawn_worker=lambda tpu: (started.append(tpu), spawned)[1])
    t0 = time.monotonic()
    assert asyncio.run(NodeManager._get_idle_worker(
        nm, timeout_s=1.0, tpu=tpu)) is spawned and spawned.busy
    assert started == [tpu]
    if polled is None:  # half of the second, and the other half unspent
        assert 0.5 <= polls[-1] - t0 and time.monotonic() - t0 < 0.9
    else:
        assert len(polls) == polled


@pytest.mark.parametrize("busy_polls", [3, 0], ids=["held", "free"])
def test_the_wait_for_chips_reaches_the_event_and_the_worker(
        monkeypatch, busy_polls):
    """What held a chip worker's spawn back is on the `worker_started`
    event and, through the reply to its registration, in the worker's
    own account of its start: the time `chips_being_released` named a
    group (three polls of 0.2 s here), and 0 where it named none."""
    from ray_tpu._internal.profiler import ProcessLog

    polls = []

    def being_released():
        polls.append(1)
        return ["/dev/vfio/0"] if len(polls) <= busy_polls else []

    async def connect(host, port):
        return object()

    monkeypatch.setattr(accelerators, "chips_being_released", being_released)
    monkeypatch.setattr(node_manager, "connect", connect)
    events, unregistered = [], []

    def spawn(tpu):
        w = node_manager._Worker(node_manager._FakeProc(), tpu=tpu)
        unregistered.append(w)
        return w

    nm = types.SimpleNamespace(
        _try_claim_idle=lambda tpu: None, _unregistered=unregistered,
        _spawn_worker=spawn, workers={}, _maybe_grant_pending=lambda: None,
        _emit_event=lambda kind, message, **data: events.append(
            (kind, data)))
    info = types.SimpleNamespace(
        worker_id=bytes(8),
        address=types.SimpleNamespace(host="127.0.0.1", port=1))

    async def lease():
        t0 = time.monotonic()
        getting = asyncio.ensure_future(NodeManager._get_idle_worker(
            nm, timeout_s=5.0, tpu=True))
        while not unregistered:      # the wait for the chips is over
            await asyncio.sleep(0.01)
        waited = time.monotonic() - t0
        reply = await NodeManager.rpc_register_worker(
            nm, None, (info, node_manager._FakeProc.pid))
        return await getting, reply, waited

    w, reply, waited = asyncio.run(lease())
    assert w.tpu and w.busy and len(polls) == busy_polls + 1
    (kind, data), = events
    assert kind == "worker_started" and data["boot_s"] >= 0
    assert reply["tpu"] is True
    assert reply["lease_asked"] <= reply["spawned"] <= time.time()
    log = ProcessLog()
    log.spawned(reply)
    startup = log.startup()
    assert log.leased_chips
    assert startup["chip_wait_s"] == data["chip_wait_s"] \
        == reply["chip_wait_s"]
    wait, boot = startup["phases"]["spawn_wait"], startup["phases"]["boot"]
    assert wait[0] <= wait[1] == boot[0] == 0.0 <= boot[1]
    if busy_polls:
        assert 0.4 <= data["chip_wait_s"] <= waited
        assert wait[1] - wait[0] >= data["chip_wait_s"] - 1e-3
    else:
        assert data["chip_wait_s"] == 0


def test_stop_waits_for_a_worker_it_had_to_kill():
    """A worker that held chips is gone only when the kernel has given
    them back, long after the signal: `stop` returns after that, so the
    next cluster on the host finds them free."""
    class Proc:
        def __init__(self, slow):
            self.slow, self.calls = slow, []

        def terminate(self):
            self.calls.append("terminate")

        def kill(self):
            self.calls.append("kill")

        def wait(self, timeout=None):
            self.calls.append(("wait", timeout))
            if self.slow and len(self.calls) == 2:
                raise subprocess.TimeoutExpired("worker", timeout)

    async def nothing():
        pass

    quick, slow = Proc(False), Proc(True)
    nm = types.SimpleNamespace(
        _tasks=[], workers={1: types.SimpleNamespace(proc=quick)},
        _unregistered=[], _doomed=[types.SimpleNamespace(proc=slow)],
        object_dir={}, shm=object(), gcs_conn=None,
        server=types.SimpleNamespace(stop=nothing))
    asyncio.run(NodeManager.stop(nm))
    assert quick.calls == ["terminate", ("wait", 3)]
    assert slow.calls == ["terminate", ("wait", 3), "kill",
                          ("wait", node_manager._WORKER_EXIT_TIMEOUT_S)]


def test_compile_cache_is_one_fixed_dir_unless_set_outside(monkeypatch):
    monkeypatch.delenv(COMPILE_CACHE_ENV, raising=False)
    root = "/some/checkout"
    assert child_env(root)[COMPILE_CACHE_ENV] == compile_cache_dir(root)
    assert child_env(root)[COMPILE_CACHE_ENV] == child_env(root)[
        COMPILE_CACHE_ENV]  # no pid, time or temp name in it
    monkeypatch.setenv(COMPILE_CACHE_ENV, "/from/outside")
    assert child_env(root)[COMPILE_CACHE_ENV] == "/from/outside"


def test_explicit_cpu_platform_reaches_a_leased_worker(monkeypatch):
    """JAX_PLATFORMS=cpu on the node (how every other test here runs a
    fake TPU resource) is the one way a leased worker is on the CPU.
    Runs before the module's shared node exists: one cluster at a time."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rt.init(num_cpus=2, resources={"TPU": 1})
    try:
        leased = rt.remote(Probe).options(num_tpus=1).remote()
        assert rt.get(leased.view.remote(), timeout=60)[
            "JAX_PLATFORMS"] == "cpu"
        assert rt.get(leased.touch_jax.remote(), timeout=120) == "cpu"
    finally:
        rt.shutdown()
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_driver_keeps_off_the_chips(one_chip_node):
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_worker_without_a_lease_is_pinned_to_the_cpu(one_chip_node):
    plain = rt.remote(Probe).remote()
    v = rt.get(plain.view.remote(), timeout=60)
    assert v["JAX_PLATFORMS"] == "cpu"
    assert rt.get(rt.remote(_view).remote(), timeout=60)[
        "JAX_PLATFORMS"] == "cpu"
    assert rt.get(plain.touch_jax.remote(), timeout=120) == "cpu"
    rt.kill(plain)


def test_leased_actor_is_not_pinned_and_the_next_lease_waits(one_chip_node):
    leased = rt.remote(Probe).options(num_tpus=1).remote()
    v = rt.get(leased.view.remote(), timeout=60)
    assert v["JAX_PLATFORMS"] == "tpu,cpu"
    assert not v["jax_imported"]  # boots without jax, like every worker
    assert [w["pid"] for w in _tpu_workers()] == [v["pid"]]

    second = rt.remote(Probe).options(num_tpus=1).remote()
    with pytest.raises(Exception) as err:
        rt.get(second.view.remote(), timeout=3)
    assert "imeout" in type(err.value).__name__, err.value
    assert [w["pid"] for w in _tpu_workers()] == [v["pid"]]

    # the chip passes on only once the first holder's process is gone,
    # and to a fresh process
    rt.kill(leased)
    v2 = rt.get(second.view.remote(), timeout=60)
    assert v2["JAX_PLATFORMS"] == "tpu,cpu" and v2["pid"] != v["pid"]
    assert _gone(v["pid"])
    rt.kill(second)
    assert _gone(v2["pid"])


def _own_start():
    from ray_tpu._internal.profiler import process_log

    log = process_log()
    return {"leased": log.leased_chips, "startup": log.startup(),
            "jax_imported": "jax" in sys.modules, "pid": os.getpid()}


def test_a_worker_knows_how_it_was_started(one_chip_node):
    """The reply to a worker's registration carries what the node
    manager knows of its start; the same is on the `worker_started`
    event. No chips were being released here: the wait is 0. And a
    worker that merely holds a lease is not touched: only a serve
    replica and a train worker make the backend's first touch."""
    leased = rt.remote(_own_start).options(num_tpus=1)
    plain = rt.remote(_own_start)
    mine, other = rt.get([leased.remote(), plain.remote()], timeout=60)
    assert mine["leased"] and not other["leased"]
    assert not mine["jax_imported"]
    for own in (mine, other):
        phases = own["startup"]["phases"]
        assert list(phases) == ["spawn_wait", "boot"]
        assert phases["spawn_wait"][0] <= phases["spawn_wait"][1] == 0.0 \
            == phases["boot"][0] < phases["boot"][1]
        assert own["startup"]["chip_wait_s"] == 0
    started = {}
    end = time.monotonic() + 20.0     # events ride the heartbeat
    while mine["pid"] not in started and time.monotonic() < end:
        started = {e["data"]["pid"]: e["data"]
                   for e in state_api.list_cluster_events(
                       kind="worker_started", limit=1000)}
        time.sleep(0.2)
    event = started[mine["pid"]]
    assert event["chip_wait_s"] == 0 and event["boot_s"] > 0
    # the event's boot is the node manager's clock around the same
    # stretch as the worker's own phase
    assert event["boot_s"] == pytest.approx(
        mine["startup"]["phases"]["boot"][1], abs=0.5)


def test_leased_worker_without_a_chip_raises(one_chip_node):
    """It does not continue on the CPU: the platform was named, so jax
    fails where an unset variable would have fallen back in silence."""
    leased = rt.remote(Probe).options(num_tpus=1).remote()
    try:
        with pytest.raises(Exception, match="Unable to initialize backend"):
            rt.get(leased.touch_jax.remote(), timeout=120)
    finally:
        rt.kill(leased)


def test_leased_task_gets_its_own_worker_which_ends_with_the_lease(
        one_chip_node):
    task = rt.remote(num_tpus=1)(_view)
    v = rt.get(task.remote(), timeout=60)
    assert v["JAX_PLATFORMS"] == "tpu,cpu"
    assert _gone(v["pid"])  # after lease_reuse_idle_s the lease goes back
    v2 = rt.get(task.remote(), timeout=60)
    assert v2["pid"] != v["pid"]
