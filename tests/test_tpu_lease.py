"""One process per chip, decided by the lease (node_manager._spawn_worker).

A worker may leave the CPU if and only if its task or actor was granted
TPU > 0. The sandbox has no chip, so these assert on what a worker is
handed — the JAX_PLATFORMS it would give jax — and on what jax does with
it, not on a device.
"""

import os
import sys
import time

import pytest

import ray_tpu as rt
from ray_tpu import state_api
from ray_tpu._internal.spawn import (COMPILE_CACHE_ENV, child_env,
                                     compile_cache_dir, jax_platforms_env)
from ray_tpu.core.node_manager import _holds_tpu


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
