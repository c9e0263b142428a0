"""Chaos harness — schedule-driven fault injection for recovery drills
(ref analog: the reference's chaos-testing utilities,
python/ray/_private/test_utils.py get_and_run_resource_killer and
release/nightly chaos_test suites: kill nodes/actors on a cadence under
load, then assert the workload's recovery SLOs).

Fault primitives cover the planes this runtime can lose:

* ``kill_actor`` / ``kill_random_actor`` — a worker actor (restartable
  actors exercise GCS auto-restart; DAG ring runners exercise
  recompile-and-resume, dag/recovery.py);
* ``kill_worker_node`` — SIGKILL a node manager (sudden node loss:
  lineage re-execution, lease revocation, object recovery);
* ``bounce_head`` — SIGKILL + same-port restart of the GCS (head HA:
  snapshot reload, client reconnect, serve controller checkpoint);
* ``kill_serve_controller`` — the serve control plane (handles keep
  routing on their last table and self-heal the controller, which
  restores its GCS checkpoint).

Used two ways: tests/test_chaos.py (through the drills in
tools/envelope_bench), or interactively::

    monkey = ChaosMonkey(cluster)
    monkey.at(2.0, monkey.kill_random_actor, runners)
    monkey.at(5.0, monkey.kill_serve_controller)
    monkey.start()
    ... drive load ...
    monkey.stop()
    assert all(e["ok"] for e in monkey.log)
"""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Callable, Optional

__all__ = ["ChaosMonkey"]


class ChaosMonkey:
    """Runs a schedule of fault injections on a background thread and
    keeps a structured log of what it killed and when, so tests can
    correlate observed recoveries with injected faults."""

    def __init__(self, cluster=None, *, seed: int = 0):
        self.cluster = cluster            # cluster_utils.Cluster or None
        self.rng = random.Random(seed)
        # one row per fired fault: {"t", "fault", "ok", "detail"|"error"}
        self.log: list[dict] = []
        self._events: list[tuple[float, str, Callable[[], Any]]] = []
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ------------------------------------------------- fault primitives
    def kill_actor(self, handle, *, no_restart: bool = False) -> str:
        """SIGKILL-equivalent actor death (rt.kill). With
        ``no_restart=False`` a ``max_restarts`` actor comes back via the
        GCS restart path — the fault recovery code must survive, not a
        permanent capacity loss."""
        import ray_tpu as rt

        rt.kill(handle, no_restart=no_restart)
        return handle._actor_id.hex()

    def kill_random_actor(self, handles: list, *,
                          no_restart: bool = False) -> str:
        return self.kill_actor(self.rng.choice(list(handles)),
                               no_restart=no_restart)

    def kill_named_actor(self, name: str, *,
                         no_restart: bool = True) -> str:
        import ray_tpu as rt

        return self.kill_actor(rt.get_actor(name), no_restart=no_restart)

    def kill_serve_controller(self) -> str:
        """Kill the serve control plane. Replicas are NOT owned by the
        controller, so the data plane keeps serving; a surviving handle
        recreates the controller, which restores its GCS checkpoint and
        ADOPTS the live replicas (serve/controller.py)."""
        from ray_tpu.serve.controller import CONTROLLER_NAME

        return self.kill_named_actor(CONTROLLER_NAME, no_restart=True)

    def kill_worker_node(self, index: Optional[int] = None) -> str:
        """Sudden node loss (SIGKILL the node manager): every worker on
        it dies, shm objects on it are gone — lineage re-execution and
        actor restarts must cover."""
        if self.cluster is None or not self.cluster.worker_nodes:
            raise RuntimeError("no worker nodes to kill")
        nodes = self.cluster.worker_nodes
        handle = (self.rng.choice(nodes) if index is None
                  else nodes[index])
        self.cluster.remove_node(handle, graceful=False)
        return handle.node_id_hex

    def bounce_head(self, down_s: float = 0.5) -> str:
        """SIGKILL the head (GCS) and restart it on the SAME port after
        ``down_s``: clients/nodes ride their reconnect loops, the GCS
        reloads its snapshot, serve handles full-resync their tables."""
        if self.cluster is None:
            raise RuntimeError("bounce_head needs a Cluster handle")
        self.cluster.kill_head(graceful=False)
        time.sleep(down_s)
        self.cluster.restart_head()
        return f"gcs:{self.cluster.gcs_port}"

    # ---------------------------------------------------------- schedule
    def at(self, t_s: float, fault: Callable, *args,
           **kwargs) -> "ChaosMonkey":
        """Fire ``fault(*args, **kwargs)`` ``t_s`` seconds after
        start(); chainable."""
        label = getattr(fault, "__name__", str(fault))
        self._events.append(
            (float(t_s), label, lambda: fault(*args, **kwargs)))
        return self

    def every(self, period_s: float, count: int, fault: Callable, *args,
              start_s: Optional[float] = None, **kwargs) -> "ChaosMonkey":
        """``count`` firings, one per ``period_s``, first at ``start_s``
        (default: one period in)."""
        t = period_s if start_s is None else start_s
        for _ in range(count):
            self.at(t, fault, *args, **kwargs)
            t += period_s
        return self

    def start(self) -> "ChaosMonkey":
        if self._thread is not None:
            raise RuntimeError("chaos schedule already started")
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="chaos-monkey", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0):
        """Stop firing further faults and wait for the thread; faults
        already injected are NOT undone."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None

    def join(self, timeout: float = 600.0):
        """Wait for the whole schedule to finish firing."""
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    # ---------------------------------------------------------- internals
    def _run(self):
        t0 = time.monotonic()
        for at_s, label, fire in sorted(self._events, key=lambda e: e[0]):
            delay = at_s - (time.monotonic() - t0)
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            row = {"t": round(time.monotonic() - t0, 3), "fault": label}
            try:
                row["detail"] = fire()
                row["ok"] = True
            except Exception as e:  # record honestly; keep the schedule
                row["ok"] = False
                row["error"] = f"{type(e).__name__}: {e}"
            self.log.append(row)
