"""Driver-side cluster bootstrap: init/shutdown (ref analog:
python/ray/_private/worker.py:1275 `init` + _private/{node,services}.py
process launching)."""

from __future__ import annotations

import atexit
import json
import os
import subprocess
import sys
import time

from ray_tpu._internal.config import get_config
from ray_tpu._internal.ids import JobID, NodeID
from ray_tpu._internal.logging_utils import setup_logger
from ray_tpu.core.common import Address
from ray_tpu.core.core_worker import CoreWorker

logger = setup_logger("runtime")

_global: "RuntimeContext | None" = None


class RuntimeContext:
    def __init__(self):
        self.head_proc: subprocess.Popen | None = None
        self.core_worker: CoreWorker | None = None
        self.gcs_address: Address | None = None
        self.nm_address: Address | None = None
        self.head_node_id: NodeID | None = None
        self.job_id: JobID | None = None
        self.owns_cluster = False
        # environment variables init() changed in this process, with
        # the values they had (None = unset): shutdown() puts them back,
        # so a later cluster started from here inherits the operator's
        # settings again (see _keep_driver_off_the_chips)
        self.env_before: dict[str, str | None] = {}


def _detect_default_resources(num_cpus, resources):
    out = dict(resources or {})
    if num_cpus is None:
        num_cpus = os.cpu_count() or 1
    out.setdefault("CPU", float(num_cpus))
    if "TPU" not in out:
        # TPU autodetect (ref analog: _private/accelerators/tpu.py:70):
        # GKE env -> GCE metadata -> devfs; advertises slice-typed
        # resources (TPU-v5e-8, TPU-v5e-8-head on worker 0) so slice
        # gang-scheduling works with no flags.
        from ray_tpu._internal.accelerators import detect_tpu_slice

        info = detect_tpu_slice(
            use_metadata=os.environ.get("RAYT_DISABLE_GCE_METADATA") != "1")
        if info is not None:
            for k, v in info.resources().items():
                out.setdefault(k, v)
    out.setdefault("memory", float(_system_memory_bytes()))
    return out


def _keep_driver_off_the_chips():
    """A node's chips belong to the workers its manager leases them to
    (node_manager._spawn_worker), and a chip serves one process at a
    time. The driver is not such a worker, so when it starts a node
    that advertises TPU it keeps its own jax on the CPU. Called once the
    head's environment is made: the head keeps the operator's setting."""
    jax = sys.modules.get("jax")
    if jax is None:
        os.environ["JAX_PLATFORMS"] = "cpu"
        return
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "this process initialised the "
                f"{jax.default_backend()!r} backend before ray_tpu.init(): "
                "it holds the chip that the cluster's TPU workers need. "
                "Call init() first, and compute on the chip in a task or "
                "actor that asks for num_tpus")
    else:
        jax.config.update("jax_platforms", "cpu")  # env is read at import
    os.environ["JAX_PLATFORMS"] = "cpu"


def _system_memory_bytes() -> int:
    try:
        import psutil

        return psutil.virtual_memory().total
    except Exception:
        return 8 << 30


def is_initialized() -> bool:
    return _global is not None


def get_runtime_context() -> RuntimeContext:
    if _global is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    return _global


def init(address: str | None = None, *, num_cpus: float | None = None,
         resources: dict | None = None, log_to_driver: bool = True,
         ignore_reinit_error: bool = False, **kwargs) -> RuntimeContext:
    global _global
    if _global is not None:
        if ignore_reinit_error:
            return _global
        raise RuntimeError("ray_tpu already initialized (pass "
                           "ignore_reinit_error=True to tolerate)")
    ctx = RuntimeContext()
    if address is None:
        from ray_tpu._internal.spawn import child_env, fast_python_argv

        total = _detect_default_resources(num_cpus, resources)
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = child_env(pkg_root)
        env["RAYT_CONFIG_JSON"] = get_config().to_json()
        if total.get("TPU"):  # after env is made: the head's is as it was
            ctx.env_before["JAX_PLATFORMS"] = os.environ.get(
                "JAX_PLATFORMS")
            _keep_driver_off_the_chips()
        ctx.head_proc = subprocess.Popen(
            fast_python_argv("ray_tpu.core.head_main")
            + ["--resources", json.dumps(total)],
            stdout=subprocess.PIPE, env=env, text=True)
        line = ctx.head_proc.stdout.readline()
        if not line:
            raise RuntimeError("head process failed to start")
        info = json.loads(line)
        ctx.gcs_address = Address("127.0.0.1", info["gcs_port"])
        ctx.nm_address = Address("127.0.0.1", info["nm_port"])
        ctx.head_node_id = NodeID.from_hex(info["node_id"])
        ctx.owns_cluster = True
    else:
        host, port = address.split(":")
        ctx.gcs_address = Address(host, int(port))
        # attach: discover the head node manager via GCS
        import asyncio

        from ray_tpu.core.gcs import GcsClient

        async def _discover():
            gcs = await GcsClient.connect(ctx.gcs_address)
            nodes = await gcs.get_all_nodes()
            await gcs.close()
            return nodes

        nodes = asyncio.run(_discover())
        head = next((n for n in nodes if n.labels.get("head")), nodes[0])
        ctx.nm_address = head.address
        ctx.head_node_id = head.node_id

    ctx.job_id = JobID.random()
    os.environ["RAYT_JOB_ID"] = ctx.job_id.hex()
    cw = CoreWorker(mode="driver", job_id=ctx.job_id,
                    gcs_address=ctx.gcs_address,
                    node_address=ctx.nm_address,
                    node_id=ctx.head_node_id)
    cw.connect_cluster()
    cw.io.run(cw.gcs.conn.call("register_job", (ctx.job_id, {"driver_pid": os.getpid()})))
    ctx.core_worker = cw
    _global = ctx
    atexit.register(shutdown)
    return ctx


def shutdown():
    global _global
    ctx = _global
    if ctx is None:
        return
    _global = None
    for name, before in ctx.env_before.items():
        if before is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = before
    try:
        if ctx.core_worker is not None:
            try:
                ctx.core_worker.io.run(
                    ctx.core_worker.gcs.conn.call("finish_job", ctx.job_id),
                    timeout=2)
            except Exception:
                pass
            ctx.core_worker.shutdown()
    finally:
        if ctx.owns_cluster and ctx.head_proc is not None:
            ctx.head_proc.terminate()
            try:
                # the node manager waits for its workers to be gone, and
                # one that held chips takes its time (node_manager.stop)
                ctx.head_proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                ctx.head_proc.kill()
    try:
        atexit.unregister(shutdown)
    except Exception:
        pass
