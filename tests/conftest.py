"""Test harness: force an 8-device virtual CPU mesh before jax imports.

This is the TPU-build analog of the reference's in-process multi-node
Cluster fixture (ref: python/ray/cluster_utils.py:135): SPMD/sharding tests
run against 8 virtual CPU devices standing in for a pod slice, so CI needs
no real TPU hardware.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the host may have a chip
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# Keep XLA from oversubscribing the (often single-core) CI host.
os.environ.setdefault("XLA_PYTHON_CLIENT_PREALLOCATE", "false")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import signal  # noqa: E402

import pytest  # noqa: E402

# Per-test wall-clock cap (the reference sets 3 min in pytest.ini:14).
# pytest-timeout isn't in the image, so use SIGALRM directly.
TEST_TIMEOUT_S = int(os.environ.get("RAYT_TEST_TIMEOUT_S", "180"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    def _on_timeout(signum, frame):
        raise TimeoutError(
            f"test exceeded {TEST_TIMEOUT_S}s (RAYT_TEST_TIMEOUT_S)")

    old = signal.signal(signal.SIGALRM, _on_timeout)
    signal.alarm(TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


@pytest.fixture
def local_cluster():
    """A started single-node cluster, shut down after the test."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, resources={"TPU": 8})
    try:
        yield ray_tpu
    finally:
        ray_tpu.shutdown()
