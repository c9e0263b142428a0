"""Fast process spawning for cluster daemons and workers.

The interpreter's `site` import walks every .pth file in site-packages;
daemons and workers must boot in ~100ms for lease latency to be sane
(ref analog: raylet pre-forked worker pool exists for the same reason,
worker_pool.h:212), so we spawn children with ``python -S`` and put the
site-packages dirs on PYTHONPATH explicitly. jax, jaxlib and libtpu are
plain packages there, so a worker started this way still finds the TPU
backend.

`child_env` is also where the per-process JAX settings are decided: the
persistent compile cache directory, that its key holds the programs'
metadata, and (through `jax_platforms_env`) whether the process may
touch the chip.
"""

from __future__ import annotations

import os
import sys
import sysconfig

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_CACHE_METADATA_ENV = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"


def fast_python_argv(module: str) -> list[str]:
    return [sys.executable, "-S", "-m", module]


def compile_cache_dir(pkg_root: str) -> str:
    """The one place compiled programs persist when the environment
    names none: a fixed path in the checkout. The path is part of the
    cache key, so it must not vary by pid, time or temp name."""
    return os.path.join(pkg_root, ".jax_cache")


def child_env(pkg_root: str, base: dict | None = None) -> dict:
    env = dict(base if base is not None else os.environ)
    paths = [pkg_root]
    for key in ("purelib", "platlib"):
        p = sysconfig.get_paths().get(key)
        if p and p not in paths:
            paths.append(p)
    # any extra dirs site added (e.g. .pth expansions) that hold
    # importable top-level modules
    for p in sys.path:
        if p and p.endswith("site-packages") and p not in paths:
            paths.append(p)
    if base is None or "PYTHONPATH" in env:
        existing = env.get("PYTHONPATH", "")
        if existing:
            paths.append(existing)
    env["PYTHONPATH"] = os.pathsep.join(paths)
    # JAX reads the variable itself; a directory set from outside wins
    env.setdefault(COMPILE_CACHE_ENV, compile_cache_dir(pkg_root))
    # The program names its device operations with jax.named_scope, and
    # a profiler trace reads those names from the executable. JAX's cache
    # key leaves such metadata out by default, so a cache filled by an
    # older tree would go on serving executables with the old names (seen
    # on the chip, PERF.md PR 24). With the metadata in the key a tree
    # whose names or lines moved compiles once more and is cached again.
    env.setdefault(COMPILE_CACHE_METADATA_ENV, "true")
    return env


def jax_platforms_env(node_platforms: str | None, tpu_lease: bool) -> str:
    """JAX_PLATFORMS for a worker process. The chips belong to whoever
    holds a TPU lease: every other worker is pinned to the CPU, so a
    process that merely imports jax can never take the chip away from
    the one that was granted it. A leased worker gets what the node's
    operator set, and "tpu,cpu" when nothing was set: naming the
    platform makes JAX raise if the chip cannot be initialised, where
    an unset variable falls back to the CPU without a word."""
    if not tpu_lease:
        return "cpu"
    return node_platforms or "tpu,cpu"
