"""TPU accelerator manager: chip/slice autodetect + resource modeling.

Ref analog: python/ray/_private/accelerators/tpu.py:70 (autodetect via
GCE metadata / GKE env vars / TPU_VISIBLE_CHIPS, pod-type resources like
"TPU-v4-16-head" at :197). A node on a TPU VM advertises:

  TPU                    = chips on this host
  TPU-<accel_type>       = chips (slice-typed capacity, e.g. TPU-v5e-8)
  TPU-<accel_type>-head  = 1 on worker 0 of the slice only

The "-head" resource is the slice-gang trick: a multi-host job places
its per-slice coordinator task on the head resource, then fans out to
the slice's other hosts via a STRICT_SPREAD placement group over
per-host {TPU: chips_per_host} bundles (`tpu_slice_bundles`).
"""

from __future__ import annotations

import errno
import json
import os
import urllib.request
from dataclasses import dataclass, field
from typing import Optional

_GCE_METADATA_URL = ("http://metadata.google.internal/computeMetadata/v1/"
                     "instance/attributes/{}")
_GCE_TIMEOUT_S = 0.5

# chips per host by generation (public TPU VM shapes): v2/v3/v4/v5p pods
# expose 4 chips/host; v5e and v6e expose up to 8
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4,
                   "v5litepod": 8, "v5e": 8, "v6e": 8}


@dataclass
class TpuSliceInfo:
    accel_type: str            # e.g. "v5e-8", "v4-16" (gen-chips)
    gen: str                   # "v4", "v5e", ...
    total_chips: int           # chips in the whole slice
    chips_on_host: int         # chips visible on THIS host
    worker_id: int = 0         # this host's index within the slice
    num_workers: int = 1
    slice_name: str = ""       # pod/slice identity (for labels)
    topology: str = ""         # e.g. "2x4" when known
    source: str = "none"       # which probe found it

    def resources(self) -> dict:
        """Schedulable resources this host should advertise."""
        out = {"TPU": float(self.chips_on_host),
               f"TPU-{self.accel_type}": float(self.chips_on_host)}
        if self.worker_id == 0:
            out[f"TPU-{self.accel_type}-head"] = 1.0
        return out

    def labels(self) -> dict:
        lab = {"tpu-gen": self.gen, "tpu-accel-type": self.accel_type,
               "tpu-worker-id": str(self.worker_id)}
        if self.slice_name:
            lab["tpu-slice"] = self.slice_name
        if self.topology:
            lab["tpu-topology"] = self.topology
        return lab


def _norm_gen(accel_type: str) -> str:
    gen = accel_type.split("-")[0].lower()
    return {"v5litepod": "v5e", "v5lite": "v5e"}.get(gen, gen)


def _gce_metadata(key: str) -> Optional[str]:
    req = urllib.request.Request(_GCE_METADATA_URL.format(key),
                                 headers={"Metadata-Flavor": "Google"})
    try:
        with urllib.request.urlopen(req, timeout=_GCE_TIMEOUT_S) as r:
            return r.read().decode()
    except Exception:
        return None


def _count_devfs_chips() -> int:
    n = 0
    for d, prefix in (("/dev", "accel"), ("/dev/vfio", "")):
        try:
            names = os.listdir(d)
        except OSError:
            continue
        if d == "/dev":
            n = max(n, len([e for e in names if e.startswith(prefix)
                            and e[len(prefix):].isdigit()]))
        else:
            n = max(n, len([e for e in names if e.isdigit()]))
    return n


def chips_being_released(vfio_dir: str = "/dev/vfio") -> list[str]:
    """The chips' vfio groups that refuse to be opened (EBUSY) though no
    process has them open. A group has one owner at a time. When its
    owner is killed the kernel tears the mappings down as the process
    exits, which takes many seconds on a host without huge pages; the
    process has no open files to show by then, and whoever opens the
    group meanwhile (libtpu, in the next cluster's worker) is refused.
    Opening a group and closing it again asks nothing of the device.
    A group held by a process that can be seen is that process's, and
    is not listed: waiting would not free it."""
    try:
        groups = {os.path.join(vfio_dir, e) for e in os.listdir(vfio_dir)
                  if e.isdigit()}
    except OSError:
        return []
    for pid in os.listdir("/proc"):
        if not groups:
            break
        if not pid.isdigit():
            continue
        fds = f"/proc/{pid}/fd"
        try:
            for fd in os.listdir(fds):
                groups.discard(os.readlink(os.path.join(fds, fd)))
        except OSError:  # gone meanwhile, or not ours to read
            continue
    busy = []
    for path in sorted(groups):
        try:
            os.close(os.open(path, os.O_RDWR))
        except OSError as e:
            if e.errno == errno.EBUSY:
                busy.append(path)
    return busy


def detect_tpu_slice(env: Optional[dict] = None,
                     use_metadata: bool = True) -> Optional[TpuSliceInfo]:
    """Probe env vars (GKE), GCE metadata, then devfs. None if no TPU."""
    env = os.environ if env is None else env

    # 1. explicit chip visibility (also how tests/operators override)
    visible = env.get("TPU_VISIBLE_CHIPS") or env.get("TPU_VISIBLE_DEVICES")
    chips_on_host = (len([c for c in visible.split(",") if c.strip()])
                     if visible else 0)

    # 2. GKE-style env (ref tpu.py GKE path): TPU_ACCELERATOR_TYPE +
    # TPU_WORKER_ID + TPU_WORKER_HOSTNAMES
    accel = env.get("TPU_ACCELERATOR_TYPE")
    source = "env"
    topology = env.get("TPU_TOPOLOGY", "")
    worker_id = int(env.get("TPU_WORKER_ID", "0") or 0)
    hostnames = env.get("TPU_WORKER_HOSTNAMES", "")
    slice_name = env.get("TPU_NAME", "")

    # 3. GCE metadata attributes (TPU VMs). Only dialed when the host
    # actually shows chips (env or devfs) — keeps non-TPU init fast.
    devfs_chips = _count_devfs_chips()
    if accel is None and use_metadata and (chips_on_host or devfs_chips):
        accel = _gce_metadata("accelerator-type")
        if accel is not None:
            source = "gce-metadata"
            wid = _gce_metadata("agent-worker-number")
            worker_id = int(wid) if wid and wid.isdigit() else 0
            tpu_env = _gce_metadata("tpu-env") or ""
            for line in tpu_env.splitlines():
                k, _, v = line.partition(":")
                v = v.strip().strip("'\"")
                if k.strip() == "TOPOLOGY":
                    topology = v
                elif k.strip() == "WORKER_HOSTNAMES":
                    hostnames = v
                elif k.strip() == "INSTANCE_NAME":
                    slice_name = slice_name or v

    if accel is None:
        # 4. bare devfs probe: single-host, generation unknown
        n = chips_on_host or devfs_chips
        if not n:
            return None
        gen = env.get("TPU_GEN", "") or "tpu"
        return TpuSliceInfo(accel_type=f"{gen}-{n}", gen=gen,
                            total_chips=n, chips_on_host=n,
                            source="devfs")

    accel = accel.strip()
    gen = _norm_gen(accel)
    try:
        total = int(accel.split("-")[-1])
    except ValueError:
        total = chips_on_host or _count_devfs_chips() or 1
    else:
        if gen in ("v2", "v3", "v4", "v5p"):
            # those accelerator-type suffixes count TensorCores (2/chip),
            # not chips (ref tpu.py halves for pre-v5e generations). Only
            # the CHIP COUNT is halved — the accelerator-type string stays
            # exactly what the platform exports ("v4-16"), since that's
            # the name users target in resource requests.
            total = max(1, total // 2)
    per_host = _CHIPS_PER_HOST.get(gen, 4)
    num_workers = max(1, -(-total // per_host))
    if hostnames:
        num_workers = max(num_workers,
                          len([h for h in hostnames.split(",") if h.strip()]))
    if not chips_on_host:
        chips_on_host = devfs_chips or min(total, per_host)
    return TpuSliceInfo(accel_type=accel.lower(), gen=gen,
                        total_chips=total,
                        chips_on_host=chips_on_host, worker_id=worker_id,
                        num_workers=num_workers, slice_name=slice_name,
                        topology=topology, source=source)


def tpu_slice_bundles(info: TpuSliceInfo) -> list[dict]:
    """Placement-group bundles for gang-scheduling a whole slice: one
    bundle per host. Use strategy=STRICT_SPREAD (one host each) with the
    coordinator targeting the `TPU-<type>-head` resource."""
    per_host = max(1, info.total_chips // max(1, info.num_workers))
    return [{"TPU": float(per_host)} for _ in range(info.num_workers)]
