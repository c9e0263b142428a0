"""The CPU rehearsal's manifest, derived: BENCHMARK.json as it stands,
with every configuration and traffic mix shrunk by the overrides that
sit under its own name in rehearsal/configs/ and rehearsal/traffic/.
A later PR that adds a configuration or a mix adds its override file
beside them; nothing here names one.

  python3 -m benchmarks.rehearsal    writes .bench_work/rehearsal/ and
                                     prints the manifest's path, for
  python3 -m benchmarks.run --manifest <that path> --workload <cell> ...

A rehearsal run computes on the CPU, so it prints what it found to
stderr and fails, as every run without the chip does.
"""

from __future__ import annotations

import json
import os

from benchmarks import manifest as manifest_mod


def overlay(full: dict, over: dict) -> dict:
    """`over` laid on `full`: groups merged key by key, the rest replaced."""
    out = dict(full)
    for k, v in over.items():
        out[k] = overlay(full[k], v) if isinstance(v, dict) \
            and isinstance(full.get(k), dict) else v
    return out


def _overlaid(kind: str, name: str, full_path: str) -> dict:
    with open(full_path) as f:
        full = json.load(f)
    with open(os.path.join(manifest_mod.HERE, "rehearsal", kind,
                           name + ".json")) as f:
        return overlay(full, json.load(f))


def derive(out_dir: str, manifest: dict | None = None) -> str:
    """Writes the shrunk files and their manifest under `out_dir`
    (absolute) and returns the manifest's path. Cells, metrics and bounds
    are the manifest's own; metric files are read where they are."""
    manifest = manifest or manifest_mod.load()
    base = manifest_mod.base_dir(manifest)
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(out_dir, sub), exist_ok=True)
    configs = []
    for c in manifest["configs"]:
        path = os.path.join(out_dir, "configs", c["name"] + ".json")
        with open(path, "w") as f:
            json.dump(_overlaid("configs", c["name"], os.path.join(
                manifest_mod.ROOT, c["file"])), f, indent=1)
        configs.append({**c, "file": path})
    for t in sorted({w["traffic"] for w in manifest["workloads"]}):
        with open(os.path.join(out_dir, "traffic", t + ".json"), "w") as f:
            json.dump(_overlaid("traffic", t, os.path.join(
                base, "traffic", t + ".json")), f, indent=1)
    link = os.path.join(out_dir, "metrics")
    if not os.path.islink(link):
        os.symlink(os.path.join(base, "metrics"), link)
    path = os.path.join(out_dir, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump({**manifest, "configs": configs,
                   "paths": [out_dir] + manifest["paths"][1:]}, f, indent=1)
    return path


if __name__ == "__main__":
    print(derive(os.path.join(manifest_mod.ROOT, ".bench_work",
                              "rehearsal")))
