"""Loads BENCHMARK.json and resolves a cell to its files, by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under the benchmark's `paths`:

  configs/<config>.json      sizes as run, source, assumed, tolerances
  traffic/<traffic>.json     parameters the one generator reads
  metrics/<metric>.json      which reader takes the number, and from what

so a later PR adds a cell by adding files and entries to BENCHMARK.json
and edits nothing that is there.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEFAULT_MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


@dataclasses.dataclass
class Cell:
    """One entry of `workloads` with everything it names, loaded."""
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    traffic_name: str
    traffic: dict         # the traffic file
    end_to_end: list      # metric entries of BENCHMARK.json for this cell
    per_layer: list       # same, each with its metric file under "file"


def load(path: str = DEFAULT_MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def base_dir(manifest: dict) -> str:
    """The first of `paths`: the directory that holds traffic/ and
    metrics/."""
    return os.path.join(ROOT, manifest["paths"][0])


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(manifest: dict, cell_name: str) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r}; have {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in manifest["configs"]}
    base = base_dir(manifest)
    config = _read_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(base, "traffic",
                                      w["traffic"] + ".json"))
    per_layer = []
    for m in manifest["per_layer"]:
        if _in_cell(m, cell_name):
            spec = _read_json(os.path.join(base, "metrics",
                                           m["name"] + ".json"))
            per_layer.append({**m, "file": spec})
    return Cell(name=cell_name, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in manifest["end_to_end"]
                            if _in_cell(m, cell_name)],
                per_layer=per_layer)


def problems(manifest: dict) -> list:
    """What the contract would refuse, as far as it can be seen without
    a run: names, units, sources, `moves`, files that are missing."""
    out = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            out.append(f"{what}: bad name {n!r}")

    e2e = {m["name"] for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("end_to_end lacks setup_s")
    seen = set()
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            name_ok(m["name"], kind)
            if m["name"] in seen:
                out.append(f"metric {m['name']} twice")
            seen.add(m["name"])
            if not UNIT_RE.match(m.get("unit", "")):
                out.append(f"{m['name']}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                out.append(f"{m['name']}: better?")
            if m.get("source") not in SOURCES:
                out.append(f"{m['name']}: bad source")
            if kind == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    out.append(f"{m['name']}: end-to-end source")
                if not 0.01 <= m.get("bound", 0) <= 0.1:
                    out.append(f"{m['name']}: bound out of range")
    cell_names = set()
    pairs = set()
    for w in manifest["workloads"]:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        if w["name"] in cell_names:
            out.append(f"workload {w['name']} twice")
        cell_names.add(w["name"])
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"pair {w['config']},{w['traffic']} twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            out.append(f"{w['name']}: chips")
        if not 1 <= len(w.get("why", "")) <= 200:
            out.append(f"{w['name']}: why is {len(w.get('why', ''))} long")
        try:
            cell = resolve(manifest, w["name"])
        except (OSError, KeyError, ValueError) as e:
            out.append(f"{w['name']}: {e!r}")
            continue
        reported = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in reported or len(reported) < 2:
            out.append(f"{w['name']}: needs setup_s and one more")
        if not cell.per_layer:
            out.append(f"{w['name']}: no per-layer metric")
        for m in cell.per_layer:
            if m["moves"] not in reported:
                out.append(f"{m['name']} moves {m['moves']}, which "
                           f"{w['name']} does not report")
            if m["file"].get("name") != m["name"]:
                out.append(f"{m['name']}: its file names another metric")
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        for wl in m.get("workloads", ()):
            if wl not in cell_names:
                out.append(f"{m['name']}: unknown workload {wl}")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        out.append("too many four-chip cells")
    for c in manifest["configs"]:
        name_ok(c["name"], "config")
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            out.append(f"config {c['name']} is used by no cell")
    return out
