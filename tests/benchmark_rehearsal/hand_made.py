"""A hand-made run of any cell of the manifest: the `obs` its driver
would return and a profiler trace in trace_spans' plain form, built from
the cell's own files and nothing else. The trace holds every scope, the
spans every field and `stats()` every counter that a metric file of the
cell names in its `args`, what the reader modules those files name
read by their own arithmetic, which hand_made/<module>.json lists:

    {"scopes": ["decode/ssm_update", ...],
     "fields": {"rayt.engine.emit": ["experts_hit", ...]}}

and, in the same form, what readers/model.py reads for the cell's model:
the scopes and fields its YARDSTICKS names (`Yardsticks.reads`), so a
model's helper is the one place that lists them.

test_manifest_entries.py holds every (entry, cell) pair to it: a number
from `obs(cell)`, None from `bare(cell)`, the same run of a program that
writes no span, scope, record or log. A later PR's cell is held the same
way with no edit here; a reader module it brings gets such a file.
Nothing here is a device number.
"""

from __future__ import annotations

import json
import os

from benchmarks import trace_spans
from benchmarks.client import Stream
from benchmarks.manifest import Cell
from benchmarks.readers import model as model_reader
from benchmarks.readers import spans
from benchmarks.traffic import Request

ENGINE = trace_spans.ENGINE_PREFIX
# what every program of the repo writes, whatever the model
SCOPES = ["decode/attn", "prefill/mlp", "loss/ce", "optimizer/", "unscoped"]
FIELDS = {ENGINE + "decode_dispatch": ["active", "live_positions"],
          ENGINE + "prefill_chunk": ["pos", "chunk", "last"],
          ENGINE + "emit": ["active", "finished"]}
COUNTERS = ["batches", "prefill_chunks", "prefills", "generated_tokens"]
T_HOST = 50.0      # the replica's perf_counter at the trace's anchor


def module_reads(name: str) -> dict:
    """hand_made/<name>.json: what reader module `name` reads beyond its
    metric files' `args`; {} for a module that has no such file."""
    path = os.path.join(os.path.dirname(__file__), "hand_made",
                        name + ".json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)


def needs(cell: Cell) -> tuple:
    """(scopes, {span: fields}, counters) the cell's readers read."""
    scopes, fields = list(SCOPES), {k: list(v) for k, v in FIELDS.items()}
    counters = list(COUNTERS)
    modules = {"spans"}
    for m in cell.per_layer:
        spec = m["file"]
        modules.add(spec["reader"].rsplit(".", 1)[0])
        args = spec.get("args", {})
        scopes += args.get("scopes", [])
        if "span" in args:
            fields.setdefault(args["span"], []).extend(
                args.get("over", []) + args.get("under", [])
                + args.get("fields", []))
        if "counter" in args:
            counters.append(args["counter"])
    own = model_reader.yardsticks({"config": cell.config,
                                   "traffic": cell.traffic})
    for made in [module_reads(name) for name in sorted(modules)] + \
            [own.reads() if own and "model" in modules else {}]:
        scopes += made.get("scopes", [])
        for span, names in made.get("fields", {}).items():
            fields.setdefault(span, []).extend(names)
    return list(dict.fromkeys(scopes)), fields, counters


def _path(scope: str) -> str:
    return "" if scope == trace_spans.UNSCOPED else \
        "jit(step)/" + scope.rstrip("/") + "/op:"


def trace(cell: Cell) -> dict:
    """One device: an operation of 100 ns under each scope, 100 ns idle
    after each. The first gap lies under no span (`unowned`), the next
    under each engine span in turn, with one between two of them that no
    span covers (the hand-off), then under two train spans; the rest are
    nobody's."""
    scopes, fields, _ = needs(cell)
    scopes += ["decode/attn"] * (12 - len(scopes))
    ops = [[f"%fusion.{k} = bf16[8,128]{{1,0}} fusion(x)", 200.0 * k, 100.0,
            {"path": _path(s)}] for k, s in enumerate(scopes + ["decode/"])]
    gap = lambda k: 200.0 * k + 100.0
    owners = [ENGINE + n for n in ("decode_dispatch", "token_sync", "admit",
                                   "prefill_chunk", "finish_prefill")]
    owners += [None, ENGINE + "emit", "rayt.train.h2d", "rayt.train.step"]
    host = []
    for k, name in enumerate(owners, start=1):
        if name is None:
            continue
        stats = {f: 1000 for f in fields.get(name, [])}
        if name == ENGINE + "decode_dispatch":
            stats["t_host"] = T_HOST
        if name == ENGINE + "prefill_chunk":
            stats["request_id"] = "r0"
        host.append([name, gap(k), 100.0, stats])
    for name, names in fields.items():      # a span the list above lacks
        if not any(ev[0] == name for ev in host):
            host.append([name, gap(1), 10.0, {f: 1000 for f in names}])
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [ev]}
                                        for ev in host]}]}


def reduced(cell: Cell) -> tuple:
    """What readers/spans.py `_both` gives for the hand-made trace."""
    tr = trace(cell)
    return trace_spans.reduce(tr), spans._table(tr)


def _programs(asked: int, seconds: float, **more) -> dict:
    return {"asked": asked, "cache_hits": asked, "cache_misses": 0,
            "trace_s": seconds, "lower_s": seconds, "compile_s": seconds,
            "cache_load_s": seconds, **more}


STARTUP = {"phases": {"spawn_wait": [-1.5, 0.0], "boot": [0.0, 0.5],
                      "backend": [0.5, 10.5], "engine_build": [14.0, 15.0],
                      "ready": [16.0, 17.0]},
           "anchor": {"wall": 1.0, "perf_counter": 2.0},
           "process_start": 1.5, "chip_wait_s": 0.25}
BY_PROGRAM = {"unlabelled": _programs(2, 2.0),
              "prefill_chunk[256@1024]": _programs(5, 3.0)}


def _stream(k: int, due: float, first: float, n: int, prompt: int) -> Stream:
    s = Stream(Request(k, 0.0, [1] * prompt, n, "window"), due)
    s.sent = due + 0.001
    s.t = [first + 0.01 * i for i in range(n)]
    s.tokens = [7] * n
    s.request_id = f"r{k}"
    s.done = s.t[-1]
    return s


def _common(cell: Cell) -> dict:
    return {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
            "chips": cell.chips, "setup_s": 100.0,
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": cell.chips}}


def obs(cell: Cell) -> dict:
    """The observations of a run in which every reader of the cell finds
    something to read (with `reduced(cell)` in the place of the trace)."""
    out = _common(cell)
    out.update(memory_peak_bytes=5_000_000_000,
               traced={"steps": 1, "traced_wall_s": 1.0},
               trace={"busy_s": 0.5, "window_s": 1.0, "collective_s": 0.2,
                      "collective_exposed_s": 0.1})
    if cell.traffic["driver"] == "train_cell":
        step = lambda n, **kw: {"step": n, "wall_s": 1.0, "loss": 1.0,
                                "stages": {"step_s": 0.9}, **kw}
        out.update(job=cell.traffic["job"], warmup_steps=3,
                   window_stamps=[0.0, 1.0, 2.0, 3.0],
                   setup={"imports_s": 3.0, "cluster_s": 2.0,
                          "weights_s": 6.0, "fit_s": 90.0},
                   steps=[step(1, startup=STARTUP, programs=_programs(
                       8, 2.0, by_program=BY_PROGRAM, last=None)),
                       step(2), step(3), step(4),
                       step(5, programs=_programs(1, 0.5, last=None)),
                       step(6)])
        return out
    _, _, counters = needs(cell)
    stats = lambda n: {**{c: n * (3 + k) for k, c in enumerate(counters)},
                       "startup": STARTUP, "programs": _programs(
                           8 + n, 2.0, by_program=BY_PROGRAM, timeline=[
                               [60.0, 4.0, 3], [95.0, 8.0, 8]])}
    # first tokens on both sides of each edge of the window, and inside
    streams = [_stream(0, 99.0, 99.5, 30, 200), _stream(1, 100.5, 101.0, 30,
                                                        400),
               _stream(2, 110.0, 110.5, 30, 200),
               _stream(3, 129.0, 130.5, 30, 200)]
    engine = {"queue_s": 0.01, "tpot_s": 0.01, "ttft_s": 0.4,
              "occupancy_mean": 0.5, "decode_steps": 29,
              "t_admit": T_HOST - 1e-6, "t_first": T_HOST + 1e-5}
    out.update(window=(100.0, 130.0), streams=streams,
               records={s.request_id: {"engine": dict(engine)}
                        for s in streams},
               before={"stats": stats(1), "programs": 9, "step_programs": 5},
               after={"stats": stats(2), "programs": 9, "step_programs": 5},
               setup={"imports_s": 3.0, "cluster_s": 2.0, "lead_s": 20.0,
                      "replica_weights_s": 6.0, "replica_up_s": 50.0})
    return out


def bare(cell: Cell) -> dict:
    """The same run of a program that writes nothing the readers read:
    no trace, no request or step record, no log, no counter."""
    out = _common(cell)
    out.update(trace=None, traced=None, setup={})
    if cell.traffic["driver"] == "train_cell":
        out.update(job=cell.traffic["job"], warmup_steps=0,
                   window_stamps=[], steps=[])
    else:
        out.update(window=(100.0, 130.0), streams=[], records={},
                   before={"stats": {}}, after={"stats": {}})
    return out
