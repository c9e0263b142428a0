"""The per-layer metrics under `setup_s`: each resolves for the cells it
names, its reader takes a hand-made `obs` (and a program without the log
gives it nothing to read), and the CPU rehearsal of a serve and of a
train cell prints every one as a number. Nothing here is a device
number, and nothing decides by a clock."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import manifest as manifest_mod
from benchmarks import rehearsal
from benchmarks.readers import startup

ROOT = manifest_mod.ROOT
MANIFEST = manifest_mod.load()
NEW = [m for m in MANIFEST["per_layer"] if m["moves"] == "setup_s"]
CELLS = ["internlm2-1.8b.chat", "internlm2-1.8b.longprompt",
         "internlm2-1.8b.lora-ft", "mistral-7b-v0.3.lora-ft-4chip"]
NAMES = ["startup_worker_s", "startup_chip_wait_s", "startup_backend_s",
         "startup_programs_s", "startup_programs_asked",
         "startup_cache_misses", "programs_in_window",
         "startup_unaccounted_s"]


def _programs(asked, seconds, **more):
    return {"asked": asked, "cache_hits": asked, "cache_misses": 0,
            "trace_s": seconds, "lower_s": seconds, "compile_s": seconds,
            "cache_load_s": seconds, **more}


STARTUP = {"phases": {"spawn_wait": [-1.5, 0.0], "boot": [0.0, 0.5],
                      "backend": [0.5, 10.5], "engine_build": [14.0, 15.0],
                      "ready": [16.0, 17.0]},
           "anchor": {"wall": 1.0, "perf_counter": 2.0},
           "process_start": 1.5, "chip_wait_s": 1.25}
# 40 s of programs: 8 the weights' (unlabelled), 4 inside engine_build
BY_PROGRAM = {"unlabelled": _programs(2, 2.0), "engine_build":
              _programs(3, 1.0), "prefill_chunk[256@1024]": _programs(5, 7.0)}
WANT = {"startup_worker_s": 2.0, "startup_chip_wait_s": 1.25,
        "startup_backend_s": 10.0, "startup_programs_s": 40.0,
        "startup_programs_asked": 10, "startup_cache_misses": 0,
        "programs_in_window": 1,
        # 100 - (1.5 + .5 + 10 + 1) - (40 - 8 - 4) - (3 + 2 + 26), and
        # in a serve cell the 10 s of programs asked for in the lead-in
        # (of 20 s, before a window that starts at 100.0) are the lead's
        "startup_unaccounted_s": {"train_cell": 28.0, "serve_cell": 38.0}}


def _serve_obs():
    before = _programs(10, 10.0, by_program=BY_PROGRAM, timeline=[
        [60.0, 12.0, 3], [79.5, 30.0, 8], [80.5, 36.0, 9], [99.0, 40.0, 10]])
    return {"setup_s": 100.0, "window": (100.0, 130.0), "setup": {
        "imports_s": 3.0, "cluster_s": 2.0, "lead_s": 20.0,
        "replica_weights_s": 6.0, "replica_up_s": 50.0},
        "before": {"stats": {"startup": STARTUP, "programs": before}},
        "after": {"stats": {"startup": STARTUP,
                            "programs": _programs(11, 10.5)}}}


def _train_obs():
    step = lambda n, **kw: {"step": n, "wall_s": 1.0, "stages": {}, **kw}
    return {"setup_s": 100.0, "warmup_steps": 3, "setup": {
        "imports_s": 3.0, "cluster_s": 2.0, "weights_s": 26.0,
        "fit_s": 90.0},
        "window_stamps": [0.0, 1.0, 2.0, 3.0],
        "steps": [step(1, startup=STARTUP, programs=_programs(
            8, 9.0, by_program=BY_PROGRAM, last=None)),
            step(2), step(3, programs=_programs(2, 1.0, last=None)),
            step(4), step(5, programs=_programs(1, 0.5, last=None)),
            step(6)]}


def _reader(metric):
    module, fn = metric["file"]["reader"].rsplit(".", 1)
    return getattr(importlib.import_module("benchmarks.readers." + module),
                   fn)


def test_the_manifest_gained_these_metrics_and_nothing_else():
    assert [m["name"] for m in NEW] == NAMES
    assert MANIFEST["per_layer"][-len(NEW):] == NEW   # put at the end
    for m in NEW:
        assert m["workloads"] == CELLS and m["better"] == "lower"
    assert manifest_mod.problems(MANIFEST) == []


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_new_metric_resolves_in_its_cell_and_reads_a_hand_made_obs(
        cell_name):
    cell = manifest_mod.resolve(MANIFEST, cell_name)
    mine = [m for m in cell.per_layer if m["moves"] == "setup_s"]
    assert [m["name"] for m in mine] == NAMES
    train = cell.traffic["driver"] == "train_cell"
    for m in mine:
        spec = m["file"]
        assert spec["reader"].startswith("startup.") and spec["what"]
        for key in ("unit", "layer", "moves", "source"):
            assert spec[key] == m[key], (m["name"], key)
        obs = _train_obs() if train else _serve_obs()
        want = WANT[m["name"]]
        if isinstance(want, dict):
            want = want[cell.traffic["driver"]]
        assert _reader(m)(obs, **spec["args"]) == pytest.approx(want), \
            m["name"]
        # the parent's program has no log: nothing to read, and no error
        if train:
            obs["steps"] = [{k: v for k, v in s.items()
                             if k not in ("startup", "programs")}
                            for s in obs["steps"]]
        else:
            for end in ("before", "after"):
                obs[end] = {"stats": {"batches": 3}, "programs": 7}
        assert _reader(m)(obs, **spec["args"]) is None


@pytest.mark.parametrize("cell_name", [
    c["name"] for c in MANIFEST["workloads"] if c["name"] not in CELLS])
def test_the_other_cells_keep_the_metrics_they_had(cell_name):
    cell = manifest_mod.resolve(MANIFEST, cell_name)
    assert not [m for m in cell.per_layer if m["moves"] == "setup_s"]


def _env(chips):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(JAX_PLATFORMS="cpu", TPU_VISIBLE_CHIPS=",".join(
        map(str, range(chips))), XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={chips}"))
    return env


def _as_numbers(metrics: dict):
    assert set(NAMES) <= set(metrics), sorted(metrics)
    for name in NAMES:
        assert isinstance(metrics[name]["value"], float), name
    assert metrics["programs_in_window"]["value"] == 0
    assert metrics["startup_chip_wait_s"]["value"] == 0
    for name in ("startup_worker_s", "startup_backend_s",
                 "startup_programs_s", "startup_programs_asked"):
        assert metrics[name]["value"] > 0, name


def test_a_serve_cell_prints_them_at_rehearsal_size(tmp_path):
    """The whole traced run of the chat cell on its twin: every new
    metric is a number, and the count from inside the program equals
    the benchmark's own listener's."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--manifest",
         rehearsal.derive(str(tmp_path)), "--workload", CELLS[0],
         "--seed", "3000000019", "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=_env(1), capture_output=True, text=True, timeout=300)
    lines = [ln for ln in proc.stderr.splitlines()
             if ln.startswith('{"correct"')]
    assert lines, proc.stderr[-2000:]
    metrics = json.loads(lines[-1])["metrics"]
    _as_numbers(metrics)
    assert metrics["programs_in_window"]["value"] == \
        metrics["chat_compiles_in_window"]["value"]
    info = json.loads(next(ln for ln in proc.stderr.splitlines()
                           if ln.startswith('{"info"')))["info"]
    # what the process asked for before the benchmark's listener was
    # there: nothing
    assert metrics["startup_programs_asked"]["value"] == \
        info["programs"]["programs"][0]


_TRAIN_SCRIPT = """
import json, sys, time
T = time.perf_counter()
from benchmarks import manifest, run, train_cell
cell = manifest.resolve(manifest.load(sys.argv[1]), sys.argv[2])
obs = train_cell.run(cell, 3000000019, 3.0, False, sys.argv[3], T)
mine = [m for m in cell.per_layer if m["moves"] == "setup_s"]
cell.per_layer = mine   # the cell's other readers need a chip's trace
print("METRICS " + json.dumps(run.per_layer(cell, obs)))
"""


def test_a_train_cell_prints_them_at_rehearsal_size(tmp_path):
    """The one-chip train cell on its twin, through the cell's own
    `run` and the harness's `per_layer` (a traced run's other readers
    want a chip): every new metric is a number."""
    work = tmp_path / "work"
    work.mkdir()
    proc = subprocess.run(
        [sys.executable, "-c", _TRAIN_SCRIPT,
         rehearsal.derive(str(tmp_path)), CELLS[2], str(work)],
        cwd=ROOT, env=_env(1), capture_output=True, text=True, timeout=300)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("METRICS ")]
    assert lines, proc.stderr[-2000:]
    _as_numbers(json.loads(lines[-1][8:]))
