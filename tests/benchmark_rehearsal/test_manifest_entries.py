"""The per-layer manifest held by names and meanings, not by lengths and
positions: every (entry, cell) pair of BENCHMARK.json to its metric
file, its reader and a hand-made run of that cell's driver
(hand_made.py, beside this file), and every cell to the entries it must keep,
by name. A later PR appends entries, and its cell's name to the shared
ones, and edits nothing here: its pairs are cases of the same tests.
Nothing here is a device number."""

import importlib
import json
import math
import os
import types

import pytest

import hand_made
import retired_readers
from benchmarks import manifest as manifest_mod
from benchmarks import run
from benchmarks.readers import model as model_reader
from benchmarks.readers import spans

ROOT = manifest_mod.ROOT
MANIFEST = manifest_mod.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
PAIRS = [(m["name"], c) for m in MANIFEST["per_layer"]
         for c in m.get("workloads", CELLS)]
METRICS = os.path.join(manifest_mod.base_dir(MANIFEST), "metrics")

# one entry a reading, for the cells that move the same end-to-end metric
SERVE = {"serve_device_idle_share", "serve_idle_admission_share",
         "serve_idle_token_sync_share", "serve_idle_dispatch_share",
         "serve_idle_handoff_share", "serve_idle_unowned_share",
         "serve_device_unscoped_share", "serve_peak_hbm_gb",
         "serve_batch_occupancy", "serve_prefill_device_share",
         "serve_prefill_ms_per_ktok", "serve_rate_at_first_token",
         "serve_decode_rounds_per_chunk", "serve_lm_head_share",
         "serve_mlp_share", "serve_itl_p90_ms"}
# the held experts' readings, for the cells whose model has them
EXPERTS = {"serve_moe_experts_share", "serve_moe_shared_router_share",
           "serve_moe_experts_roofline_share"}
# a model's own yardsticks, read through its configuration's helper
# (readers/model.py); every serve_tokens_per_s cell has the first
MODEL = {"serve_mfu", "serve_decode_attn_roofline_share",
         "serve_prefill_attn_roofline_share", "serve_cache_read_excess"}
TRAIN = {"train_mfu", "train_host_share", "train_device_idle_share",
         "train_peak_hbm_gb", "train_idle_unowned_share",
         "train_recompute_share", "train_flash_roofline_share"}
STARTUP = {"startup_worker_s", "startup_chip_wait_s", "startup_backend_s",
           "startup_programs_s", "startup_programs_asked",
           "startup_cache_misses", "programs_in_window",
           "startup_unaccounted_s"}
# what each cell must keep, so that a deletion is seen; more is welcome
MUST_HAVE = {
    "internlm2-1.8b.chat": STARTUP | {
        "chat_loadgen_lateness_p99_ms", "chat_proxy_overhead_p50_ms",
        "chat_ttft_p50_ms", "chat_ttft_p75_ms", "chat_engine_queue_p50_ms",
        "chat_batch_occupancy", "chat_engine_tpot_p50_ms",
        "chat_prefill_step_share", "chat_device_idle_share",
        "chat_peak_hbm_gb", "chat_idle_token_sync_share",
        "chat_idle_admission_share", "chat_idle_handoff_share",
        "chat_idle_unowned_share", "chat_prefill_device_share",
        "chat_decode_attention_share", "chat_device_unscoped_share"},
    "internlm2-1.8b.longprompt": SERVE | STARTUP | {
        "serve_mfu", "serve_attn_proj_share",
        "longprompt_decode_attention_share",
        "longprompt_ttft_decode_interleave_share"},
    "internlm2-1.8b.lora-ft": TRAIN | STARTUP | {
        "lora_device_unscoped_share", "lora_flash_share",
        "lora_optimizer_share"},
    "mistral-7b-v0.3.lora-ft-4chip": TRAIN | STARTUP | {
        "lora4_collective_exposed_share"},
    # its decode-only granite_mlp_share stands for serve_mlp_share
    "granite-4.0-h-micro.chat-closed": (SERVE - {"serve_mlp_share"}) | \
    STARTUP | {
        "serve_mfu", "serve_attn_proj_share",
        "granite_decode_attention_share", "granite_mlp_share",
        "granite_ssm_update_share", "granite_ssm_scan_share",
        "granite_ssm_mixer_rest_share",
        "granite_ssm_update_roofline_share"},
    "dots3-note-prev.longdoc-closed": SERVE | STARTUP | EXPERTS | {
        "serve_mfu", "serve_window_attn_share", "serve_attn_proj_share",
        "dots3_index_score_share", "dots3_index_topk_share",
        "dots3_sparse_attn_share",
        "dots3_index_score_roofline_share",
        "dots3_sparse_attn_roofline_share"},
    "EvaByte.bytes-longdoc-closed": SERVE | STARTUP | MODEL | {
        "evabyte_window_attn_share", "evabyte_chunk_attn_share",
        "evabyte_summarise_share", "serve_attn_proj_share"},
    "Kimi-K2.6.docqa-closed": SERVE | STARTUP | EXPERTS | MODEL | {
        "kimik2_moe_expert_tiles", "kimik2_mla_attn_share",
        "serve_attn_proj_share"},
    "Laguna-S-2.1.codectx-closed": SERVE | STARTUP | EXPERTS | MODEL | {
        "laguna_full_attn_share", "laguna_moe_tile_fill",
        "serve_window_attn_share", "serve_attn_proj_share"},
}
# what PR 50 retired: each read 0.0 on both sides of every line, and has
# a twin from inside the program or a test that holds what it guarded
RETIRED = {"chat_compiles_in_window", "longprompt_compiles_in_window",
           "granite_compiles_in_window", "dots3_compiles_in_window",
           "evabyte_programs_in_window", "chat_decode_kv_update_share"}
# what PR 60 merged, each into the shared entry that reads the same
# number in its cell (old name -> new name)
MERGED = {
    "evabyte_mfu": "serve_mfu", "kimik2_mfu": "serve_mfu",
    "laguna_mfu": "serve_mfu",
    "evabyte_decode_attn_roofline_share": "serve_decode_attn_roofline_share",
    "kimik2_mla_decode_attn_roofline_share":
        "serve_decode_attn_roofline_share",
    "laguna_decode_attn_roofline_share": "serve_decode_attn_roofline_share",
    "evabyte_prefill_attn_roofline_share":
        "serve_prefill_attn_roofline_share",
    "kimik2_mla_prefill_attn_roofline_share":
        "serve_prefill_attn_roofline_share",
    "laguna_prefill_attn_roofline_share":
        "serve_prefill_attn_roofline_share",
    "evabyte_cache_read_excess": "serve_cache_read_excess",
    "kimik2_cache_read_excess": "serve_cache_read_excess",
    "laguna_cache_read_excess": "serve_cache_read_excess",
    "dots3_window_attn_share": "serve_window_attn_share",
    "laguna_window_attn_share": "serve_window_attn_share",
    "evabyte_attn_proj_share": "serve_attn_proj_share",
    "laguna_attn_proj_share": "serve_attn_proj_share",
    "kimik2_mla_proj_share": "serve_attn_proj_share",
    "dots3_mla_proj_share": "serve_attn_proj_share"}
RETIRED |= set(MERGED)


@pytest.fixture(scope="module")
def runs():
    """cell -> (the resolved cell, its hand-made obs and trace sums)."""
    made = {}

    def of(name):
        if name not in made:
            cell = manifest_mod.resolve(MANIFEST, name)
            made[name] = (cell, hand_made.obs(cell), hand_made.reduced(cell))
        return made[name]
    return of


@pytest.mark.parametrize("entry,cell_name", PAIRS,
                         ids=[f"{e}@{c}" for e, c in PAIRS])
def test_entry_reads_a_number_in_each_cell_that_lists_it(
        entry, cell_name, runs, monkeypatch):
    cell, obs, both = runs(cell_name)
    m = next(m for m in cell.per_layer if m["name"] == entry)
    # the entry's file is there, names it, and agrees with it
    with open(os.path.join(METRICS, entry + ".json")) as f:
        spec = json.load(f)
    assert spec == m["file"] and spec["name"] == entry and spec["what"]
    for key in ("unit", "layer", "moves", "source"):
        assert spec[key] == m[key], key
    # the cell reports the end-to-end metric the entry should move
    assert m["moves"] in {e["name"] for e in cell.end_to_end}
    # the reader is there; the harness tells it whose run it reads
    assert "cell" not in spec.get("args", {})
    read = run._reader(spec["reader"])
    assert callable(read)
    # a number from a hand-made run of this cell's driver ...
    monkeypatch.setattr(spans, "_both", lambda name: both)
    value = read(obs, **spec.get("args", {}))
    assert isinstance(value, (int, float)) and math.isfinite(value), value
    # ... and nothing, and no error, from a run without its inputs
    monkeypatch.setattr(spans, "_both", lambda name: (None, None))
    assert read(hand_made.bare(cell), **spec.get("args", {})) is None


@pytest.mark.parametrize("cell_name", sorted(MUST_HAVE))
def test_each_cell_keeps_its_entries_by_name(cell_name):
    cell = manifest_mod.resolve(MANIFEST, cell_name)
    names = {m["name"] for m in cell.per_layer}
    assert names >= MUST_HAVE[cell_name], MUST_HAVE[cell_name] - names
    assert not names & RETIRED


def test_the_manifest_leaves_room_and_no_file_is_left_over():
    assert len(MANIFEST["per_layer"]) <= 96       # of the contract's 128
    assert manifest_mod.problems(MANIFEST) == []
    entries = {m["name"] for m in MANIFEST["per_layer"]}
    files = {f[:-5] for f in os.listdir(METRICS) if f.endswith(".json")}
    assert files == entries
    assert not entries & RETIRED
    # one entry a reading: no second entry that moves the same metric
    # through the same reader with the same arguments
    seen = {}
    for m in MANIFEST["per_layer"]:
        with open(os.path.join(METRICS, m["name"] + ".json")) as f:
            spec = json.load(f)
        key = (m["moves"], spec["reader"],
               json.dumps(spec.get("args", {}), sort_keys=True))
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


OLD_READERS = [(module, fn) for module in retired_readers.CELLS
               for fn in retired_readers.FUNCTIONS]


@pytest.mark.parametrize("module,fn", OLD_READERS,
                         ids=[f"{m}.{f}" for m, f in OLD_READERS])
def test_the_one_reader_reads_what_the_models_own_reader_read(
        module, fn, runs, monkeypatch):
    """readers/<module>.<fn>, as it stood before PR 60 merged it
    (retired_readers.py), and readers/model.<fn>, which finds the same
    arithmetic through the configuration's helper: the same number to
    the last digit on that model's hand-made run, and both nothing where
    there is nothing to read."""
    cell, obs, both = runs(retired_readers.CELLS[module])
    old = getattr(retired_readers, module + "_" + fn)
    new = getattr(model_reader, fn)
    monkeypatch.setattr(spans, "_both", lambda name: both)
    value = old(obs)
    assert isinstance(value, float) and math.isfinite(value) and value > 0
    assert new(obs) == value
    monkeypatch.setattr(spans, "_both", lambda name: (None, None))
    assert old(hand_made.bare(cell)) is None
    assert new(hand_made.bare(cell)) is None


NO_YARDSTICK = [
    # a helper that names the operations a token and no attention work
    ("dots3-note-prev.longdoc-closed", "decode_attn_roofline_share"),
    ("dots3-note-prev.longdoc-closed", "prefill_attn_roofline_share"),
    ("dots3-note-prev.longdoc-closed", "cache_read_excess"),
    # configurations with no "model" key, found by their driver
    ("internlm2-1.8b.longprompt", "decode_attn_roofline_share"),
    ("internlm2-1.8b.longprompt", "cache_read_excess"),
    ("granite-4.0-h-micro.chat-closed", "prefill_attn_roofline_share"),
    # a driver that names no yardsticks at all
    ("internlm2-1.8b.lora-ft", "mfu"),
]


@pytest.mark.parametrize("cell_name,fn", NO_YARDSTICK,
                         ids=[f"{f}@{c}" for c, f in NO_YARDSTICK])
def test_a_yardstick_a_model_does_not_name_reads_nothing(
        cell_name, fn, runs, monkeypatch):
    """The generic readers give None, and no error, for a cell whose
    helper or driver names no yardstick of that kind, on a run in which
    every other reader of the cell finds something to read."""
    _, obs, both = runs(cell_name)
    monkeypatch.setattr(spans, "_both", lambda name: both)
    assert getattr(model_reader, fn)(obs) is None


@pytest.mark.parametrize("fn", retired_readers.FUNCTIONS)
@pytest.mark.parametrize("config,traffic", [
    ({}, {"driver": "no_such_driver"}),        # no model key, unknown driver
    ({"model": "no_such_model"}, {"driver": "serve_cell"}),
    ({}, {}), ({"model": "manifest"}, {})],    # a module with no YARDSTICKS
    ids=["unknown-driver", "unknown-model", "neither", "no-table"])
def test_a_configuration_the_readers_cannot_place_reads_nothing(
        fn, config, traffic, runs, monkeypatch):
    _, obs, both = runs("Kimi-K2.6.docqa-closed")
    monkeypatch.setattr(spans, "_both", lambda name: both)
    assert getattr(model_reader, fn)(obs) is not None     # placed: a number
    lost = {**obs, "config": config, "traffic": traffic}
    assert model_reader.yardsticks(lost) is None
    assert getattr(model_reader, fn)(lost) is None


def test_a_models_yardsticks_name_what_its_hand_made_run_holds(runs):
    """Every helper or driver that holds a YARDSTICKS names scopes as
    "<phase>/<scope>" and span fields that the hand-made trace of its
    cell then carries, so the pairs above read a number with no
    hand_made/<module>.json for readers/model.py."""
    seen = 0
    for name in CELLS:
        cell, obs, _ = runs(name)
        own = model_reader.yardsticks(obs)
        if own is None or not any(m["file"]["reader"].startswith("model.")
                                  for m in cell.per_layer):
            continue
        seen += 1
        reads = own.reads()
        scopes, fields, _ = hand_made.needs(cell)
        assert reads["scopes"] and set(reads["scopes"]) <= set(scopes)
        assert all(s.split("/")[0] in ("decode", "prefill")
                   for s in reads["scopes"])
        for span, names in reads["fields"].items():
            assert span.startswith("rayt.engine.") and names
            assert set(names) <= set(fields[span])
    assert seen >= 6       # every serve_tokens_per_s cell, and any later
    # and every name PR 60 merged lives on as an entry of the manifest
    assert set(MERGED.values()) <= {m["name"] for m in MANIFEST["per_layer"]}


def test_what_a_hand_made_trace_must_hold_for_a_reader_module():
    """hand_made/<module>.json, where a reader module has one, names
    scopes and span fields in the forms hand_made.py builds a trace
    from, and names a module that is there."""
    here = os.path.join(os.path.dirname(__file__), "hand_made")
    for f in sorted(os.listdir(here)):
        assert f.endswith(".json"), f
        importlib.import_module("benchmarks.readers." + f[:-5])
        made = hand_made.module_reads(f[:-5])
        assert made and set(made) <= {"scopes", "fields"}, f
        assert all("/" in s for s in made.get("scopes", [])), f
        assert all(span.startswith("rayt.") and names
                   for span, names in made.get("fields", {}).items()), f


def test_the_harness_names_the_cell_to_the_readers(runs, monkeypatch):
    """run.per_layer puts the cell's name, configuration and traffic
    into `obs` (serve_cell's own `obs` holds no configuration: the
    long-prompt cell's first traced run of PR 60 failed on it); every
    reader that reads the trace asks for that cell's, and the line holds
    every entry of the cell."""
    cell, obs, both = runs("Kimi-K2.6.docqa-closed")
    asked = set()
    monkeypatch.setattr(spans, "_both",
                        lambda name: asked.add(name) or both)
    line = run.per_layer(cell, {k: v for k, v in obs.items()
                                if k not in ("cell", "config", "traffic")})
    assert asked == {cell.name}
    assert set(line) == {m["name"] for m in cell.per_layer}
    assert all(isinstance(v["value"], float) and v["unit"]
               for v in line.values())
    # the idle shares by owner sum to the device's idle share of the
    # trace (the hand-made one also holds two train spans' gaps)
    red = both[0]
    owned = sum(line[n]["value"] for n in SERVE if "_idle_" in n
                and n != "serve_device_idle_share")
    train = sum(v for k, v in red["idle_s"].items()
                if k.startswith("rayt.train."))
    assert owned + 100 * train / red["window_s"] == \
        pytest.approx(100 * (1 - red["busy_s"] / red["window_s"]))


def test_a_failed_cell_prints_the_chain_of_its_causes(capsys, monkeypatch,
                                                       tmp_path):
    def fail():
        try:
            try:
                raise OSError("open(/dev/vfio/0): Device or resource busy")
            except OSError as e:
                raise RuntimeError("backend 'tpu' failed") from e
        except RuntimeError:
            raise ValueError("training failed after 0 restarts")

    with pytest.raises(ValueError) as err:
        fail()
    assert [type(c) for c in run.causes(err.value)] == [RuntimeError,
                                                       OSError]
    assert run.causes(ValueError("alone")) == []
    # through `main`: one line a cause on standard error, and no result
    import benchmarks.serve_cell as driver
    monkeypatch.setattr(driver, "run", lambda *a: fail())
    # `main` empties the cell's work directory, which another test's run
    # of this cell may be using in the checkout: give it one of its own
    monkeypatch.setattr(run, "manifest_mod", types.SimpleNamespace(
        **{**vars(manifest_mod), "ROOT": str(tmp_path)}))
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) == 1
    out, errs = capsys.readouterr()
    assert out == ""
    lines = [ln for ln in errs.splitlines() if "because of" in ln]
    assert len(lines) == 2 and "vfio" in lines[1]


def _at_limits(cell, over=None, by=1.01):
    """An `obs` whose checks read every limit of the configuration
    itself, and the one named `over` (as `compared` names it) 1% more,
    in the last check of two where the driver checks requests."""
    tol = cell.config["tolerances"]
    at = lambda name, limit: limit * (by if name == over else 1.0)
    if cell.traffic["driver"] == "train_cell":
        return {"check": {
            "finite": True, "loss_rel": at("loss_rel", tol["loss_rel"]),
            "grad_rel_rms": {proj + "_a": at("grad_rel_rms." + proj + "_a",
                                             limit)
                             for proj, limit in tol["grad_rel_rms"].items()}}}
    check = {name: at(name, limit) for name, limit in tol.items()
             if isinstance(limit, (int, float))}
    # serve_cell's name for the reading under `token_margin_logits`
    check.update(finite=True, token_max_margin=check["token_margin_logits"])
    return {"checks": [dict(check, **{k: 0.0 for k in check
                                      if k != "finite"}), check],
            "config": cell.config}


@pytest.mark.parametrize("cell_name", CELLS)
def test_compared_says_what_correct_decided(cell_name):
    """Every driver's `compared` names each number its `correct` holds
    to a limit of the configuration, the worst over the checks: readings
    at their limits pass, and any one of them over its limit fails, as
    does one that is no number, which reads None and not the other
    checks' largest."""
    cell = manifest_mod.resolve(MANIFEST, cell_name)
    driver = importlib.import_module("benchmarks." + cell.traffic["driver"])
    tol = cell.config["tolerances"]
    obs = _at_limits(cell)
    said = driver.compared(obs, tol)
    assert said and driver.correct(obs, tol)
    assert all(value == limit for value, limit in said.values())
    for name in said:
        obs = _at_limits(cell, over=name)
        assert not driver.correct(obs, tol), name
        worse = driver.compared(obs, tol)
        assert [n for n, (value, limit) in worse.items()
                if value > limit] == [name]
        for no_number in (math.nan, math.inf):
            obs = _at_limits(cell, over=name, by=no_number)
            assert not driver.correct(obs, tol), name
            assert [n for n, (value, _) in driver.compared(obs, tol).items()
                    if value is None] == [name]
    if "checks" in obs:     # no finished request to check: not correct
        none = {"checks": [], "config": cell.config}
        assert driver.compared(none, tol) == {}
        assert not driver.correct(none, tol)
