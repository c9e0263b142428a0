"""CPU rehearsal of what the `EvaByte` configuration brings to the
benchmark: its configuration file against the catalog row, its traffic
file under the shared driver, the bytes and operations its shares of a
peak are shares of, and its readers on a hand-made trace. Nothing here
is a device number. (The cell's whole run on its twin is
test_benchmark_rehearsal.py's `test_cell_runs_end_to_end_at_rehearsal_
size`, which takes every cell of BENCHMARK.json; the model against its
reference is tests/test_evabyte.py.)"""

import json
import os

import numpy as np
import pytest

from benchmarks import eva_model, eva_ops
from benchmarks import manifest as manifest_mod
from benchmarks import model_cell
from benchmarks import traffic as traffic_mod
from benchmarks.readers import model as reader
from benchmarks.readers import spans as spans_reader

ROOT = manifest_mod.ROOT
MANIFEST = manifest_mod.load()
CELL = "EvaByte.bytes-longdoc-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SHAPES = [[6000, 576], [10000, 896], [14500, 1216], [24000, 1792]]


@pytest.fixture(scope="module")
def full():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "EvaByte.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalog_rows_but_for_what_is_reduced(full):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "EvaByte")
    assert full["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert full["source"] == entry["source"]
    assert manifest_mod.problems(MANIFEST) == []
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert full["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in full, key
        if key != "num_hidden_layers":
            assert full[key] == value, key
    assert (full["num_hidden_layers"],
            full["published"]["num_hidden_layers"]) == (8, 32)
    assert len(full["assumed"]) == 8       # (a) to (g), and what they are
    assert [a[:3] for a in full["assumed"][:7]] == [
        f"({c})" for c in "abcdefg"]
    for key in ("departures", "deployment", "held_as", "tolerances", "why"):
        assert full[key], key
    assert set(eva_model.LIMITS) <= set(full["tolerances"])
    assert full["tolerances"]["why"]
    assert (full["model"], eva_model.PROGRAM_MODULE) == (
        "eva_model", "ray_tpu.models.evabyte")


def test_cell_is_a_closed_loop_of_four_fixed_shapes():
    cell = manifest_mod.resolve(MANIFEST, CELL)
    tr = cell.traffic
    assert (tr["driver"], tr["kind"], cell.chips) == (
        "model_cell", "serve_closed", 1)
    assert (tr["clients"], tr["engine"]["max_seq_len"]) == (24, 32768)
    assert 12 <= tr["engine"]["max_batch"] <= 16
    assert tr["engine"]["prefill_chunk"] in (1024, 2048)
    assert tr["shapes"] == SHAPES and tr["schedule_seed"] == 43
    assert tr["engine"]["prefix_cache_entries"] == 0
    assert tr["engine"]["prompt_buckets"] == [8192, 12288, 16384, 24576]
    assert (tr["lead_s"] >= 57, tr["drain_first_tokens_s"], tr["trace_s"]) \
        == (True, 20, 4)
    assert tr["check"] == {"samples": 2, "check_len": 12288,
                           "decode_tokens": 256}
    buckets = tr["engine"]["prompt_buckets"]

    def requests(seed):
        it = model_cell.closed_loop(tr, cell.config["vocab_size"], seed)
        return [next(it) for _ in range(12)]

    a, b = requests(1), requests(3_000_000_019)
    for reqs in (a, b):
        for k in range(0, 12, 4):     # every cycle carries the same work
            assert sorted(len(r.tokens) for r in reqs[k:k + 4]) == \
                [p for p, _ in SHAPES]
            assert sorted(r.max_new_tokens for r in reqs[k:k + 4]) == \
                [o for _, o in SHAPES]
        for r in reqs:
            assert 1 <= min(r.tokens) and max(r.tokens) <= 319
            assert max(buckets) + r.max_new_tokens < \
                tr["engine"]["max_seq_len"]
    # one schedule for every seed: the same lengths in the same order,
    # other byte values
    assert [(len(r.tokens), r.max_new_tokens) for r in a] == \
        [(len(r.tokens), r.max_new_tokens) for r in b]
    assert a[0].tokens != b[0].tokens
    # one bucket a shape, each warmed by one of the warm prompts
    assert [traffic_mod.bucket_of(p, buckets) for p, _ in SHAPES] == buckets
    assert [traffic_mod.bucket_of(w["prompt_len"], buckets)
            for w in tr["warm"]] == buckets
    # the check reads the two shapes that fit its bucket, and the
    # shorter one decodes across its third window's end
    assert [p for p, _ in SHAPES if p <= tr["check"]["check_len"]] == \
        [6000, 10000]
    assert 6000 < 3 * cell.config["window_size"] < \
        6000 + tr["check"]["decode_tokens"]


def test_a_program_without_the_model_fails_at_once(monkeypatch, tmp_path):
    """What the parent commit does with this cell: no cluster, no wait."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    cell = manifest_mod.resolve(MANIFEST, CELL)
    with pytest.raises(RuntimeError, match="ray_tpu.models.evabyte"):
        model_cell.run(cell, 1, 1.0, False, str(tmp_path), 0.0)


def test_required_bytes_and_operations_against_hand_counts(full):
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert layer == 202_375_168
    assert eva_ops.matmul_params(full) == 8 * layer + 4096 * 2560
    # one key and one value of 32 heads x 128 in bfloat16
    assert eva_ops.decode_attn_bytes(full, 1) == 16384.0
    assert eva_ops.attn_flops(full, 1) == 4 * 128 * 32
    # at decode the bytes bound the attention: one query a key
    assert eva_ops.attn_flops(full, 1) / 197e12 < \
        0.01 * eva_ops.decode_attn_bytes(full, 1) / 819e9
    # what a query attends to: its window up to itself, 128 summaries a
    # window before
    assert [int(eva_ops.attended(full, t)) for t in
            (0, 2047, 2048, 6143, 6144, 32767)] == [
        1, 2048, 129, 2048 + 256, 1 + 384, 2048 + 15 * 128]
    # a cycle's attention a byte served, by hand for one shape
    pairs = sum(t % 2048 + 1 + 128 * (t // 2048) for t in range(6575))
    assert eva_ops.attn_flops_per_token(full, [[6000, 576]]) == \
        pytest.approx(8 * 16384.0 * pairs / 6576)
    whole = eva_ops.attn_flops_per_token(full, SHAPES)
    assert 0.05 < whole / (2 * eva_ops.matmul_params(full)) < 0.08


def _hand_made_trace(with_eva: bool):
    scope = "eva_window_attn" if with_eva else "attn"
    dev = [
        ["%while.1 = (s32[]) while(x)", 0, 1000,
         {"path": "jit(step)/decode/while"}],
        ["%custom-call.2 = bf16[8] custom-call(y)", 100, 300,
         {"path": f"jit(step)/decode/while/body/{scope}/pallas_call"}],
        ["%fusion.3 = f32[8] fusion(z)", 500, 100,
         {"path": "jit(step)/decode/while/body/eva_summarise/exp"}],
        ["%fusion.4 = bf16[8] fusion(u)", 1500, 200,
         {"path": f"jit(step)/prefill/while/body/{scope}/dot_general"}],
        ["%fusion.5 = f32[8] fusion(w)", 2000, 400,
         {"path": "jit(step)/prefill/while/body/eva_chunk_attn/exp"}],
        ["%fusion.6 = bf16[8] fusion(m)", 2500, 500,
         {"path": "jit(step)/prefill/while/body/mlp/dot_general"}],
        ["%copy.7 = bf16[8] copy(v)", 3100, 100, {"path": ""}]]
    if not with_eva:
        dev = [ev for ev in dev if "eva_" not in ev[3]["path"]]
    host = [["rayt.engine.decode_dispatch", 50, 20,
             {"active": 16, "live_positions": 1000,
              "decode_window_positions_live": 8000,
              "decode_summaries_live": 6000,
              "decode_window_positions_read": 9000,
              "decode_summaries_read": 6500, "windows_folded": 0}],
            ["rayt.engine.prefill_chunk", 1400, 20,
             {"pos": 0, "chunk": 1024, "last": 0,
              "prefill_window_keys_visible": 4_000_000,
              "prefill_window_keys_visited": 25_000_000,
              "prefill_summaries_visible": 1_000_000,
              "prefill_summaries_visited": 12_000_000,
              "windows_folded": 1}],
            ["rayt.engine.decode_dispatch", 9000, 20,   # after the trace
             {"active": 16, "decode_window_positions_live": 1,
              "decode_summaries_live": 1,
              "decode_window_positions_read": 1,
              "decode_summaries_read": 1}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": host}]}]}


def test_readers_on_a_hand_made_trace(monkeypatch, full):
    from benchmarks import trace_spans

    def use(trace):
        monkeypatch.setattr(trace_spans, "newest_xplane",
                            lambda d: "hand-made")
        monkeypatch.setattr(os.path, "getmtime", lambda p: 1.0)
        monkeypatch.setattr(trace_spans, "events_from_xplane",
                            lambda p: trace)
        spans_reader._reduced.clear()

    use(_hand_made_trace(True))
    obs = {"cell": CELL, "config": full, "device": {"kind": "TPU v5 lite"}}
    # busy: while's self 600 + 300 + 100 + 200 + 400 + 500 + 100 ns
    share = lambda scopes: spans_reader.path_share(obs, scopes)
    assert share(["decode/eva_window_attn", "prefill/eva_window_attn"]) \
        == pytest.approx(100 * 500 / 2200)
    assert share(["decode/eva_chunk_attn", "prefill/eva_chunk_attn"]) \
        == pytest.approx(100 * 400 / 2200)
    assert share(["decode/eva_summarise"]) == pytest.approx(100 * 100 / 2200)
    assert share(["decode/mlp", "prefill/mlp"]) == \
        pytest.approx(100 * 500 / 2200)
    phases = lambda names: spans_reader.phase_share(obs, names)
    assert phases(["prefill"]) == pytest.approx(100 * 1100 / 2200)
    assert phases(["none"]) == pytest.approx(100 * 100 / 2200)
    # the one dispatch span that began in the traced stretch
    assert reader.decode_attn_roofline_share(obs) == pytest.approx(
        100 * (14000 * 16384 / 819e9) / 300e-9)
    assert reader.prefill_attn_roofline_share(obs) == pytest.approx(
        100 * (5_000_000 * 16384 / 197e12) / 600e-9)
    # the cache's read excess, by the fields the helper names
    assert reader.cache_read_excess(obs) == pytest.approx(15500 / 14000)
    # the share of the whole: the window's rate against the peak
    monkeypatch.setattr(reader, "serve_tokens_per_s", lambda obs: 12000.0)
    obs["traffic"] = {"shapes": SHAPES}
    per_token = 2 * eva_ops.matmul_params(full) \
        + eva_ops.attn_flops_per_token(full, SHAPES)
    assert reader.mfu(obs) == pytest.approx(
        100 * per_token * 12000 / 197e12)
    assert 15 < reader.mfu(obs) < 25
    # a program that names no such scope (the parent commit): nothing to
    # read, no error, and the line leaves the metric out
    use(_hand_made_trace(False))
    assert share(["decode/eva_window_attn"]) is None
    assert share(["decode/mlp", "prefill/mlp"]) > 0     # its scope is there
    for fn in (reader.decode_attn_roofline_share,
               reader.prefill_attn_roofline_share, reader.mfu):
        assert fn(obs) is None
    monkeypatch.setattr(trace_spans, "newest_xplane", lambda d: None)
    spans_reader._reduced.clear()
    assert share(["decode/mlp"]) is None and phases(["prefill"]) is None
    assert reader.cache_read_excess(obs) is None
    assert reader.mfu(obs) is None


def test_every_metric_of_the_cell_names_it_and_a_reader_that_is_there():
    """The model's own entries, by name (test_manifest_entries.py holds
    every entry of every cell to its file and its reader)."""
    cell = manifest_mod.resolve(MANIFEST, CELL)
    own = {m["name"]: m for m in cell.per_layer
           if m["name"].startswith("evabyte_")}
    assert set(own) == {"evabyte_window_attn_share",
                        "evabyte_chunk_attn_share",
                        "evabyte_summarise_share"}
    for m in own.values():
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == "serve_tokens_per_s"
        assert m["file"]["what"] and "cell" not in m["file"]["args"]
        assert m["file"]["reader"] == "spans.path_share", m["name"]
    # its yardsticks are the shared entries', read through its helper
    shared = {m["name"]: m for m in cell.per_layer
              if m["file"]["reader"].startswith("model.")}
    assert set(shared) == {"serve_decode_attn_roofline_share",
                           "serve_prefill_attn_roofline_share",
                           "serve_mfu", "serve_cache_read_excess"}
    for m in shared.values():
        assert callable(getattr(reader, m["file"]["reader"].split(".")[1]))
    peaks = {n for n in shared if n.endswith(("roofline_share", "_mfu"))}
    assert all(shared[n]["unit"] == "%" for n in peaks) and len(peaks) == 3
    assert CELL in next(m for m in MANIFEST["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}


def test_program_config_and_reference_hp_from_the_file(full):
    import jax.numpy as jnp

    cfg = eva_model.program_config(full, "serve", max_seq_len=32768)
    assert (cfg.n_layers, cfg.n_heads, cfg.window_size, cfg.chunk_size,
            cfg.n_pred_heads, cfg.vocab_size) == (8, 32, 2048, 16, 8, 320)
    assert cfg.dtype == jnp.bfloat16 == cfg.param_dtype
    assert eva_model.reference_hp(full) == {
        "heads": 32, "window": 2048, "chunk": 16, "pred_heads": 8,
        "rope_theta": 100000.0, "norm_eps": 1e-5}
    ok = {"finite": True, **dict.fromkeys(eva_model.LIMITS, 0.0)}
    tol = full["tolerances"]
    assert eva_model.correct({"checks": [ok, ok]}, tol)
    assert not eva_model.correct({"checks": []}, tol)
    for name in eva_model.LIMITS:       # by one of the limits, not by each
        assert not eva_model.correct(
            {"checks": [ok, {**ok, name: np.float32(tol[name]) * 1.01}]},
            tol)
