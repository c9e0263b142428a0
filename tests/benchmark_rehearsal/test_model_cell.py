"""CPU rehearsal of what the `dots3-note-prev` configuration brings to
the benchmark: its configuration file against the catalog row, its
traffic file and driver, the bytes and operations its roofline shares
are shares of, and its readers on a hand-made trace. Nothing here is a
device number. (The cell's whole run on its twin is
test_benchmark_rehearsal.py's `test_cell_runs_end_to_end_at_rehearsal_
size`, which takes every cell of BENCHMARK.json; the model against its
reference is tests/test_dots3_note.py.)"""

import json
import os

import pytest

from benchmarks import manifest as manifest_mod
from benchmarks import model_cell, sparse_moe_model, sparse_moe_ops
from benchmarks import traffic as traffic_mod
from benchmarks.readers import sparse_moe as reader
from benchmarks.readers import spans as spans_reader

ROOT = manifest_mod.ROOT
MANIFEST = manifest_mod.load()
CELL = "dots3-note-prev.longdoc-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def full():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalog_rows_but_for_what_is_reduced(full):
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "dots3-note-prev")
    reduced = ["num_hidden_layers", "layer_types", "n_routed_experts",
               "vocab_size"]
    assert full["reduced"] == entry["reduced"] == reduced
    assert full["source"] == entry["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    assert full["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in full, key
        if key not in reduced:
            assert full[key] == value, key
    # what is held, and the published value beside it
    assert full["num_hidden_layers"] == 5 == len(full["layer_types"])
    assert full["layer_types"] == row["config"]["layer_types"][:5]
    assert (full["n_routed_experts"], full["router_experts"],
            full["experts_first"]) == (32, 256, 0)
    assert full["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert full["published"]["num_hidden_layers"] == 46
    assert full["published"]["n_routed_experts"] == 256
    assert full["published"]["vocab_size"] == 152064
    for key in ("assumed", "departures", "deployment", "held_as",
                "tolerances"):
        assert full[key], key
    assert set(sparse_moe_model.LIMITS) <= set(full["tolerances"])
    assert full["model"] == "sparse_moe_model"


def test_cell_is_a_closed_loop_of_four_fixed_shapes():
    cell = manifest_mod.resolve(MANIFEST, CELL)
    tr = cell.traffic
    assert (tr["driver"], tr["kind"], cell.chips) == (
        "model_cell", "serve_closed", 1)
    assert (tr["clients"], tr["engine"]["max_batch"],
            tr["engine"]["max_seq_len"]) == (48, 32, 24576)
    assert tr["shapes"] == [[3000, 224], [6000, 320], [10000, 448],
                            [20000, 640]]
    assert tr["engine"]["prefix_cache_entries"] == 0
    buckets = tr["engine"]["prompt_buckets"]
    seeds = (1, 3_000_000_019)

    def requests(seed):
        it = model_cell.closed_loop(tr, cell.config["vocab_size"], seed)
        return [next(it) for _ in range(12)]

    a, b = requests(seeds[0]), requests(seeds[1])
    for reqs in (a, b):
        for k in range(0, 12, 4):     # every cycle carries the same work
            assert sorted(len(r.tokens) for r in reqs[k:k + 4]) == \
                [3000, 6000, 10000, 20000]
            assert sorted(r.max_new_tokens for r in reqs[k:k + 4]) == \
                [224, 320, 448, 640]
        for r in reqs:
            assert max(r.tokens) < cell.config["vocab_size"]
            assert min(r.tokens) >= 1
            assert len(r.tokens) <= max(buckets)
            assert max(buckets) + r.max_new_tokens < \
                tr["engine"]["max_seq_len"]
    # one schedule for every seed: the same lengths in the same order,
    # other token ids
    assert [(len(r.tokens), r.max_new_tokens) for r in a] == \
        [(len(r.tokens), r.max_new_tokens) for r in b]
    assert [len(r.tokens) for r in a[:4]] != [len(r.tokens) for r in a[4:8]] \
        or [len(r.tokens) for r in a[4:8]] != [len(r.tokens) for r in a[8:]]
    assert a[0].tokens != b[0].tokens
    assert tr["schedule_seed"] == 32
    again = requests(seeds[0])
    assert [(r.tokens, r.max_new_tokens) for r in a] == \
        [(r.tokens, r.max_new_tokens) for r in again]
    # every bucket's chunk program is warmed by one of the warm prompts
    assert {traffic_mod.bucket_of(w["prompt_len"], buckets)
            for w in tr["warm"]} == set(buckets) == \
        {traffic_mod.bucket_of(p, buckets) for p, _ in tr["shapes"]}
    # the check's samples fit one bucket of check_len
    assert sum(1 for p, _ in tr["shapes"]
               if p <= tr["check"]["check_len"]) >= 2
    # `shapes` and `schedule_seed` are the driver's terms: no other path
    with pytest.raises(KeyError):
        next(model_cell.closed_loop({"shapes": tr["shapes"]}, 100, 1))


def test_check_reads_one_finished_request_of_each_shape_that_fits():
    """The longest shapes first, ended in the window before the drain,
    never one that erred, stopped short or ended before the window."""
    import types

    def stream(n, done, tokens=4, error=None):
        return types.SimpleNamespace(
            done=done, error=error, tokens=[1] * tokens,
            req=types.SimpleNamespace(tokens=[1] * n, max_new_tokens=4))

    window, chk = (100.0, 130.0), {"samples": 2, "check_len": 8192}
    streams = [stream(3000, 110.0), stream(3000, 120.0), stream(6000, 135.0),
               stream(6000, 128.0), stream(10000, 115.0), stream(6000, 90.0),
               stream(6000, 105.0, error="x"), stream(6000, 106.0, tokens=3),
               stream(20000, None)]
    for seed in (1, 2, 3_000_000_019):
        got = model_cell.check_samples(streams, window, seed, chk)
        assert [len(s.req.tokens) for s in got] == [6000, 3000]
        assert got[0].done == 128.0 and got[1].done in (110.0, 120.0)
    # none of a shape ended inside the window: one from the drain
    got = model_cell.check_samples(
        [s for s in streams if s.done != 128.0], window, 1, chk)
    assert [(len(s.req.tokens), s.done) for s in got][0] == (6000, 135.0)
    assert model_cell.check_samples(streams, window, 1,
                                    {**chk, "samples": 1})[0].done == 128.0


def test_a_program_without_the_model_fails_at_once(monkeypatch, tmp_path):
    """What the parent commit does with this cell: no cluster, no wait."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    cell = manifest_mod.resolve(MANIFEST, CELL)
    with pytest.raises(RuntimeError, match="dots3_note"):
        model_cell.run(cell, 1, 1.0, False, str(tmp_path), 0.0)


def test_required_bytes_and_operations_against_hand_counts(full):
    assert sparse_moe_ops.expert_params(full) == 3 * 5120 * 1536
    # 47.2 MB for each expert hit
    assert sparse_moe_ops.expert_bytes(full, 1) == 47_185_920.0
    assert sparse_moe_ops.expert_bytes(full, 84) == 84 * 47_185_920.0
    assert sparse_moe_ops.expert_flops(full, 32) == 32 * 2.0 * 23_592_960
    # at decode sizes the bytes bound the experts: one row an expert
    assert sparse_moe_ops.expert_flops(full, 1) / 197e12 < \
        0.01 * sparse_moe_ops.expert_bytes(full, 1) / 819e9
    # 256 B a live position a full layer
    assert sparse_moe_ops.index_score_bytes(full, 1000) == 256_000.0
    assert sparse_moe_ops.index_score_flops(full, 1) == 64 * (2 * 128 + 3)
    assert sparse_moe_ops.index_score_flops(full, 1) / 197e12 < \
        sparse_moe_ops.index_score_bytes(full, 1) / 819e9
    # 1,152 B an attended row; 128 heads x (576 + 512) x 2 operations
    assert sparse_moe_ops.sparse_attn_bytes(full, 2048) == 2048 * 1152.0
    assert sparse_moe_ops.sparse_attn_flops(full, 1) == 128 * 2.0 * 1088


def _hand_made_trace(with_moe: bool):
    scope = "moe_experts" if with_moe else "mlp"
    dev = [
        ["%while.1 = (s32[]) while(x)", 0, 1000,
         {"path": f"jit(step)/decode/{scope}/while"}],
        ["%fusion.2 = bf16[8] fusion(y)", 100, 300,
         {"path": f"jit(step)/decode/{scope}/while/body/dot_general"}],
        ["%fusion.3 = f32[8] fusion(z)", 1200, 200,
         {"path": "jit(step)/decode/index_score/bqhd,bkd->bqhk/dot_general"}],
        ["%fusion.4 = bf16[8] fusion(u)", 1500, 100,
         {"path": "jit(step)/decode/sparse_attn/take_along_axis/gather"}],
        ["%fusion.5 = f32[8] fusion(w)", 2000, 400,
         {"path": "jit(step)/prefill/sparse_attn/while/body/exp"}],
        ["%copy.6 = bf16[8] copy(v)", 3000, 100, {"path": ""}]]
    host = [["rayt.engine.decode_dispatch", 50, 20,
             {"active": 30, "live_positions": 1000,
              "decode_index_positions_scored": 2000,
              "decode_latent_positions_attended": 1500,
              "decode_window_positions_attended": 900}],
            ["rayt.engine.emit", 1100, 20,
             {"active": 30, "finished": 0, "expert_rows": 32,
              "experts_hit": 20}],
            ["rayt.engine.decode_dispatch", 1300, 20,
             {"active": 32, "live_positions": 1100,
              "decode_index_positions_scored": 2200,
              "decode_latent_positions_attended": 1600,
              "decode_window_positions_attended": 950}],
            ["rayt.engine.emit", 2500, 20,
             {"active": 32, "finished": 1, "expert_rows": 30,
              "experts_hit": 22}],
            ["rayt.engine.emit", 9000, 20,      # began after the trace
             {"active": 1, "finished": 0, "expert_rows": 1,
              "experts_hit": 1}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": host}]}]}


def test_operations_a_token_against_hand_counts(full):
    """`serve_mfu`'s yardstick in this cell
    (sparse_moe_ops.flops_per_token), on two requests small enough to
    count by hand and at the cell's shapes."""
    ops = sparse_moe_ops
    # a full layer: q_a 5120 x 1024, q_b 1024 x 128 x 192, kv_a 5120 x
    # 576, the up-projections 512 x 128 x 256, gate 5120 x 128, o 16384 x
    # 5120, the indexer's wq_b 1024 x 64 x 128, key 5120 x 128, weights
    # 5120 x 64
    full_layer = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576
                  + 512 * 128 * 256 + 5120 * 128 + 128 * 128 * 5120
                  + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    sliding = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088
               + 1024 * 64 * 320 + 5120 * 64 + 64 * 128 * 5120)
    assert ops.attention_params(full, "full_attention") == full_layer
    assert ops.attention_params(full, "sliding_attention") == sliding
    assert ops.held_pairs_per_token(full) == 1.0       # 8 x 32 / 256
    expert = 3 * 5120 * 1536
    matrices = (2 * full_layer + 3 * sliding + 3 * 5120 * 13824
                + 4 * (5120 * 256 + expert * (1 + 1.0)))
    assert ops.matmul_params(full) == matrices
    assert 0.95e9 < matrices < 1.05e9
    # [3, 2]: 4 queries that see 1, 2, 3, 4 keys; [3000, 1]: 3000 queries
    # of which the last 952 choose 2,048 and the last 2,487 see 513
    assert ops._first(4, 2048) == 10 and ops._first(4, 3) == 9
    shapes = [[3, 2], [3000, 1]]
    scored = 10 + 3000 * 3001 // 2
    chosen = 10 + 2048 * 2049 // 2 + 952 * 2048
    window = 10 + 513 * 514 // 2 + 2487 * 513
    attention = (2 * (64 * (2 * 128 + 3) * scored + 2 * 128 * 320 * chosen)
                 + 3 * 2 * 64 * 384 * window)
    want = (2 * matrices * 3004 + 2 * 5120 * 19008 * 3 + attention) / 3006
    assert ops.flops_per_token(full, shapes) == pytest.approx(want)
    cell = manifest_mod.resolve(MANIFEST, CELL).traffic["shapes"]
    assert 2.4e9 < ops.flops_per_token(full, cell) < 2.7e9
    own = sparse_moe_model.YARDSTICKS
    assert own.flops_per_token(full, {"shapes": shapes}) == \
        ops.flops_per_token(full, shapes)
    assert own.decode_attn_work is None and own.cache_read is None


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
    # busy: while's self 700 + 300 + 200 + 100 + 400 + 100 ns
    share = lambda scopes: spans_reader.path_share(obs, scopes)
    assert share(["decode/moe_experts"]) == pytest.approx(100 * 1000 / 1800)
    assert share(["decode/sparse_attn", "prefill/sparse_attn"]) == \
        pytest.approx(100 * 500 / 1800)
    assert share(["decode/index_score"]) == pytest.approx(100 * 200 / 1800)
    phases = lambda names: spans_reader.phase_share(obs, names)
    assert phases(["prefill"]) == pytest.approx(100 * 400 / 1800)
    assert phases(["none"]) == pytest.approx(100 * 100 / 1800)
    assert phases(["decode", "prefill", "none"]) == pytest.approx(100.0)
    # the two emit spans that began in the traced stretch: 42 experts hit
    assert reader.experts_roofline_share(obs) == pytest.approx(
        100 * (42 * 47_185_920 / 819e9) / 1000e-9)
    assert reader.index_score_roofline_share(obs) == pytest.approx(
        100 * (4200 * 256 / 819e9) / 200e-9)
    ops_bound = 3100 * 128 * 2.0 * 1088 / 197e12
    assert ops_bound > 3100 * 1152 / 819e9      # just: operations bound it
    assert reader.sparse_attn_roofline_share(obs) == pytest.approx(
        100 * ops_bound / 100e-9)
    # a program that names no such scope (the parent commit): nothing to
    # read, no error, and the line leaves the metric out
    use(_hand_made_trace(False))
    assert share(["decode/moe_experts"]) is None
    assert reader.experts_roofline_share(obs) is None
    assert reader.index_score_roofline_share(obs) > 0   # its scope is there
    monkeypatch.setattr(trace_spans, "newest_xplane", lambda d: None)
    spans_reader._reduced.clear()
    assert share(["decode/index_score"]) is None
    assert phases(["prefill"]) is None
    for fn in (reader.experts_roofline_share,
               reader.index_score_roofline_share,
               reader.sparse_attn_roofline_share):
        assert fn(obs) is None


def test_every_metric_of_the_cell_names_it_and_a_reader_that_is_there():
    """The model's own entries, by name (test_manifest_entries.py holds
    every entry of every cell to its file and its reader)."""
    cell = manifest_mod.resolve(MANIFEST, CELL)
    own = {m["name"]: m for m in cell.per_layer
           if m["name"].startswith("dots3_")}
    assert {"dots3_index_score_share", "dots3_index_topk_share",
            "dots3_sparse_attn_share"} <= set(own)
    assert {"serve_window_attn_share", "serve_attn_proj_share",
            "serve_mfu"} <= {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        assert m["moves"] in ("serve_tokens_per_s", "setup_s"), m["name"]
    for m in own.values():
        assert m["workloads"] == [CELL], m["name"]
        assert m["file"]["what"] and "cell" not in m["file"]["args"]
        assert not m["file"]["reader"].startswith("ssm.")
    roof = {n for n in own if n.endswith("roofline_share")}
    assert roof == {"dots3_index_score_roofline_share",
                    "dots3_sparse_attn_roofline_share"}
    assert "serve_moe_experts_roofline_share" in {
        m["name"] for m in cell.per_layer}
    assert all(own[n]["unit"] == "%" and own[n]["layer"] == "kernels"
               and own[n]["file"]["reader"].startswith("sparse_moe.")
               and callable(getattr(reader, own[n]["file"]["reader"][11:]))
               for n in roof)
    assert CELL in next(m for m in MANIFEST["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
