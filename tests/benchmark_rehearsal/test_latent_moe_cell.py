"""CPU rehearsal of what the `Kimi-K2.6` configuration brings to the
benchmark: its configuration file against the catalog row, its traffic
file under the shared driver, the bytes and operations its two shares
of a peak are shares of, and its readers on a hand-made trace. Nothing
here is a device number. (The cell's whole run on its twin is
test_benchmark_rehearsal.py's `test_cell_runs_end_to_end_at_rehearsal_
size`, which takes every cell of BENCHMARK.json; the model against its
reference is tests/test_kimi_k2.py.)"""

import json
import os

import pytest

from benchmarks import latent_moe_model, latent_moe_ops
from benchmarks import manifest as manifest_mod
from benchmarks import model_cell
from benchmarks import traffic as traffic_mod
from benchmarks.readers import model as reader
from benchmarks.readers import spans as spans_reader
from benchmarks.readers import sparse_moe as experts_reader

ROOT = manifest_mod.ROOT
MANIFEST = manifest_mod.load()
CELL = "Kimi-K2.6.docqa-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SHAPES = [[4000, 128], [8000, 192], [13000, 256], [23000, 384]]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


@pytest.fixture(scope="module")
def full():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "Kimi-K2.6.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalog_rows_but_for_what_is_reduced(full):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "Kimi-K2.6")
    assert full["reduced"] == entry["reduced"] == REDUCED
    assert full["source"] == entry["source"]
    assert entry["file"] == "benchmarks/configs/Kimi-K2.6.json"
    assert manifest_mod.problems(MANIFEST) == []
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-K2.6")
    assert full["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in full, key
        if key not in REDUCED:
            assert full[key] == value, key
    # what is held, and the published value beside it
    assert full["published"] == {k: row["config"][k] for k in REDUCED}
    assert (full["num_hidden_layers"], full["first_k_dense_replace"]) == (5, 1)
    assert (full["n_routed_experts"], full["router_experts"],
            full["experts_first"]) == (12, 384, 0)
    assert full["n_routed_experts"] >= full["num_experts_per_tok"]
    assert full["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("assumed", "departures", "deployment", "held_as",
                "tolerances"):
        assert full[key], key
    assert set(latent_moe_model.LIMITS) <= set(full["tolerances"])
    assert full["tolerances"]["why"] and full["model"] == "latent_moe_model"
    assert "3,496,763,904" in full["deployment"]
    assert "5,033,164,800" in full["deployment"]


def test_cell_is_a_closed_loop_of_four_fixed_shapes():
    cell = manifest_mod.resolve(MANIFEST, CELL)
    tr = cell.traffic
    assert (tr["driver"], tr["kind"], cell.chips) == (
        "model_cell", "serve_closed", 1)
    assert (tr["clients"], tr["engine"]["max_batch"],
            tr["engine"]["max_seq_len"], tr["engine"]["prefill_chunk"]) == (
        48, 32, 24576, 1024)
    assert tr["shapes"] == SHAPES and tr["schedule_seed"] == 46
    assert tr["engine"]["prefix_cache_entries"] == 0
    buckets = tr["engine"]["prompt_buckets"]
    assert buckets == [4096, 8192, 13312, 23552]
    assert all(b % tr["engine"]["prefill_chunk"] == 0 for b in buckets)

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
            assert 1 <= min(r.tokens) and \
                max(r.tokens) < cell.config["vocab_size"] == 20480
            assert max(buckets) + r.max_new_tokens < \
                tr["engine"]["max_seq_len"]
    # one schedule for every seed: the same lengths in the same order,
    # other token ids
    assert [(len(r.tokens), r.max_new_tokens) for r in a] == \
        [(len(r.tokens), r.max_new_tokens) for r in b]
    assert a[0].tokens != b[0].tokens
    # every bucket's chunk program is warmed by one of the warm prompts
    assert {traffic_mod.bucket_of(w["prompt_len"], buckets)
            for w in tr["warm"]} == set(buckets) == \
        {traffic_mod.bucket_of(p, buckets) for p, _ in tr["shapes"]}
    # the check's samples: the 8,000- and the 4,000-token shape
    chk = tr["check"]
    assert [p for p, _ in SHAPES if p <= chk["check_len"]] == [4000, 8000]
    assert (chk["samples"], chk["check_len"]) == (2, 8192)
    # 21 slots would keep the prefill queue fed: mean prompt 12 chunks,
    # mean answer 240 rounds
    chunks = sum(-(-p // 1024) for p, _ in SHAPES) / 4
    rounds = sum(o for _, o in SHAPES) / 4
    assert (chunks, rounds) == (12.0, 240.0)
    assert 1 + rounds / chunks <= 21 < tr["engine"]["max_batch"]


def test_a_program_without_the_model_fails_at_once(monkeypatch, tmp_path):
    """What the parent commit does with this cell: no cluster, no wait."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    cell = manifest_mod.resolve(MANIFEST, CELL)
    with pytest.raises(RuntimeError, match="kimi_k2"):
        model_cell.run(cell, 1, 1.0, False, str(tmp_path), 0.0)


def test_required_bytes_and_operations_against_hand_counts(full):
    ops = latent_moe_ops
    # 101.1 M of attention matrices a layer, 44.0 M an expert
    assert ops.attention_params(full) == 101_122_048
    assert ops.expert_params(full) == 3 * 7168 * 2048 == 44_040_192
    assert ops.held_pairs_per_token(full) == 0.25
    assert ops.head_params(full) == 7168 * 20480
    # 5 x attention, the dense MLP, 4 x (router, shared, a quarter expert)
    assert ops.matmul_params(full) == (
        5 * 101_122_048 + 3 * 7168 * 18432
        + 4 * (7168 * 384 + 1.25 * 44_040_192))
    assert 1.13e9 < ops.matmul_params(full) < 1.14e9
    # a cached row is 1,152 B without its fill; 64 heads x (576 + 512) x 2
    assert ops.decode_attn_bytes(full, 1000) == 1_152_000.0
    assert ops.decode_attn_flops(full, 1) == 64 * 2.0 * 1088 == 139_264
    # memory-bound, at half the ridge
    assert ops.decode_attn_flops(full, 1) / 197e12 == pytest.approx(
        0.5 * ops.decode_attn_bytes(full, 1) / 819e9, rel=0.01)
    assert ops.attn_flops(full, 1) == 64 * 2.0 * 320
    # a token attends to 8.2 k keys at the mean of one cycle: 1.68 GFLOP
    # of attention beside 2.27 of matrix products and 0.006 of the head
    per_token = ops.flops_per_token(full, SHAPES)
    tokens = sum(p + o for p, o in SHAPES)
    pairs = sum((p + o - 1) * (p + o) // 2 for p, o in SHAPES)
    assert 8000 < pairs / tokens < 8300
    attn = 5 * 64 * 640.0 * pairs / tokens
    assert 1.6e9 < attn < 1.7e9
    assert per_token == pytest.approx(
        2 * ops.matmul_params(full) * (tokens - 4) / tokens
        + 2 * ops.head_params(full) * 960 / tokens + attn)
    assert 3.9e9 < per_token < 4.0e9


def _hand_made_trace(scope: str):
    dev = [
        ["%custom-call.1 = bf16[32,64,512] custom-call(x)", 0, 1000,
         {"path": f"jit(step)/decode/{scope}/pallas_call"}],
        ["%fusion.2 = bf16[32,64,128] fusion(y)", 1200, 200,
         {"path": f"jit(step)/decode/{scope}/bhc,chv->bhv/dot_general"}],
        ["%fusion.3 = bf16[8] fusion(z)", 2000, 400,
         {"path": "jit(step)/prefill/mla_q/dot_general"}],
        ["%custom-call.4 = bf16[1,64,1024,128] custom-call(q)", 2400, 500,
         {"path": "jit(step)/prefill/mla_prefill_attn/pallas_call"}],
        ["%fusion.5 = bf16[8] fusion(e)", 2900, 100,
         {"path": "jit(step)/decode/moe_experts/while/body/dot_general"}],
        ["%copy.6 = bf16[8] copy(v)", 3000, 100, {"path": ""}]]
    host = [["rayt.engine.decode_dispatch", 50, 20,
             {"active": 30, "live_positions": 300_000,
              "decode_latent_positions_live": 1_500_000,
              "decode_latent_positions_read": 1_650_000}],
            ["rayt.engine.decode_dispatch", 1300, 20,
             {"active": 32, "live_positions": 310_000,
              "decode_latent_positions_live": 1_550_000,
              "decode_latent_positions_read": 1_700_000}],
            ["rayt.engine.prefill_chunk", 1900, 20,
             {"pos": 0, "chunk": 1024, "last": 0,
              "prefill_latent_keys_visible": 2_000_000,
              "prefill_latent_keys_visited": 2_600_000}],
            ["rayt.engine.emit", 2950, 20,
             {"active": 32, "finished": 0, "expert_rows": 9,
              "experts_hit": 7, "expert_tiles": 7}],
            ["rayt.engine.decode_dispatch", 9000, 20,   # after the trace
             {"active": 1, "live_positions": 5}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": host}]}]}


def _metric(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_readers_on_a_hand_made_trace(monkeypatch, full):

    from benchmarks import trace_spans

    def use(trace):
        monkeypatch.setattr(trace_spans, "newest_xplane",
                            lambda d: "hand-made")
        monkeypatch.setattr(os.path, "getmtime", lambda p: 1.0)
        monkeypatch.setattr(trace_spans, "events_from_xplane",
                            lambda p: trace)
        spans_reader._reduced.clear()

    tr = manifest_mod.resolve(MANIFEST, CELL).traffic
    obs = {"cell": CELL, "config": full, "traffic": tr,
           "device": {"kind": "TPU v5 lite"}}
    monkeypatch.setattr(reader, "serve_tokens_per_s", lambda obs: 16_000.0)
    use(_hand_made_trace("mla_decode_attn"))
    # 610,000 live positions x 5 layers x 1,152 B at 819 GB/s over the
    # 1,200 ns under decode's scope: the bytes bound it
    positions = 610_000 * 5
    assert reader.decode_attn_roofline_share(obs) == pytest.approx(
        100 * (positions * 1152 / 819e9) / 1200e-9)
    assert reader.mfu(obs) == pytest.approx(
        100 * latent_moe_ops.flops_per_token(full, SHAPES) * 16_000 / 197e12)
    assert 31 < reader.mfu(obs) < 33
    # a chunk's 2,000,000 visible pairs (the program sums them over the
    # layers) x 64 heads x 2 x 320 at 197 TFLOP/s over prefill's 500 ns
    assert reader.prefill_attn_roofline_share(obs) == pytest.approx(
        100 * (2_000_000 * 40_960 / 197e12) / 500e-9)
    # 7 experts hit x 88 MB at 819 GB/s over the 100 ns under decode's
    # moe_experts (the 9 pairs' operations bound nothing)
    assert experts_reader.experts_roofline_share(obs) == pytest.approx(
        100 * (7 * 88_080_384 / 819e9) / 100e-9)
    # what the entries that are data name: the read excess, the shares
    assert reader.cache_read_excess(obs) == \
        pytest.approx(3_350_000 / 3_050_000)
    share = lambda name: spans_reader.path_share(
        obs, **_metric(name)["args"])
    assert share("kimik2_mla_attn_share") == pytest.approx(100 * 1700 / 2300)
    assert share("serve_attn_proj_share") == pytest.approx(100 * 400 / 2300)
    assert share("serve_moe_experts_share") == \
        pytest.approx(100 * 100 / 2300)
    assert share("serve_moe_shared_router_share") is None   # not in it
    # a program that names no such scope (the parent commit): nothing to
    # read, no error, and the line leaves the metric out
    use(_hand_made_trace("sparse_attn"))
    assert reader.decode_attn_roofline_share(obs) is None
    assert reader.prefill_attn_roofline_share(obs) > 0   # its scope is there
    monkeypatch.setattr(trace_spans, "newest_xplane", lambda d: None)
    spans_reader._reduced.clear()
    for fn in (reader.decode_attn_roofline_share, reader.mfu,
               reader.prefill_attn_roofline_share, reader.cache_read_excess,
               experts_reader.experts_roofline_share):
        assert fn(obs) is None


def test_tiles_a_decode_step_are_the_counter_over_the_rounds():
    from benchmarks.readers import engine

    tiles = _metric("kimik2_moe_expert_tiles")
    assert tiles["reader"] == "engine.counter_per_round"
    ends = lambda a, b: {"before": {"stats": a}, "after": {"stats": b}}
    obs = ends({"batches": 100, "moe_expert_tiles": 900},
               {"batches": 300, "moe_expert_tiles": 2500})
    assert engine.counter_per_round(obs, **tiles["args"]) == 8.0
    # a program from before the counter, and a window without a round
    assert engine.counter_per_round(
        ends({"batches": 1}, {"batches": 3}), **tiles["args"]) is None
    assert engine.counter_per_round(
        ends({"batches": 3, "moe_expert_tiles": 1},
             {"batches": 3, "moe_expert_tiles": 1}), **tiles["args"]) is None


def test_every_metric_of_the_cell_names_it_and_a_reader_that_is_there():
    """The model's own entries, by name (test_manifest_entries.py holds
    every entry of every cell to its file and its reader)."""
    cell = manifest_mod.resolve(MANIFEST, CELL)
    own = {m["name"]: m for m in cell.per_layer
           if m["name"].startswith("kimik2_")}
    assert set(own) == {"kimik2_moe_expert_tiles", "kimik2_mla_attn_share"}
    for m in own.values():
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == "serve_tokens_per_s" and m["file"]["what"]
        assert "cell" not in m["file"]["args"], m["name"]
    # its yardsticks are the shared entries', read through its helper
    by_name = {m["name"]: m for m in cell.per_layer}
    assert {"serve_cache_read_excess", "serve_attn_proj_share"} <= \
        set(by_name)
    peaks = {n: by_name[n] for n in (
        "serve_mfu", "serve_decode_attn_roofline_share",
        "serve_prefill_attn_roofline_share")}
    assert all(m["unit"] == "%" and m["better"] == "higher"
               and m["file"]["reader"].startswith("model.")
               for m in peaks.values())
    assert peaks["serve_mfu"]["layer"] == "the whole"
    assert {m["layer"] for n, m in peaks.items() if n != "serve_mfu"} == \
        {"kernels"}
    # the shared readings the cell reports beside its own
    assert {"serve_device_idle_share", "serve_batch_occupancy",
            "serve_prefill_ms_per_ktok", "startup_programs_s",
            "serve_moe_experts_roofline_share"} <= \
        {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                   "setup_s"}
    assert "Kimi-K2.6" in [c["name"] for c in MANIFEST["configs"]]


def test_program_config_and_reference_hp_from_the_file(full):
    import jax.numpy as jnp

    cfg = latent_moe_model.program_config(full, "serve", max_seq_len=24576)
    assert (cfg.max_seq_len, cfg.dtype) == (24576, jnp.bfloat16)
    assert (cfg.rope_factor, cfg.rope_original_len, cfg.rope_theta) == (
        64.0, 4096, 5e4)
    hp = latent_moe_model.reference_hp(full)
    assert hp["rope_scaling"] == full["rope_scaling"]
    assert (hp["heads"], hp["nope"], hp["rope"], hp["v"], hp["kv_rank"]) == (
        64, 128, 64, 128, 512)
    assert (hp["experts_per_tok"], hp["routed_scaling"],
            hp["experts_first"]) == (8, 2.827, 0)
    assert latent_moe_model.PROGRAM_MODULE == "ray_tpu.models.kimi_k2"
