"""CPU rehearsal of what the `Laguna-S-2.1` configuration brings to the
benchmark: its configuration file against the catalog row, its traffic
file under the shared driver, the bytes and operations its shares of a
peak are shares of, its readers on a hand-made trace, and the cell's
whole run on its twin, `correct`. Nothing here is a device number. (The
model against its reference is tests/test_laguna.py; every entry of the
cell against a hand-made run of its driver is test_manifest_entries.py,
which takes the new pairs as data.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import gqa_moe_model, gqa_moe_ops
from benchmarks import manifest as manifest_mod
from benchmarks import model_cell, rehearsal
from benchmarks import traffic as traffic_mod
from benchmarks.readers import model as reader
from benchmarks.readers import spans as spans_reader
from benchmarks.readers import sparse_moe as experts_reader

ROOT = manifest_mod.ROOT
MANIFEST = manifest_mod.load()
CELL = "Laguna-S-2.1.codectx-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SHAPES = [[2000, 384], [6000, 512], [12000, 640], [20000, 768]]
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "gating_types", "num_attention_heads_per_layer", "num_experts",
           "vocab_size"]
PER_LAYER = REDUCED[1:5]
OWN = {"laguna_full_attn_share", "laguna_moe_tile_fill"}
# its yardsticks and the scopes it shares, under the shared names
MODEL = {"serve_mfu", "serve_decode_attn_roofline_share",
         "serve_prefill_attn_roofline_share", "serve_cache_read_excess"}
SCOPES = {"serve_window_attn_share", "serve_attn_proj_share"}


@pytest.fixture(scope="module")
def full():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "Laguna-S-2.1.json")) as f:
        return json.load(f)


def test_configuration_is_the_catalog_rows_but_for_what_is_reduced(full):
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "Laguna-S-2.1")
    assert full["reduced"] == entry["reduced"] == REDUCED
    assert full["source"] == entry["source"]
    assert entry["file"] == "benchmarks/configs/Laguna-S-2.1.json"
    assert manifest_mod.problems(MANIFEST) == []
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    assert full["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in full, key
        if key not in REDUCED:
            assert full[key] == value, key
    # the per-layer lists are the published ones' first five entries: one
    # whole period behind the leading dense layer
    for key in PER_LAYER:
        assert full[key] == row["config"][key][:5], key
    assert full["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert full["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert full["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert full["published"]["num_experts"] == row["config"]["num_experts"]
    assert (full["num_hidden_layers"], full["mlp_only_layers"]) == (5, [0])
    assert (full["num_experts"], full["router_experts"],
            full["experts_first"]) == (64, 256, 0)
    assert full["num_experts"] >= full["num_experts_per_tok"] == 10
    assert full["vocab_size"] * 4 == row["config"]["vocab_size"]
    for key in ("assumed", "departures", "deployment", "held_as",
                "tolerances"):
        assert full[key], key
    assert "confirmed" in full["assumed"][0]
    assert set(gqa_moe_model.LIMITS) <= set(full["tolerances"])
    assert full["tolerances"]["why"] and full["model"] == "gqa_moe_model"
    assert "3,002,017,792" in full["deployment"]
    assert "6,442,450,944" in full["deployment"]


def test_cell_is_a_closed_loop_of_four_fixed_shapes():
    cell = manifest_mod.resolve(MANIFEST, CELL)
    tr = cell.traffic
    assert (tr["driver"], tr["kind"], cell.chips) == (
        "model_cell", "serve_closed", 1)
    assert (tr["clients"], tr["engine"]["max_batch"],
            tr["engine"]["max_seq_len"], tr["engine"]["prefill_chunk"]) == (
        48, 32, 24576, 1024)
    assert tr["shapes"] == SHAPES and tr["schedule_seed"] == 51
    assert tr["engine"]["prefix_cache_entries"] == 0
    buckets = tr["engine"]["prompt_buckets"]
    assert buckets == [2048, 6144, 12288, 20480]
    # whole in chunks, in the chunk kernel's key tiles and the decode
    # kernel's blocks of 512
    assert all(b % tr["engine"]["prefill_chunk"] == 0 and b % 512 == 0
               for b in buckets + [tr["engine"]["max_seq_len"]])

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
                max(r.tokens) < cell.config["vocab_size"] == 25088
            assert max(buckets) + r.max_new_tokens < \
                tr["engine"]["max_seq_len"]
    assert [(len(r.tokens), r.max_new_tokens) for r in a] == \
        [(len(r.tokens), r.max_new_tokens) for r in b]
    assert a[0].tokens != b[0].tokens
    # every bucket's chunk program is warmed by one of the warm prompts
    assert {traffic_mod.bucket_of(w["prompt_len"], buckets)
            for w in tr["warm"]} == set(buckets) == \
        {traffic_mod.bucket_of(p, buckets) for p, _ in tr["shapes"]}
    # the check's samples: the 6,000- and the 2,000-token shape
    chk = tr["check"]
    assert [p for p, _ in SHAPES if p <= chk["check_len"]] == [2000, 6000]
    assert (chk["samples"], chk["check_len"], chk["decode_tokens"]) == (
        2, 6144, 64)
    # a mean request is 10 chunks and 576 decode rounds: a chunk in more
    # than half of all rounds while 32 slots are full
    chunks = sum(-(-p // 1024) for p, _ in SHAPES) / 4
    rounds = sum(o for _, o in SHAPES) / 4
    assert (chunks, rounds) == (10.0, 576.0)
    assert 32 * chunks / rounds > 0.5


def test_a_program_without_the_model_fails_at_once(monkeypatch, tmp_path):
    """What the parent commit does with this cell: no cluster, no wait."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    cell = manifest_mod.resolve(MANIFEST, CELL)
    with pytest.raises(RuntimeError, match="ray_tpu.models.laguna"):
        model_cell.run(cell, 1, 1.0, False, str(tmp_path), 0.0)


def test_required_bytes_and_operations_against_hand_counts(full):
    ops = gqa_moe_ops
    assert (ops.heads(full, ops.FULL), ops.heads(full, ops.SLIDING)) == (
        48, 72)
    assert (ops.layers(full, ops.FULL), ops.layers(full, ops.SLIDING)) == (
        2, 3)
    # Wq and Wo 18.87 M each, Wk + Wv 6.29 M, the gate 0.15 M
    assert ops.attention_params(full, ops.FULL) == (
        2 * 3072 * 48 * 128 + 2 * 3072 * 8 * 128 + 3072 * 48) == 44_187_648
    assert ops.attention_params(full, ops.SLIDING) == 63_135_744
    assert ops.expert_params(full) == 3 * 3072 * 1024 == 9_437_184
    assert ops.shared_expert_params(full) == 9_437_184
    assert ops.held_pairs_per_token(full) == 2.5
    assert ops.head_params(full) == 3072 * 25088
    assert ops.matmul_params(full) == (
        2 * 44_187_648 + 3 * 63_135_744 + 3 * 3072 * 12288
        + 4 * (3072 * 256 + 3.5 * 9_437_184))
    assert 0.52e9 < ops.matmul_params(full) < 0.53e9
    # K and V over 8 kv heads of 128 in bf16: 4,096 B a position
    assert ops.decode_attn_bytes(full, 1000) == 4_096_000.0
    assert ops.attn_flops(full, 1, 0) == 48 * 512
    assert ops.attn_flops(full, 0, 1) == 72 * 512
    # a decode query's operations bound nothing: 6 or 9 heads a kv head
    assert ops.attn_flops(full, 0, 1) / 197e12 < 0.05 * 4096 / 819e9
    per_token = ops.flops_per_token(full, SHAPES)
    tokens = sum(p + o for p, o in SHAPES)
    pairs = sum((p + o - 1) * (p + o) // 2 for p, o in SHAPES)
    assert 7500 < pairs / tokens < 7600
    window = sum(512 * 513 // 2 + (p + o - 1 - 512) * 512 for p, o in SHAPES)
    assert 499 < window / tokens < 500
    attn = (2 * 48 * pairs + 3 * 72 * window) * 512.0 / tokens
    assert 0.42e9 < attn < 0.43e9
    assert per_token == pytest.approx(
        2 * ops.matmul_params(full) * (tokens - 4) / tokens
        + 2 * ops.head_params(full) * 2304 / tokens + attn)
    assert 1.48e9 < per_token < 1.49e9


def _hand_made_trace(scope: str):
    dev = [
        ["%custom-call.1 = bf16[32,8,6,128] custom-call(x)", 0, 1000,
         {"path": f"jit(step)/decode/{scope}/attn/pallas_call"}],
        ["%custom-call.2 = bf16[32,8,9,128] custom-call(y)", 1200, 200,
         {"path": "jit(step)/decode/window_attn/pallas_call"}],
        ["%fusion.3 = bf16[8] fusion(z)", 2000, 400,
         {"path": "jit(step)/prefill/attn_qkv/dot_general"}],
        ["%custom-call.4 = bf16[1,8,6,1024,128] custom-call(q)", 2400, 500,
         {"path": f"jit(step)/prefill/{scope}/pallas_call"}],
        ["%fusion.5 = bf16[8] fusion(e)", 2900, 100,
         {"path": "jit(step)/decode/moe_experts/while/body/dot_general"}],
        ["%fusion.6 = bf16[8] fusion(g)", 3000, 100,
         {"path": "jit(step)/decode/attn_gate_out/dot_general"}],
        ["%copy.7 = bf16[8] copy(v)", 3100, 100, {"path": ""}]]
    host = [["rayt.engine.decode_dispatch", 50, 20,
             {"active": 30, "live_positions": 300_000,
              "decode_full_positions_attended": 600_000,
              "decode_window_positions_attended": 46_080,
              "decode_full_positions_read": 630_000,
              "decode_window_positions_read": 46_080}],
            ["rayt.engine.prefill_chunk", 1900, 20,
             {"pos": 0, "chunk": 1024, "last": 0,
              "prefill_full_keys_visible": 2_000_000,
              "prefill_full_keys_visited": 2_600_000,
              "prefill_window_keys_visible": 1_500_000,
              "prefill_window_keys_visited": 4_700_000}],
            ["rayt.engine.emit", 2950, 20,
             {"active": 32, "finished": 0, "expert_rows": 80,
              "experts_hit": 46, "expert_tiles": 47}],
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
    monkeypatch.setattr(reader, "serve_tokens_per_s", lambda obs: 25_000.0)
    use(_hand_made_trace("full_attn"))
    # 646,080 positions x 4,096 B at 819 GB/s over the 1,200 ns under
    # decode's two scopes: the bytes bound it
    assert reader.decode_attn_roofline_share(obs) == pytest.approx(
        100 * (646_080 * 4096 / 819e9) / 1200e-9)
    assert reader.mfu(obs) == pytest.approx(
        100 * gqa_moe_ops.flops_per_token(full, SHAPES) * 25_000 / 197e12)
    assert 18 < reader.mfu(obs) < 19
    # a chunk's visible pairs (summed over the layers by the program) x
    # their kind's heads x 512 at 197 TFLOP/s over prefill's 500 ns
    assert reader.prefill_attn_roofline_share(obs) == pytest.approx(
        100 * ((2_000_000 * 48 + 1_500_000 * 72) * 512 / 197e12) / 500e-9)
    # 46 experts hit x 18.9 MB at 819 GB/s over the 100 ns under decode's
    # moe_experts (the 80 pairs' operations bound nothing)
    assert experts_reader.experts_roofline_share(obs) == pytest.approx(
        100 * (46 * 18_874_368 / 819e9) / 100e-9)
    # what the entries that are data name
    ratio = lambda name: spans_reader.field_ratio(obs, **_metric(name)["args"])
    assert _metric("laguna_moe_tile_fill")["reader"] == "spans.field_ratio"
    assert reader.cache_read_excess(obs) == pytest.approx(676_080 / 646_080)
    assert ratio("laguna_moe_tile_fill") == pytest.approx(80 / 47)
    share = lambda name: spans_reader.path_share(
        obs, **_metric(name)["args"])
    assert share("laguna_full_attn_share") == pytest.approx(100 * 1500 / 2400)
    assert share("serve_window_attn_share") == pytest.approx(100 * 200 / 2400)
    assert share("serve_attn_proj_share") == pytest.approx(100 * 500 / 2400)
    assert share("serve_moe_experts_share") == \
        pytest.approx(100 * 100 / 2400)
    assert share("serve_mlp_share") is None               # not in it
    # a program that names no such scope (the parent commit): nothing to
    # read, no error, and the line leaves the metric out
    use(_hand_made_trace("mla_decode_attn"))
    assert reader.decode_attn_roofline_share(obs) > 0   # window_attn is there
    assert share("laguna_full_attn_share") is None
    monkeypatch.setattr(trace_spans, "newest_xplane", lambda d: None)
    spans_reader._reduced.clear()
    for fn in (reader.decode_attn_roofline_share, reader.mfu,
               reader.prefill_attn_roofline_share, reader.cache_read_excess,
               experts_reader.experts_roofline_share):
        assert fn(obs) is None


def test_every_metric_of_the_cell_names_it_and_a_reader_that_is_there():
    """The model's own entries, by name (test_manifest_entries.py holds
    every entry of every cell to its file and its reader)."""
    cell = manifest_mod.resolve(MANIFEST, CELL)
    own = {m["name"]: m for m in cell.per_layer
           if m["name"].startswith("laguna_")}
    assert set(own) == OWN
    for m in own.values():
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == "serve_tokens_per_s" and m["file"]["what"]
        assert "cell" not in m["file"]["args"], m["name"]
    by_name = {m["name"]: m for m in cell.per_layer}
    peaks = {n: by_name[n] for n in MODEL - {"serve_cache_read_excess"}}
    assert all(m["unit"] == "%" and m["better"] == "higher"
               and m["file"]["reader"].startswith("model.")
               for m in peaks.values())
    assert peaks["serve_mfu"]["layer"] == "the whole"
    assert {m["layer"] for n, m in peaks.items() if n != "serve_mfu"} == \
        {"kernels"}
    # the shared readings the cell reports beside its own: the sixteen
    # serve_*, the three of the held experts, the eight under setup_s,
    # its model's four yardsticks and the two scopes other models write
    names = set(by_name)
    shared = names - OWN
    assert shared >= MODEL | SCOPES
    assert len(shared) == 16 + 3 + 8 + len(MODEL | SCOPES)
    assert {"serve_device_idle_share", "serve_batch_occupancy",
            "serve_prefill_ms_per_ktok", "serve_mlp_share",
            "serve_lm_head_share", "serve_peak_hbm_gb",
            "serve_moe_experts_share", "serve_moe_shared_router_share",
            "serve_moe_experts_roofline_share", "startup_programs_s",
            "programs_in_window"} <= shared
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                   "setup_s"}
    # no count of cells or configurations: a later PR appends its own
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_program_config_and_reference_hp_from_the_file(full):
    import jax.numpy as jnp

    cfg = gqa_moe_model.program_config(full, "serve", max_seq_len=24576)
    assert (cfg.max_seq_len, cfg.dtype) == (24576, jnp.bfloat16)
    assert (cfg.dim, cfg.heads_full, cfg.heads_sliding, cfg.n_kv_heads,
            cfg.head_dim, cfg.sliding_window) == (3072, 48, 72, 8, 128, 512)
    assert (cfg.hidden_dim, cfg.moe_hidden_dim, cfg.shared_hidden_dim,
            cfg.n_routed_experts, cfg.experts_held, cfg.experts_per_tok,
            cfg.routed_scaling) == (12288, 1024, 1024, 256, 64, 10, 2.5)
    assert (cfg.rope_theta, cfg.rope_factor, cfg.rope_original_len,
            cfg.rope_partial, cfg.swa_rope_theta) == (
        5e5, 128.0, 8192, 0.5, 1e4)
    assert cfg.rope_attention_factor == pytest.approx(1.4852030263919618)
    hp = gqa_moe_model.reference_hp(full)
    assert hp["rope_parameters"] == full["rope_parameters"]
    assert (hp["kv_heads"], hp["head_dim"], hp["sliding_window"]) == (
        8, 128, 512)
    assert (hp["experts_per_tok"], hp["routed_scaling"],
            hp["experts_first"]) == (10, 2.5, 0)
    assert gqa_moe_model.PROGRAM_MODULE == "ray_tpu.models.laguna"


def test_the_cell_runs_correct_on_its_twin_and_prints_the_checks(tmp_path):
    """The cell's whole run on its CPU twin under a SECOND seed
    (test_benchmark_rehearsal.py runs every cell under one): `correct`,
    no failure, every limit beside its reading, and on the info line what
    the model's counters read since the process began."""
    cell = manifest_mod.resolve(MANIFEST, CELL)
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(JAX_PLATFORMS="cpu", TPU_VISIBLE_CHIPS="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--manifest",
         rehearsal.derive(str(tmp_path)), "--workload", CELL,
         "--seed", "7", "--seconds", "3", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0           # computed on the CPU: no result
    lines = [ln for ln in proc.stderr.splitlines()
             if ln.startswith('{"correct"')]
    assert lines, proc.stderr[-2000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["compared"]) == set(gqa_moe_model.LIMITS)
    for name, (value, limit) in result["compared"].items():
        assert 0 <= value <= limit == cell_twin_tolerance(tmp_path, name)
    info = json.loads(next(ln for ln in proc.stderr.splitlines()
                           if ln.startswith('{"info"')))["info"]
    since = info["checks"][0]["engine_since_start"]
    assert since["cache_bytes"]["window"] > 0 < since["cache_bytes"]["kv"]
    for name in ("decode_full_positions_attended",
                 "decode_window_positions_attended",
                 "prefill_full_keys_visible", "prefill_window_keys_visible",
                 "moe_expert_rows", "moe_expert_tiles"):
        assert since[name] > 0, name
    assert since["decode_window_positions_attended"] < \
        since["decode_full_positions_attended"]


def cell_twin_tolerance(tmp_path, name: str):
    with open(os.path.join(str(tmp_path), "configs",
                           "Laguna-S-2.1.json")) as f:
        return json.load(f)["tolerances"][name]
