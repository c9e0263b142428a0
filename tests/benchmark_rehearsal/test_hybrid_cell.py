"""CPU rehearsal of what the hybrid (Mamba-2 + attention) configuration
brings to the benchmark: its configuration and traffic files, its driver
and deployment class, the plain reference behind `correct`, the
state-space scopes' reader and the bytes it is a share of. Nothing here
is a device number. (The cell's whole run on its twin is
test_benchmark_rehearsal.py's `test_cell_runs_end_to_end_at_rehearsal_
size`, which takes every cell of BENCHMARK.json.)"""

import json
import os

import numpy as np
import pytest

from benchmarks import hybrid_cell, hybrid_model, rehearsal, ssm_ops
from benchmarks import manifest as manifest_mod
from benchmarks import traffic as traffic_mod
from benchmarks.readers import spans as spans_reader
from benchmarks.readers import ssm as ssm_reader

ROOT = manifest_mod.ROOT
MANIFEST = manifest_mod.load()
CELL = "granite-4.0-h-micro.chat-closed"


@pytest.fixture(scope="module")
def full():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def twin(full):
    with open(os.path.join(ROOT, "benchmarks", "rehearsal", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return rehearsal.overlay(full, json.load(f))


def test_configuration_is_the_published_one_uncut(full):
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert full["source"] == entry["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    assert full["reduced"] == entry["reduced"] == []
    assert "train" not in full["held_as"]
    cfg = hybrid_model.program_config(full, "serve", max_seq_len=4096)
    assert (cfg.n_layers, cfg.count("mamba"), cfg.count("attention")) == \
        (40, 36, 4)
    assert cfg.period == tuple(full["layer_types"][:10])
    assert (cfg.dim, cfg.d_inner, cfg.conv_dim, cfg.head_dim) == \
        (2048, 4096, 4352, 64)
    assert cfg.tie_embeddings and str(cfg.state_dtype) == "float32"
    assert 3.18e9 < cfg.num_params() < 3.20e9
    hp = hybrid_model.reference_hp(full)
    assert hp["attention"] == 1 / 64 and hp["logits_scaling"] == 8.0


def test_cell_is_a_closed_loop_over_its_own_driver():
    cell = manifest_mod.resolve(MANIFEST, CELL)
    tr = cell.traffic
    assert tr["driver"] == "hybrid_cell" and tr["kind"] == "serve_closed"
    assert tr["clients"] > tr["engine"]["max_batch"]     # a queue behind
    assert tr["engine"]["prefill_chunk"] == cell.config["mamba_chunk_size"]
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    # the model's own scopes, by name, and the kernel's roofline share
    # (test_manifest_entries.py holds every entry of every cell to its
    # file and its reader, and names what each cell must keep)
    own = {m["name"]: m for m in cell.per_layer
           if m["name"].startswith("granite_")}
    assert {"granite_ssm_update_share", "granite_ssm_scan_share",
            "granite_ssm_mixer_rest_share",
            "granite_ssm_update_roofline_share"} <= set(own)
    for m in own.values():
        assert m["workloads"] == [CELL] and m["file"]["what"], m["name"]
        assert "cell" not in m["file"]["args"], m["name"]
    # the same work for every seed, a cycle at a time
    buckets, cyc = tr["engine"]["prompt_buckets"], tr["cycle"]

    def requests(seed):
        it = traffic_mod.closed_loop(tr, 1000, seed)
        return [next(it) for _ in range(3 * cyc)]

    a, b = requests(1), requests(3_000_000_019)
    assert traffic_mod.shape_summary(a, buckets) == \
        traffic_mod.shape_summary(b, buckets)
    assert traffic_mod.shape_summary(a[:cyc], buckets) == \
        traffic_mod.shape_summary(a[cyc:2 * cyc], buckets)
    assert a[0].tokens != b[0].tokens
    for r in a:
        assert len(r.tokens) <= max(buckets)
        assert max(buckets) + r.max_new_tokens < tr["engine"]["max_seq_len"]
    # every warm-up prompt's program is one the mix meets, and every one
    # the mix meets is warmed: bucket, and whether a leading chunk is
    # skipped
    chunk = tr["engine"]["prefill_chunk"]

    def program(n):
        bucket = traffic_mod.bucket_of(n, buckets)
        return bucket, bucket > chunk and (bucket - n) >= chunk

    assert {program(len(r.tokens)) for r in a} <= \
        {program(w["prompt_len"]) for w in tr["warm"]}


def test_a_program_without_the_model_fails_at_once(monkeypatch, tmp_path):
    """What the parent commit does with this cell: no cluster, no wait."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    cell = manifest_mod.resolve(MANIFEST, CELL)
    with pytest.raises(RuntimeError, match="granite_hybrid"):
        hybrid_cell.run(cell, 1, 1.0, False, str(tmp_path), 0.0)


def test_state_update_bytes_are_the_slots_state_in_and_out(full):
    one = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert ssm_ops.ssm_update_bytes(full, 1) == 2.0 * one
    assert ssm_ops.ssm_update_bytes(full, 32) == 64.0 * one
    assert 4.8e9 < ssm_ops.ssm_update_bytes(full, 32) < 4.95e9
    # bytes bound it: operations over the peak are a hundredth of them
    assert ssm_ops.ssm_update_flops(full, 32) / 197e12 < \
        0.01 * ssm_ops.ssm_update_bytes(full, 32) / 819e9


def _hand_made_trace(with_ssm: bool):
    scope = "ssm_update" if with_ssm else "mlp"
    dev = [
        ["%while.1 = (s32[]) while(x)", 0, 1000,
         {"path": "jit(step)/decode/while"}],
        ["%fusion.2 = f32[8] fusion(y)", 100, 300,
         {"path": f"jit(step)/decode/while/body/closed_call/{scope}/mul"}],
        ["%fusion.3 = bf16[8] fusion(z)", 500, 200,
         {"path": "jit(step)/decode/while/body/closed_call/mlp/dot_general"}],
        ["%fusion.4 = f32[8] fusion(w)", 2000, 400,
         {"path": "jit(step)/prefill/while/body/"
          + ("ssm_scan" if with_ssm else "attn") + "/exp"}],
        ["%copy.5 = bf16[8] copy(v)", 3000, 100, {"path": ""}]]
    host = [["rayt.engine.decode_dispatch", 50, 20, {"active": 30}],
            ["rayt.engine.decode_dispatch", 1500, 20, {"active": 32}],
            ["rayt.engine.decode_dispatch", 9000, 20, {"active": 1}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": host}]}]}


def test_operations_a_token_against_hand_counts(full):
    """`serve_mfu`'s yardstick in this cell (ssm_ops.flops_per_token)."""
    from benchmarks import attention_ops

    tr = manifest_mod.resolve(MANIFEST, CELL).traffic
    c = attention_ops.cycle_sums(tr)
    assert (c["tokens"], c["passed"], c["outputs"]) == (
        50 + 136 + 293 + 795 + 153 + 222 + 295 + 430, 2374 - 4, 1100)
    # a Mamba layer: in_z 2048 x 4096, in_xbc 2048 x 4352, in_dt 2048 x 64,
    # out_proj 4096 x 2048; an attention layer: q and o 2048 x 2048, k and
    # v 2048 x 512; the MLP behind either 3 x 2048 x 8192
    mamba = 2048 * (4096 + 4352 + 64) + 4096 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    matrices = 36 * mamba + 4 * attention + 40 * 3 * 2048 * 8192
    assert 2.9e9 < matrices < 3.0e9      # of the model's 3.19 G parameters
    # the recurrence: 5 operations an element of 64 x 64 x 128, 36 layers;
    # the convolution: 4 products and sums a channel of 4352
    a_token = 2 * matrices + 36 * 5 * 64 * 64 * 128 + 36 * 2 * 4 * 4352
    want = (a_token * c["passed"] + 2 * 2048 * 100352 * c["outputs"]
            + 4 * 64 * 32 * 4 * c["pairs"]) / c["tokens"]
    assert ssm_ops.flops_per_token(full, tr) == pytest.approx(want)
    assert 6.1e9 < want < 6.4e9
    assert hybrid_cell.YARDSTICKS.flops_per_token is ssm_ops.flops_per_token


def test_ssm_reader_sums_self_time_by_any_scope_name(monkeypatch, full):
    from benchmarks import trace_spans

    monkeypatch.setattr(trace_spans, "newest_xplane", lambda d: "hand-made")
    monkeypatch.setattr(os.path, "getmtime", lambda p: 1.0)
    monkeypatch.setattr(trace_spans, "events_from_xplane",
                        lambda p: _hand_made_trace(True))
    spans_reader._reduced.clear()
    obs = {"cell": CELL, "config": full, "device": {"kind": "TPU v5 lite"}}
    tab = spans_reader.table(CELL)
    # busy: while's self 500 + 300 + 200 + 400 + 100 ns
    assert tab["busy_s"] == pytest.approx(1500e-9)
    # the third dispatch began after the trace
    assert spans_reader.field_sum(obs, ssm_reader.DISPATCH,
                                  ["active"]) == 30 + 32
    share = lambda scopes: spans_reader.path_share(obs, scopes)
    assert share(["decode/ssm_update"]) == pytest.approx(100 * 300 / 1500)
    assert share(["prefill/ssm_scan"]) == pytest.approx(100 * 400 / 1500)
    assert share(["decode/mlp"]) == pytest.approx(100 * 200 / 1500)
    assert share(["decode/ssm_update", "prefill/ssm_scan"]) == \
        pytest.approx(100 * 700 / 1500)
    phases = lambda names: spans_reader.phase_share(obs, names)
    assert phases(["prefill"]) == pytest.approx(100 * 400 / 1500)
    assert phases(["none"]) == pytest.approx(100 * 100 / 1500)
    assert phases(["decode", "prefill", "none"]) == pytest.approx(100.0)
    least = ssm_ops.ssm_update_bytes(full, 62) / 819e9
    assert ssm_reader.update_roofline_share(obs) == \
        pytest.approx(100 * least / 300e-9)
    # a program that names no such scope: nothing to read, no error
    monkeypatch.setattr(trace_spans, "events_from_xplane",
                        lambda p: _hand_made_trace(False))
    spans_reader._reduced.clear()
    assert share(["decode/ssm_update"]) is None
    assert share(["decode/ssm_update", "decode/mlp"]) == \
        pytest.approx(100 * 500 / 1500)
    assert ssm_reader.update_roofline_share(obs) is None
    monkeypatch.setattr(trace_spans, "newest_xplane", lambda d: None)
    assert share(["decode/mlp"]) is None and phases(["prefill"]) is None
    spans_reader._reduced.clear()


@pytest.fixture(scope="module")
def service(twin):
    """The cell's deployment class on the twin, in this process, with
    the published tied head (the override unties it for llama's sake)."""
    from benchmarks.hybrid_deployment import BenchHybridService

    config = {**twin, "tie_word_embeddings": True}
    return BenchHybridService(config, 3_000_000_019, {
        "max_batch": 2, "max_seq_len": 256, "prompt_buckets": [32, 128],
        "prefill_chunk": 32, "tp": 1}), config


def test_deployment_checks_finished_requests_against_the_reference(service):
    """`correct` as the cell decides it: the engine's own stream, then
    the timed path's prefill and 64 cached steps against the plain
    reference, logits and recurrent state, at the twin's tolerances; a
    state held in bfloat16 comes out as not correct by the state's limit
    alone, weights rounded to multiples of 1/8 by the logits' too, and
    weights rounded to fp8 read over
    2.5 times what the stated precision reads (the twin's limit, which llama's twin
    test shares, is too wide for fp8; tests/test_granite_hybrid.py holds
    a sharper one)."""
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks import serve_cell
    from benchmarks.reference import granite_hybrid_ref

    svc, config = service
    # the twin's own limit for 64 steps; the override's is wide, for the
    # rehearsal's 3 steps after a prompt of a few tokens (its `why`)
    tol = {**config["tolerances"], "state_rel_rms": 0.0065}
    assert "lm_head" not in svc.engine.params
    assert svc.engine.stats()["prefix_cache_entries"] == 0
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (100, 20)]

    async def generate(p):
        return [t async for t in svc.engine.generate(p, max_new_tokens=70)]

    samples = [{"tokens": p, "generated": asyncio.run(generate(p))}
               for p in prompts]
    checks = svc.reference_check(samples, 128, 64)
    assert hybrid_cell.correct({"checks": checks}, tol), checks
    assert all(c["generated"] == 70 for c in checks)
    assert all(len(c["state_rel_rms_by_layer"]) == 9 for c in checks)
    stated = svc.engine.cfg
    svc.engine.cfg = dataclasses.replace(stated, state_dtype=jnp.bfloat16)
    try:
        bad = svc.reference_check(samples, 128, 64)
    finally:
        svc.engine.cfg = stated
    assert serve_cell.correct({"checks": bad}, tol), bad   # the logits pass
    assert not hybrid_cell.correct({"checks": bad}, tol), bad
    exact = svc.engine.params
    for name, lower in (
            ("fp8", lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)),
            ("eighths", lambda w: (jnp.round(w * 8) / 8).astype(w.dtype))):
        svc.engine.params = jax.tree.map(
            lambda w: lower(w) if w.ndim > 2 else w, exact)
        try:
            # the reference keeps the weights as stated
            real = granite_hybrid_ref.logits_and_states
            granite_hybrid_ref.logits_and_states = \
                lambda params, *rest: real(exact, *rest)
            bad = svc.reference_check(samples, 128, 64)
        finally:
            granite_hybrid_ref.logits_and_states = real
            svc.engine.params = exact
        worst = max(c["logits_rel_rms"] for c in checks)
        assert min(c["logits_rel_rms"] for c in bad) > 2.5 * worst, (name, bad)
        if name == "eighths":
            assert not serve_cell.correct({"checks": bad}, tol), bad
        assert not hybrid_cell.correct({"checks": bad}, tol), bad
