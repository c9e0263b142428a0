"""benchmarks/trace_spans.py and benchmarks/readers/spans.py held to a
hand-made trace, to a stretch of a trace recorded on the chip
(benchmarks/testdata/recorded_spans_trace.json: one decode step of the
chat cell and the admission that followed it, TPU v5 lite, PR 24), and
to a hand-encoded .xplane.pb. Nothing here is a device number."""

import json
import os
import struct

import pytest

from benchmarks import attention_ops, manifest as manifest_mod, trace_spans
from benchmarks.readers import spans

ROOT = manifest_mod.ROOT
BODY = "jit(step)/decode/while/body/closed_call/"


def _hand_made() -> dict:
    """Two steps' worth of operations with two idle gaps, [300, 400) and
    [500, 600), and engine spans on two threads around them."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(x)", 0, 300,
             {"path": "jit(step)/decode/while:"}],
            ["%fusion.2 = bf16[8,128]{1,0} fusion(y)", 10, 100,
             {"path": BODY + "attn/mul:"}],
            ["%fusion.3 = bf16[8,4096,8,128]{3,2,1,0} fusion(z)", 150, 50,
             {"path": BODY + "kv_update/vmap(vmap())/scatter:"}],
            ["%copy.4 = bf16[24,8,4096,8,128]{4,3,2,1,0} copy(c)", 400, 100,
             {"path": ""}],
            ["%fusion.5 = bf16[1,256,2048]{2,1,0} fusion(w)", 600, 100,
             {"path": "jit(step)/prefill/while/body/closed_call/mlp/"
                      "jit(silu)/logistic:"}]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "asyncio_0", "events": [
                ["rayt.engine.decode_dispatch", 0, 20,
                 {"active": 2, "t_host": 50.0}],
                ["rayt.engine.token_sync", 30, 300, {"active": 2}],
                ["rayt.engine.emit", 335, 10,
                 {"active": 2, "finished": 0}]]},
            {"name": "asyncio_1", "events": [
                ["rayt.engine.admit", 350, 210,
                 {"request_id": "r1", "prompt_len": 180, "bucket": 512,
                  "slot": 1}],
                ["rayt.engine.prefill_chunk", 360, 20,
                 {"request_id": "r1", "pos": 256, "chunk": 256,
                  "last": 1}],
                ["rayt.engine.finish_prefill", 500, 50,
                 {"request_id": "r1", "slot": 1}]]}]}]}


def _recorded() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "recorded_spans_trace.json")) as f:
        return json.load(f)


def _train() -> dict:
    """One device of a LoRA step: first forward, recomputed forward,
    backward, the three kernels, the optimizer; an idle gap under the
    next step's h2d phase and one under no span."""
    loss = "jit(one_step)/loss/"
    back = loss + "transpose(jvp())/while/body/closed_call/checkpoint/"
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%fusion.1 = bf16[4,2048,8192]{2,1,0} fusion(a)", 0, 100,
             {"path": loss + "jvp()/while/body/closed_call/mlp/mul:"}],
            ["%dot_product_attention.2 = bf16[4,16,2048,128]{3,2,1,0} "
             "custom-call(q)", 100, 50,
             {"path": loss + "jvp()/while/body/closed_call/attn/"
                      "jit(dot_product_attention)/flash_fwd/pallas_call:"}],
            ["%fusion.3 = bf16[4,2048,8192]{2,1,0} fusion(b)", 150, 100,
             {"path": back + "rematted_computation/mlp/mul:"}],
            ["%dot_product_attention.4 = bf16[4,16,2048,128]{3,2,1,0} "
             "custom-call(q)", 250, 50,
             {"path": back + "rematted_computation/attn/"
                      "jit(dot_product_attention)/flash_fwd/pallas_call:"}],
            ["%custom-call.5 = bf16[4,16,2048,128]{3,2,1,0} custom-call(g)",
             300, 60, {"path": back + "attn/jit(dot_product_attention)/"
                               "flash_bwd_dq/pallas_call:"}],
            ["%custom-call.6 = f32[4,16,2048,128]{3,2,1,0} custom-call(g)",
             360, 90, {"path": back + "attn/jit(dot_product_attention)/"
                               "flash_bwd_dkv/pallas_call:"}],
            ["%fusion.7 = bf16[4,2048,2048]{2,1,0} fusion(c)", 450, 140,
             {"path": back + "attn_qkv/lora/dot_general:"}],
            ["%fusion.8 = f32[24,2048,16]{2,1,0} fusion(m)", 590, 10,
             {"path": "jit(one_step)/optimizer/mul:"}],
            ["%convert.9 = bf16[24,2048,8192]{2,1,0} convert(w)", 640, 60,
             {"path": ""}]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["rayt.train.step", 0, 605, {"step": 7}],
            ["rayt.train.h2d", 610, 25, {"step": 8}]]}]}]}


CASES = {
    "hand-made": dict(
        trace=_hand_made, window_ns=700, busy_ns=500,
        scope_ns={"decode": 150, "decode/attn": 100, "decode/kv_update": 50,
                  "unscoped": 100, "prefill/mlp": 100},
        phase_ns={"decode": 300, "prefill": 100}, recompute_ns=0,
        idle_ns={"rayt.engine.token_sync": 30, "rayt.engine.emit": 10,
                 "rayt.engine.between_spans": 10, "rayt.engine.admit": 40,
                 "rayt.engine.prefill_chunk": 20,
                 "rayt.engine.finish_prefill": 50, "unowned": 40},
        spans={"rayt.engine.admit": [1, 210, 140],
               "rayt.engine.token_sync": [1, 300, 300]},
        anchor={"t_host": 50.0, "trace_ns": 0},
        unscoped_first="copy.4 bf16[24,8,4096,8,128]"),
    "train": dict(
        trace=_train, window_ns=700, busy_ns=660,
        scope_ns={"loss/mlp": 200, "loss/flash_fwd": 100,
                  "loss/flash_bwd_dq": 60, "loss/flash_bwd_dkv": 90,
                  "loss/lora": 140, "optimizer": 10, "unscoped": 60},
        phase_ns={"loss": 590, "optimizer": 10}, recompute_ns=150,
        idle_ns={"rayt.train.step": 5, "rayt.train.h2d": 25, "unowned": 10},
        spans={"rayt.train.step": [1, 605, 605]},
        anchor=None, unscoped_first="convert.9 bf16[24,2048,8192]"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reduction_of_a_hand_made_trace(case):
    """(c) scope sums, gap owners, anchor, `unscoped`, `unowned`."""
    want = CASES[case]
    out = trace_spans.reduce(want["trace"]())
    assert out["window_s"] == pytest.approx(want["window_ns"] / 1e9)
    assert out["busy_s"] == pytest.approx(want["busy_ns"] / 1e9)
    for key, table in (("scope_s", "scope_ns"), ("phase_s", "phase_ns"),
                       ("idle_s", "idle_ns")):
        got = {k: v for k, v in out[key].items() if v > 1e-15}
        assert got == pytest.approx(
            {k: v / 1e9 for k, v in want[table].items()}), key
    assert out["recompute_s"] == pytest.approx(want["recompute_ns"] / 1e9)
    assert sum(out["scope_s"].values()) == pytest.approx(out["busy_s"])
    assert sum(out["idle_s"].values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    for name, (count, total, own) in want["spans"].items():
        assert out["spans"][name] == pytest.approx(
            [count, total / 1e9, own / 1e9])
    assert out["anchor"] == want["anchor"]
    assert out["unscoped_ops"][0][0] == want["unscoped_first"]
    assert out["has_scopes"] and out["has_spans"]


def test_a_trace_without_a_device_or_without_our_names():
    assert trace_spans.reduce({"planes": []}) is None
    host_only = {"planes": [p for p in _hand_made()["planes"]
                            if p["name"].startswith("/host:")]}
    assert trace_spans.reduce(host_only) is None
    # the parent's program: operations with paths but no scope of ours,
    # runtime events but no rayt.* span
    bare = _hand_made()
    for ev in bare["planes"][0]["lines"][0]["events"]:
        ev[3]["path"] = "jit(step)/while/body/closed_call/mul:"
    bare["planes"][1]["lines"] = []
    out = trace_spans.reduce(bare)
    assert not out["has_scopes"] and not out["has_spans"]
    assert out["scope_s"] == pytest.approx({"unscoped": 500e-9})
    assert out["idle_s"] == pytest.approx({"unowned": 200e-9})
    assert out["anchor"] is None


def test_hand_off_is_only_between_engine_spans_close_together():
    trace = _hand_made()
    # push the admission 10 ms away: what lies between is nobody's
    late = trace_spans.HANDOFF_MAX_NS + 1e6
    for ev in trace["planes"][1]["lines"][1]["events"]:
        ev[1] += late
    trace["planes"][0]["lines"][0]["events"][3:] = [
        ["%copy.4 = bf16[8]{0} copy(c)", 400 + late, 100, {"path": ""}]]
    out = trace_spans.reduce(trace)
    assert out["idle_s"]["rayt.engine.between_spans"] == pytest.approx(5e-9)
    assert out["idle_s"]["unowned"] == pytest.approx((5 + late) / 1e9)


@pytest.mark.parametrize("path,want", [
    (BODY + "attn_qkv/lora/dot_general:", ("decode", "lora", False)),
    ("jit(step)/prefill/lm_head/dot_general:", ("prefill", "lm_head", False)),
    ("jit(step)/decode/while/body/dynamic_update_slice:",
     ("decode", None, False)),
    ("jit(one_step)/loss/transpose(jvp(ce))/while/body/closed_call/"
     "checkpoint/rematted_computation/jit(_where)/select_n:",
     ("loss", "ce", True)),
    ("jit(one_step)/loss/jvp(embed)/jit(_take)/gather:",
     ("loss", "embed", False)),
    ("jit(make_batch)/jit(_randint)/iota_2x32_shape:", (None, None, False)),
    ("", (None, None, False)),
])
def test_scope_of_a_name_path(path, want):
    assert trace_spans.scope_of(path) == want


def test_reduction_of_the_recorded_chip_trace():
    """(c) a stretch of a chip trace: sums are held to brute force, the
    names to what that run was seen to hold."""
    trace = _recorded()
    out = trace_spans.reduce(trace)
    dev = [ev for p in trace["planes"] if p["name"] == "/device:TPU:0"
           for ln in p["lines"] for ev in ln["events"]]
    assert len(dev) > 4000
    t0 = min(ev[1] for ev in dev)
    t1 = max(ev[1] + ev[2] for ev in dev)
    assert out["window_s"] == pytest.approx((t1 - t0) / 1e9)
    # busy time by a sweep over sorted edges, not by the merged union
    edges = sorted([(ev[1], 1) for ev in dev if ev[2] > 0]
                   + [(ev[1] + ev[2], -1) for ev in dev if ev[2] > 0])
    busy = depth = 0
    for (t, d), (t_next, _) in zip(edges, edges[1:]):
        depth += d
        if depth > 0:
            busy += t_next - t
    assert out["busy_s"] == pytest.approx(busy / 1e9, rel=1e-6)
    # the recorded times are rounded to 0.1 ns, which lets a loop's last
    # operation end past the loop: self times are then a little over
    assert sum(out["scope_s"].values()) == pytest.approx(out["busy_s"],
                                                         rel=2e-4)
    assert sum(out["idle_s"].values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    share = {k: 100 * v / out["busy_s"] for k, v in out["scope_s"].items()}
    assert share["decode/attn"] == pytest.approx(48.5, abs=0.1)
    assert share["unscoped"] == pytest.approx(13.5, abs=0.1)
    assert {k for k in share if "/" in k} >= {
        "decode/attn_qkv", "decode/kv_update", "decode/attn",
        "decode/attn_out", "decode/mlp", "decode/lm_head", "decode/sample",
        "prefill/embed", "prefill/mlp", "prefill/attn"}
    assert [n for n, _ in out["unscoped_ops"][:2]] == [
        "copy.109 bf16[24,8,4096,8,128]", "copy.106 bf16[24,8,4096,8,128]"]
    assert out["idle_s"]["unowned"] < 1e-9
    assert out["idle_s"]["rayt.engine.finish_prefill"] == pytest.approx(
        4.977e-3, rel=1e-3)
    assert {k: v[0] for k, v in out["spans"].items()} == {
        "rayt.engine.decode_dispatch": 1, "rayt.engine.token_sync": 1,
        "rayt.engine.emit": 1, "rayt.engine.prefill_chunk": 1,
        "rayt.engine.finish_prefill": 1, "rayt.engine.admit": 1}
    assert out["anchor"] == {"t_host": 146.603811118, "trace_ns": 1000.0}
    assert trace_spans.to_trace_ns(out["anchor"], 146.613811118) == \
        pytest.approx(1000.0 + 10e6)
    chunk = out["fields"]["rayt.engine.prefill_chunk"][0]
    assert (chunk["pos"], chunk["chunk"], chunk["last"]) == (256, 256, 1)
    assert chunk["request_id"] == \
        out["fields"]["rayt.engine.admit"][0]["request_id"]


# ------------------------------------------------------------- readers
@pytest.fixture
def reduced(monkeypatch):
    """Readers given a reduction instead of a cell's trace directory."""
    def use(trace):
        red = trace_spans.reduce(trace)
        monkeypatch.setattr(spans, "reduction", lambda cell: red)
        return red
    return use


def test_idle_owners_sum_to_the_idle_share(reduced):
    red = reduced(_recorded())
    owners = [["rayt.engine.token_sync", "rayt.engine.decode_dispatch"],
              ["rayt.engine.admit", "rayt.engine.prefill_chunk",
               "rayt.engine.finish_prefill"],
              ["rayt.engine.emit", "rayt.engine.between_spans"],
              ["unowned"]]
    shares = [spans.idle_share({}, "c", o) for o in owners]
    assert sum(shares) == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    assert shares[1] == pytest.approx(
        100 * (4.5586285 + 4.9768714) / 89.016805, rel=1e-4)


def test_scope_readers_on_the_hand_made_trace(reduced):
    reduced(_hand_made())
    share = lambda *a, **kw: spans.scope_share({}, "c", *a, **kw)
    assert share(["decode/attn"]) == pytest.approx(20.0)
    assert share(["prefill/"]) == pytest.approx(20.0)
    assert share(["decode/"]) == pytest.approx(60.0)
    assert share(["unscoped"]) == pytest.approx(20.0)
    assert share(["decode/", "prefill/", "unscoped"]) == pytest.approx(100.0)
    # by shape: the scan's own cache traffic and the unscoped copy
    assert share(["decode/kv_update"], ops_like={
        "decode": r"while", "unscoped": r"bf16\[24,8,4096,8,128\]"}) == \
        pytest.approx(10.0 + 30.0 + 20.0)
    # 100 ns of prefill for the one 256-token chunk
    assert spans.prefill_ms_per_ktok({}, "c") == pytest.approx(
        100e-9 * 1e3 / 0.256)
    # a request admitted at 350 whose first token was read at 550 (on
    # the anchor's clock: t_host 50.0 is trace time 0): inside [350, 550)
    # nothing of phase decode ran; from 100 on, [100, 110) and [150, 200)
    rec = lambda a, b: {"records": {"r1": {"engine": {
        "t_admit": 50.0 + a * 1e-9, "t_first": 50.0 + b * 1e-9}}}}
    assert spans.ttft_decode_interleave_share(rec(350, 550), "c") == \
        pytest.approx(0.0)
    assert spans.ttft_decode_interleave_share(rec(100, 550), "c") == \
        pytest.approx(100 * 60 / 450)
    assert spans.ttft_decode_interleave_share({"records": {}}, "c") is None


@pytest.mark.parametrize("shape", [
    "24,8,8,128,4096", "24,8,8,4096,128",     # K and V since PR 25
    "8,8,128,4096", "8,8,4096,128",           # one layer of each
    "1,8,8,128,4096", "1,8,8,4096,128",
    "24,8,4096,8,128", "8,4096,8,128"])       # the seed's axis order
def test_kv_update_share_counts_a_whole_cache_move_by_shape(reduced, shape):
    """chat_decode_kv_update_share's own patterns, on a hand-encoded
    move of a whole cache (or a layer of it) in phase `decode` under no
    part and under no scope at all. The patterns of PR 24 named the
    seed's axis order only, and miss the others."""
    trace = _hand_made()
    ops = trace["planes"][0]["lines"][0]["events"]
    # the 100 ns of decode/attn now move a cache inside the layer loop,
    # and the unscoped copy takes the shape too
    ops[1] = [f"%copy.2 = bf16[{shape}]{{4,3,2,1,0}} copy(y)", 10, 100,
              {"path": "jit(step)/decode/while/body/dynamic_slice:"}]
    ops[3][0] = f"%copy.4 = bf16[{shape}]{{4,3,2,1,0}} copy(c)"
    reduced(trace)
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           "chat_decode_kv_update_share.json")) as f:
        args = json.load(f)["args"]
    share = lambda like: spans.scope_share({}, "c", args["scopes"],
                                           ops_like=like)
    rows = 10.0            # decode/kv_update itself: 50 of 500 ns
    assert share(None) == pytest.approx(rows)
    assert share(args["ops_like"]) == pytest.approx(rows + 20.0 + 20.0)
    old = {"decode": r"bf16\[(24,)?8,4096,8,128\]",
           "unscoped": r"bf16\[24,8,4096,8,128\]"}
    seeds_order = {"24,8,4096,8,128": 40.0, "8,4096,8,128": 20.0}
    assert share(old) == pytest.approx(rows + seeds_order.get(shape, 0.0))
    # an in-place write of new rows names the whole cache as its output
    # and moves none of it: under our part it is counted once, as rows
    ops[1] = [f"%dynamic-update-slice.2 = bf16[{shape}]{{4,3,2,1,0}} "
              "dynamic-update-slice(y)", 10, 100,
              {"path": BODY + "kv_update/dynamic_update_slice:"}]
    # ... and insert_row's, outside every scope, is no move either
    ops[3][0] = (f"%constant_dynamic-update-slice_fusion.1 = bf16[{shape}]"
                 "{4,3,2,1,0} fusion(c)")
    reduced(trace)
    assert share(args["ops_like"]) == share(None) == pytest.approx(30.0)


def test_train_readers_and_the_flash_yardstick(reduced):
    reduced(_train())
    assert spans.recompute_share({}, "c") == pytest.approx(100 * 150 / 660)
    assert spans.scope_share({}, "c", ["loss/flash_fwd", "loss/flash_bwd_dq",
                                       "loss/flash_bwd_dkv"]) == \
        pytest.approx(100 * 250 / 660)
    config = {"num_attention_heads": 16, "num_key_value_heads": 8,
              "hidden_size": 2048, "num_hidden_layers": 24}
    flops = attention_ops.causal_attention_train_flops(config, 4, 2048)
    # six products over the causal half of 2048 x 2048, per head and layer
    assert flops == 6 * 2 * (2048 * 2048 / 2) * 128 * 16 * 4 * 24
    assert attention_ops.causal_attention_train_bytes(config, 4, 2048) == \
        2 * 24 * 4 * 2048 * 128 * (6 * 16 + 6 * 8)
    obs = {"traced": {"steps": 1}, "chips": 1, "config": config,
           "job": {"batch_size": 4, "seq_len": 2048},
           "device": {"kind": "TPU v5 lite"}}
    assert spans.flash_roofline_share(obs, "c") == pytest.approx(
        100 * (flops / 197e12) / 250e-9)
    half = dict(obs, chips=4)
    assert spans.flash_roofline_share(half, "c") == pytest.approx(
        spans.flash_roofline_share(obs, "c") / 4)


def test_readers_leave_out_what_the_program_does_not_write(reduced):
    """On the parent commit (no scope, no span) and on the CPU (no
    device plane) every reader returns None and raises nothing."""
    bare = _hand_made()
    for ev in bare["planes"][0]["lines"][0]["events"]:
        ev[3]["path"] = "jit(step)/while/body/closed_call/mul:"
    bare["planes"][1]["lines"] = []
    obs = {"records": {"r": {"engine": {"queue_s": 0.1}}}, "traced": {
        "steps": 3}}
    for trace in (bare, {"planes": []}):
        reduced(trace)
        assert spans.idle_share(obs, "c", ["unowned"]) is None
        assert spans.scope_share(obs, "c", ["unscoped"]) is None
        assert spans.recompute_share(obs, "c") is None
        assert spans.prefill_ms_per_ktok(obs, "c") is None
        assert spans.flash_roofline_share(obs, "c") is None
        assert spans.ttft_decode_interleave_share(obs, "c") is None


def test_every_new_metric_names_its_cell_and_a_reader_that_takes_it():
    manifest = manifest_mod.load()
    seen = 0
    for cell_name in (w["name"] for w in manifest["workloads"]):
        cell = manifest_mod.resolve(manifest, cell_name)
        for m in cell.per_layer:
            if not m["file"]["reader"].startswith("spans."):
                continue
            seen += 1
            assert m["file"]["args"]["cell"] == cell_name, m["name"]
            assert m["workloads"] == [cell_name], m["name"]
            assert callable(getattr(spans, m["file"]["reader"][6:]))
            assert m["file"]["what"]
    assert seen == 25
    # no trace under the cell's work directory: nothing to reduce
    assert spans.reduction("no-such-cell") is None


# ------------------------------------------------------ the wire reader
def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, message: bytes) -> bytes:
    return _field(1, key) + _field(2, message)


def test_xplane_file_is_read_without_generated_code(tmp_path):
    """A device plane whose operation's name path sits in its metadata
    record's `tf_op` stat (by reference), and a host plane with a span
    whose fields are the event's own stats."""
    stat_names = {1: "tf_op", 2: "request_id", 3: "t_host", 4: "slot",
                  5: BODY + "attn/mul:", 6: "hlo_category"}
    stat_meta = b"".join(_field(5, _entry(k, _field(1, k) + _field(2, v)))
                         for k, v in stat_names.items())
    op = "%fusion.2 = bf16[8,128]{1,0} fusion(y), kind=kLoop"
    device = (_field(2, "/device:TPU:0") + stat_meta
              + _field(4, _entry(7, _field(1, 7) + _field(2, op) + _field(
                  5, _field(1, 6) + _field(5, "loop fusion")) + _field(
                  5, _field(1, 1) + _field(7, 5))))
              + _field(3, _field(2, "XLA Modules") + _field(3, 1000)
                       + _field(4, _field(1, 7) + _field(2, 0)
                                + _field(3, 9_000_000)))
              + _field(3, _field(2, "XLA Ops") + _field(3, 1000)
                       + _field(4, _field(1, 7) + _field(2, 2_000_000)
                                + _field(3, 5_000_000))))
    host = (_field(2, "/host:CPU") + stat_meta
            + _field(4, _entry(1, _field(1, 1) + _field(
                2, "rayt.engine.finish_prefill")))
            + _field(4, _entry(2, _field(1, 2) + _field(2, "np.asarray")))
            + _field(3, _field(2, "asyncio_0") + _field(3, 500)
                     + _field(4, _field(1, 2) + _field(2, 0) + _field(3, 10))
                     + _field(4, _field(1, 1) + _field(2, 3_000_000)
                              + _field(3, 1_000_000)
                              + _field(4, _field(1, 2) + _field(5, "abc"))
                              + _field(4, _field(1, 3) + _field(2, 20964.5))
                              + _field(4, _field(1, 4) + _field(
                                  4, (1 << 64) - 1)))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host)
                     + _field(1, _field(2, "Task Environment")))
    assert trace_spans.events_from_xplane(str(path)) == {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            [op, 3000.0, 5000.0, {"path": BODY + "attn/mul:"}]]}]},
        {"name": "/host:CPU", "lines": [{"name": "asyncio_0", "events": [
            ["rayt.engine.finish_prefill", 3500.0, 1000.0,
             {"request_id": "abc", "t_host": 20964.5, "slot": -1}]]}]}]}
    assert trace_spans.metadata_stats(str(path)) == {"/device:TPU:0": [
        [("hlo_category", "loop fusion"), ("tf_op", BODY + "attn/mul:")]]}
    assert trace_spans.newest_xplane(str(tmp_path)) == str(path)
    assert trace_spans.reduce_dir(str(tmp_path / "none")) is None
