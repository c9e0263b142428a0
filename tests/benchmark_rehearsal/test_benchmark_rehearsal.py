"""CPU rehearsal of the benchmark harness (BENCHMARK.json, benchmarks/)
at tiny size. Nothing here is a device number: these tests hold the
manifest, the generators, the trace reduction and the plain references
to what the chip runs rely on.
"""

import importlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import manifest as manifest_mod
from benchmarks import peaks, rehearsal, serve_cell, trace_reduce, train_cell
from benchmarks import traffic as traffic_mod
from benchmarks.client import Stream
from benchmarks.traffic import Request

ROOT = manifest_mod.ROOT
MANIFEST = manifest_mod.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
SERVE_TRAFFIC = sorted({w["traffic"] for w in MANIFEST["workloads"]
                        if manifest_mod.resolve(MANIFEST, w["name"])
                        .traffic["driver"] == "serve_cell"})
TRAIN_CELLS = [n for n in CELLS if manifest_mod.resolve(MANIFEST, n)
               .traffic["driver"] == "train_cell"]
# what the contract calls a width: `reduced` may never name one
WIDTH_RE = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                      r"_dim$|_rank$|head_size|expansion|experts_per_tok")


def test_manifest_is_well_formed():
    assert manifest_mod.problems(MANIFEST) == []
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(manifest_mod.DEFAULT_MANIFEST) < 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51


@pytest.fixture(scope="module")
def rehearsal_manifest(tmp_path_factory):
    """BENCHMARK.json with every configuration and traffic mix shrunk
    by the override file under its own name: nothing here names one."""
    return manifest_mod.load(rehearsal.derive(
        str(tmp_path_factory.mktemp("rehearsal"))))


def test_rehearsal_manifest_is_derived_for_every_cell(rehearsal_manifest):
    assert manifest_mod.problems(rehearsal_manifest) == []
    for key in ("workloads", "end_to_end", "per_layer", "run_seconds"):
        assert rehearsal_manifest[key] == MANIFEST[key]
    for name in CELLS:
        full = manifest_mod.resolve(MANIFEST, name)
        twin = manifest_mod.resolve(rehearsal_manifest, name)
        assert twin.config["hidden_size"] < full.config["hidden_size"]
        assert twin.config["source"] == full.config["source"]
        assert twin.traffic["driver"] == full.traffic["driver"]
        assert twin.traffic != full.traffic
        assert [m["name"] for m in twin.per_layer] == \
            [m["name"] for m in full.per_layer]
    assert rehearsal.overlay({"a": {"b": 1, "c": [1, 2]}, "d": 3},
                             {"a": {"c": [9]}, "e": 4}) == \
        {"a": {"b": 1, "c": [9]}, "d": 3, "e": 4}


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_its_files_by_name(cell_name):
    cell = manifest_mod.resolve(MANIFEST, cell_name)
    driver = importlib.import_module("benchmarks." + cell.traffic["driver"])
    for fn in ("run", "attempted_failed", "correct", "info"):
        assert callable(getattr(driver, fn))
    for m in cell.end_to_end:
        mod = importlib.import_module("benchmarks.end_to_end." + m["name"])
        assert callable(mod.read)
    for m in cell.per_layer:
        spec = m["file"]
        module, fn = spec["reader"].rsplit(".", 1)
        reader = getattr(importlib.import_module(
            "benchmarks.readers." + module), fn)
        assert callable(reader)
        for key in ("unit", "layer", "moves", "source"):
            assert spec[key] == m[key], (m["name"], key)
    assert set(cell.config["tolerances"]) >= {"why"}


@pytest.mark.parametrize("config_name", CONFIGS)
def test_configuration_keeps_every_published_width(config_name):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config_name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert not [k for k in entry["reduced"] if WIDTH_RE.search(k)]
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size", "vocab_size",
                "rope_theta", "rms_norm_eps", "max_position_embeddings"):
        assert key in cfg
    assert cfg["assumed"] and cfg["deployment"]


@pytest.mark.parametrize("traffic_name", SERVE_TRAFFIC)
def test_generator_offers_the_same_work_for_every_seed(traffic_name):
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           traffic_name + ".json")) as f:
        spec = json.load(f)
    buckets = spec["engine"]["prompt_buckets"]
    seeds = (1, 3_000_000_019)   # the driver's seeds pass 2**31

    def requests(seed):
        if spec["kind"] == "serve_open":
            return traffic_mod.open_loop(spec, 1000, seed, 30.0)
        it = traffic_mod.closed_loop(spec, 1000, seed)
        return [next(it) for _ in range(3 * spec["cycle"])]

    a, b = requests(seeds[0]), requests(seeds[1])
    open_loop = spec["kind"] == "serve_open"
    # an open loop's lead-in is the arc of the ring that precedes the
    # seed's own starting point: other requests for another seed
    for stratum in {r.stratum for r in a} - ({"lead"} if open_loop
                                             else set()):
        sa = [r for r in a if r.stratum == stratum]
        sb = [r for r in b if r.stratum == stratum]
        assert traffic_mod.shape_summary(sa, buckets) == \
            traffic_mod.shape_summary(sb, buckets)
        assert sorted(len(r.tokens) for r in sa) == \
            sorted(len(r.tokens) for r in sb)
        assert sorted(r.max_new_tokens for r in sa) == \
            sorted(r.max_new_tokens for r in sb)
    assert [len(r.tokens) for r in a] != [len(r.tokens) for r in b]
    assert a[0].tokens != b[0].tokens
    # the same seed gives the same inputs
    again = requests(seeds[0])
    assert [(r.tokens, r.max_new_tokens, r.due_s) for r in a] == \
        [(r.tokens, r.max_new_tokens, r.due_s) for r in again]
    for r in a:
        assert len(r.tokens) <= max(buckets)
        assert max(buckets) + r.max_new_tokens < \
            spec["engine"]["max_seq_len"]
    if open_loop:
        n_win = sum(1 for r in a if r.stratum == "window")
        assert n_win == round(spec["rate_per_s"] * 30.0)
        assert all(spec["lead_s"] <= r.due_s <= spec["lead_s"] + 30.0
                   for r in a if r.stratum == "window")
        assert a == sorted(a, key=lambda r: r.due_s)
        assert [r.index for r in a] == list(range(len(a)))
        assert set(traffic_mod.shape_summary(
            [r for r in a if r.stratum == "window"],
            buckets)["per_bucket"]) == set(buckets)
        windows_are_one_ring(spec, a, b)
    else:
        cyc = spec["cycle"]
        assert traffic_mod.shape_summary(a[:cyc], buckets) == \
            traffic_mod.shape_summary(a[cyc:2 * cyc], buckets)


def windows_are_one_ring(spec, a, b):
    """Two seeds' windows are one turn of the same ring: the same
    requests at the same distances, started at another point; each
    lead-in repeats the end of its own window one turn earlier."""
    lead, turn = spec["lead_s"], 30.0

    def on_ring(reqs, stratum):
        return sorted(((r.due_s - lead) % turn, len(r.tokens),
                       r.max_new_tokens) for r in reqs
                      if r.stratum == stratum and r.due_s > 0)

    wa, wb = on_ring(a, "window"), on_ring(b, "window")
    shapes = lambda win: [x[1:] for x in win]
    k = next(k for k in range(len(wa))      # b's window is a's, turned
             if shapes(wb) == shapes(wa[k:] + wa[:k]))
    turned = wa[k:] + wa[:k]
    shift = (wb[0][0] - turned[0][0]) % turn
    assert 0.01 < shift < turn - 0.01       # and started elsewhere
    for x, y in zip(wb, turned):
        off = (x[0] - y[0] - shift) % turn
        assert min(off, turn - off) < 1e-6
    for reqs, win in ((a, wa), (b, wb)):
        tail = [x for x in win if x[0] >= turn - lead]
        assert [(round(t, 6), p, o) for t, p, o in on_ring(reqs, "lead")] \
            == [(round(t, 6), p, o) for t, p, o in tail]


def test_quantile_lengths_stay_inside_their_limits():
    spec = {"dist": "lognormal", "median": 200, "sigma": 0.9,
            "min": 16, "max": 1000}
    vals = traffic_mod.quantile_lengths(spec, 50)
    assert vals == sorted(vals) and vals[0] >= 16 and vals[-1] <= 1000
    assert 150 <= sorted(vals)[25] <= 260
    with pytest.raises(ValueError):
        traffic_mod.quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 3)


# ------------------------------------------------------- trace reduction
def _brute_busy(events, t0, t1, step=1.0):
    """Busy time by sampling every `step` ns: shares no code with the
    reduction's interval arithmetic."""
    grid = np.arange(t0, t1, step)
    busy = np.zeros(len(grid), bool)
    for _, s, d in events:
        busy |= (grid >= s) & (grid < s + d)
    return busy.sum() * step


def test_trace_reduction_on_a_hand_made_trace():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", 0, 100], ["all-gather.2", 150, 50],
            ["fusion.3", 180, 100], ["copy.4", 400, 100]]}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["outer", 0, 1000], ["np.asarray", 90, 70],
            ["PjitFunction(step)", 270, 140]]}]}]}
    out = trace_reduce.reduce(trace)
    assert out["window_s"] == pytest.approx(500e-9)
    assert out["busy_s"] == pytest.approx(330e-9)
    assert out["collective_s"] == pytest.approx(50e-9)
    assert out["collective_exposed_s"] == pytest.approx(30e-9)
    assert dict(map(tuple, out["idle_gaps"])) == pytest.approx(
        {"PjitFunction(step)": 120e-9, "np.asarray": 50e-9})
    assert out["device_ops"][0][1] == pytest.approx(100e-9)
    assert trace_reduce.reduce({"planes": []})["busy_s"] == 0.0
    # a `while` that spans its body: self time, and exposure by leaves
    nested = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(x)", 0, 300],
            ["%fusion.2 = bf16[8,128]{1,0} fusion(y)", 10, 100],
            ["%all-reduce.3 = bf16[8,128]{1,0} all-reduce(z)", 150, 50]]}]}]}
    out = trace_reduce.reduce(nested)
    assert out["busy_s"] == pytest.approx(300e-9)
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"while.1 s32[]": 150e-9, "fusion.2 bf16[8,128]": 100e-9,
         "all-reduce.3 bf16[8,128]": 50e-9})
    assert out["collective_exposed_s"] == pytest.approx(50e-9)


def test_trace_reduction_reproduces_the_recorded_chip_trace():
    """testdata/recorded_trace.json: the first events of every line of a
    trace taken on the chip (TPU v5 lite), in the reduction's plain
    form. Busy and idle time must match a brute-force count."""
    path = os.path.join(ROOT, "benchmarks", "testdata",
                        "recorded_trace.json")
    with open(path) as f:
        trace = json.load(f)
    out = trace_reduce.reduce(trace)
    dev = [p for p in trace["planes"]
           if trace_reduce.DEVICE_PLANE.match(p["name"])]
    assert dev, "the recorded trace has no device plane"
    evs = [e for ln in dev[0]["lines"] for e in ln["events"] if e[2] > 0]
    t0 = min(e[1] for p in dev for ln in p["lines"]
             for e in ln["events"] if e[2] > 0)
    t1 = max(e[1] + e[2] for p in dev for ln in p["lines"]
             for e in ln["events"] if e[2] > 0)
    step = (t1 - t0) / 200_000
    brute = _brute_busy(evs, t0, t1, step) / 1e9
    first = out["busy_s_by_device"][dev[0]["name"]]
    assert out["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert first == pytest.approx(brute, rel=2e-3)
    assert 0 < first <= out["window_s"]
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle <= out["window_s"] - first + 1e-9
    assert out["device_ops"] and out["device_ops"][0][1] > 0


# ------------------------------------------------- metrics from stamps
def _stream(due, first, gaps, prompt_len, error=None):
    s = Stream(Request(0, 0.0, [1] * prompt_len, len(gaps) + 1, "window"),
               due)
    s.sent = due + 0.001
    t = [first]
    for g in gaps:
        t.append(t[-1] + g)
    if first is not None:
        s.t = t
        s.tokens = [7] * len(t)
    s.error = error
    s.done = (s.t[-1] if s.t else due + 1)
    return s


def test_window_metrics_are_taken_over_every_stamp_in_the_window():
    streams = [_stream(10.0, 10.5, [0.1] * 9, 100),
               _stream(11.0, 11.2, [0.2] * 5, 200),
               _stream(12.0, None, [], 50, error="boom"),
               _stream(5.0, 5.15, [0.1] * 60, 300)]  # from the lead-in
    obs = {"window": (10.0, 20.0), "streams": streams,
           "traffic": {"kind": "serve_open", "ttft_limit_s": 0.4}}
    gaps = serve_cell.window_gaps(obs)
    # every token but a stream's first, the lead-in stream's after 10.0 too
    assert len(gaps) == sum(1 for s in streams for x in s.t[1:]
                            if 10.0 <= x <= 20.0) >= 9 + 5 + 10
    assert serve_cell.itl_p90_ms(obs) == pytest.approx(
        np.percentile(gaps, 90) * 1e3)
    # due in window: three; the erred one and the one 0.5 s late fail
    assert serve_cell.attempted_failed(obs) == (3, 2)
    # all the window's work over all of the window: every generated
    # token that arrived inside it, and the prompt tokens answered, a
    # counter read at first tokens (5.15: 300, 10.5: 100, 10.999: 1000,
    # 11.2: 200, 20.999: 1000) and interpolated at the window's edges
    with pytest.raises(RuntimeError):     # no reading after the window
        serve_cell.serve_tokens_per_s(obs)
    # sent 9.001, first token 10.999, a second one 0.1 s later
    early = _stream(9.0, 10.999, [0.1], 1000)
    late = _stream(19.0, 20.999, [0.1], 1000)
    obs["streams"] = streams + [early, late]
    arrived = sum(1 for s in obs["streams"] for x in s.t
                  if 10.0 <= x <= 20.0)
    at_start = 300 + 100 * (10.0 - 5.15) / (10.5 - 5.15)
    at_end = 1600 + 1000 * (20.0 - 11.2) / (20.999 - 11.2)
    assert serve_cell.serve_tokens_per_s(obs) == pytest.approx(
        (at_end - at_start + arrived) / 10.0)
    from benchmarks.readers import client as client_readers

    assert client_readers.tokens_per_s_at_first_token(obs) == \
        pytest.approx((100 + 1000 + 200 + arrived) / 10.0)


def test_ttft_percentile_is_from_when_due_and_counts_the_missing():
    from benchmarks.readers import client as client_readers

    # due at 10.0 .. 18.0, first token 0.10 .. 0.90 s after being due;
    # each was sent 1 ms late, which the time from when due contains
    streams = [_stream(10.0 + i, 10.0 + i + 0.1 * (i + 1), [0.05] * 3, 20)
               for i in range(9)]
    obs = {"window": (10.0, 20.0), "streams": streams,
           "traffic": {"kind": "serve_open", "ttft_limit_s": 2.0}}
    ttft = [0.1 * (i + 1) for i in range(9)]
    for q in (50, 75, 90):
        assert client_readers.ttft_percentile_ms(obs, q) == pytest.approx(
            np.percentile(ttft, q) * 1e3)
    assert client_readers.ttft_percentile_ms(obs, 50) == pytest.approx(
        client_readers.ttft_p50_ms(obs))
    assert streams[0].sent > streams[0].due
    # outside the window's dues: not this window's requests
    obs["streams"] = streams + [_stream(5.0, 9.0, [0.1] * 20, 20),
                                _stream(20.5, 20.6, [0.1], 20)]
    assert client_readers.ttft_percentile_ms(obs, 75) == pytest.approx(
        np.percentile(ttft, 75) * 1e3)
    # one that erred and one past the limit count as slower than all
    # the others: eleven requests, the two slowest missing
    missing = [_stream(15.5, None, [], 20, error="boom"),
               _stream(16.5, 19.0, [0.1], 20)]
    obs["streams"] = streams + missing
    assert serve_cell.attempted_failed(obs) == (11, 2)
    assert serve_cell.judged_ttft_s(obs).count(float("inf")) == 2
    assert client_readers.ttft_percentile_ms(obs, 50) == pytest.approx(
        np.percentile(ttft + [9.0, 9.0], 50) * 1e3)
    assert client_readers.ttft_percentile_ms(obs, 80) == pytest.approx(900.0)
    # a percentile that falls among the missing is no number
    assert client_readers.ttft_percentile_ms(obs, 90) is None
    # no first token yet, still inside its limit when the run stopped
    # (the last stamp of any stream, 19.1): not judged either way
    obs["streams"] = streams + [_stream(19.0, None, [], 20)]
    assert serve_cell.attempted_failed(obs) == (9, 0)
    assert client_readers.ttft_percentile_ms(obs, 50) == pytest.approx(500.0)
    obs["streams"] = []
    assert client_readers.ttft_percentile_ms(obs, 50) is None


def test_ttft_metrics_of_the_manifest_are_held_to_their_reader():
    """Every `chat_ttft_p<nn>_ms` of BENCHMARK.json takes the nn-th
    percentile, through the reader its metric file names."""
    from benchmarks.readers import client as client_readers

    ttft = [0.01 * (i + 1) for i in range(40)]
    obs = {"window": (10.0, 20.0), "streams": [
        _stream(10.0 + 0.1 * i, 10.0 + 0.1 * i + x, [0.05], 20)
        for i, x in enumerate(ttft)],
        "traffic": {"kind": "serve_open", "ttft_limit_s": 2.0}}
    cell = manifest_mod.resolve(MANIFEST, "internlm2-1.8b.chat")
    seen = set()
    for m in cell.per_layer:
        found = re.fullmatch(r"chat_ttft_p(\d+)_ms", m["name"])
        if not found:
            continue
        q = int(found.group(1))
        assert (m["unit"], m["better"], m["source"]) == \
            ("ms", "lower", "host_clock")
        assert m["workloads"] == [cell.name]
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
        reader = getattr(client_readers,
                         m["file"]["reader"].split(".", 1)[1])
        assert reader(obs, **m["file"]["args"]) == pytest.approx(
            np.percentile(ttft, q) * 1e3), m["name"]
        seen.add(q)
    assert seen == {50, 75}


@pytest.mark.parametrize("cell_name", TRAIN_CELLS)
def test_train_job_is_whole_micro_batches_and_leaves_steps_to_measure(
        cell_name, rehearsal_manifest):
    """A step of `batch_size` rows in `grad_accum` micro-batches (the
    recipe's own knob, 1 where the mix does not name it), at full size
    and on the twin; a traced run keeps two whole steps ahead of the
    profiler, which `train_tokens_per_s` needs; a window of run_seconds
    holds as many at the step time the mix's file states."""
    for manifest in (MANIFEST, rehearsal_manifest):
        tr = manifest_mod.resolve(manifest, cell_name).traffic
        job = tr["job"]
        accum = job.get("grad_accum", 1)
        assert accum >= 1 and job["batch_size"] % accum == 0
        assert tr["warmup_steps"] >= 1 and tr["trace_steps"] >= 1
        assert tr["trace_after_steps"] >= 2
    obs = {"window_stamps": [0.0, 5.25, 10.5],
           "job": manifest_mod.resolve(MANIFEST, cell_name).traffic["job"]}
    assert train_cell.train_tokens_per_s(obs) == pytest.approx(
        obs["job"]["batch_size"] * obs["job"]["seq_len"] / 5.25)


def test_train_rate_counts_whole_steps_between_stamps():
    obs = {"window_stamps": [100.0, 100.5, 101.0, 101.5],
           "job": {"batch_size": 4, "seq_len": 2048}, "warmup_steps": 3,
           "steps": [{"step": i, "loss": 1.0, "wall_s": 0.5,
                      "stages": {"step_s": 0.45}} for i in range(1, 9)]}
    assert train_cell.train_tokens_per_s(obs) == pytest.approx(
        4 * 2048 * 3 / 1.5)
    assert [s["step"] for s in train_cell.window_steps(obs)] == [4, 5, 6]
    assert train_cell.attempted_failed(obs) == (3, 0)
    from benchmarks.readers import train as train_readers

    assert train_readers.host_share(obs) == pytest.approx(10.0)


def test_peaks_and_required_operations():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "internlm2-1.8b.json")) as f:
        cfg = json.load(f)
    # 24 x (2 x 2048^2 + 2 x 2048 x 1024 + 3 x 2048 x 8192) + 2048 x 92544
    assert peaks.matmul_params(cfg) == 1_699_479_552
    per_token = peaks.lora_train_flops_per_token(
        cfg, 2048, 16, ("wq", "wk", "wv", "wo"))
    assert 7.3e9 < per_token < 7.6e9


def test_llama_serve_operations_a_token_against_hand_counts():
    """`serve_mfu`'s yardstick in the long-prompt cell: one cycle of the
    closed loop, the attention in expectation over the pairing."""
    import itertools

    from benchmarks import attention_ops

    cell = manifest_mod.resolve(MANIFEST, "internlm2-1.8b.longprompt")
    prompts, outputs = traffic_mod.cycle_lengths(cell.traffic)
    assert prompts == [1625 + 250 * i for i in range(8)]
    assert outputs == [19 + 6 * i for i in range(8)]
    c = attention_ops.cycle_sums(cell.traffic)
    assert (c["tokens"], c["passed"], c["outputs"]) == (20320, 20312, 320)
    # the mean over a sample of pairings is the mean over all pairs
    by_pair = {(p, o): (p + o - 1) * (p + o) // 2
               for p in prompts for o in outputs}
    assert c["pairs"] == sum(by_pair.values()) / 8
    turns = [outputs[k:] + outputs[:k] for k in range(8)]     # 8 matchings
    assert sum(sum(by_pair[p, o] for p, o in zip(prompts, t))
               for t in turns) / 8 == c["pairs"]
    assert attention_ops.causal_pairs(3) == 6
    # 24 x 62.9 M block weights a position, the head of 189.5 M for each
    # output token, 4 x 128 x 16 heads x 24 layers a pair
    block, head = 24 * 62_914_560, 2048 * 92544
    assert attention_ops.serve_flops_per_token(
        cell.config, cell.traffic) == pytest.approx(
        (2 * block * 20312 + 2 * head * 320 + 196_608 * c["pairs"]) / 20320)
    assert 3.2e9 < attention_ops.serve_flops_per_token(
        cell.config, cell.traffic) < 3.4e9
    # a seed's cycle holds these lengths, in another pairing
    reqs = list(itertools.islice(
        traffic_mod.closed_loop(cell.traffic, 1000, 3_000_000_019), 8))
    assert sorted(len(r.tokens) for r in reqs) == prompts
    assert sorted(r.max_new_tokens for r in reqs) == outputs


# ------------------------------------------- reference against the program
def test_reference_imports_nothing_from_the_program():
    ref_dir = os.path.join(ROOT, "benchmarks", "reference")
    for name in os.listdir(ref_dir):
        if name.endswith(".py"):
            with open(os.path.join(ref_dir, name)) as f:
                src = f.read()
            assert not re.search(r"^\s*(import|from)\s+ray_tpu", src, re.M)


@pytest.mark.parametrize("config_name", CONFIGS)
def test_reference_and_program_agree_on_the_debug_twin(
        config_name, rehearsal_manifest, monkeypatch):
    """Forward logits (what serving is held to) and LoRA loss and
    adapter gradients (what training is held to) on the configuration's
    twin, at the twin's tolerances; and a lower precision than the file
    states fails both comparisons."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, lora
    from ray_tpu.parallel.mesh import build_mesh

    from benchmarks import model
    from benchmarks.reference import llama_ref

    entry = next(c for c in rehearsal_manifest["configs"]
                 if c["name"] == config_name)
    with open(entry["file"]) as f:
        twin = json.load(f)
    tol = twin["tolerances"]
    model.register_preset(twin, "train")
    try:
        cfg = llama.config_for(twin["name"], max_seq_len=128)
        mesh = build_mesh({"data": 1}, jax.devices()[:1])
        params = model.jitted_init(cfg, 3_000_000_019)
        ref_params = llama.init_params(cfg, jax.random.PRNGKey(0))
        assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
            jax.tree.map(lambda a: (a.shape, a.dtype), ref_params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 96), 0,
                                  cfg.vocab_size)
        prog = np.asarray(llama.forward(params, toks, cfg)[0])
        rows = jnp.arange(96)
        ref = np.asarray(llama_ref.logits_at(
            params, toks, model.reference_hp(twin), rows))
        rel = np.sqrt(((prog - ref) ** 2).mean() / (ref ** 2).mean())
        assert rel <= tol.get("logits_rel_rms", 0.05), rel
        lcfg = lora.LoraConfig(rank=4, alpha=cfg.lora_alpha)
        check = lambda base: train_cell._reference_check(
            cfg, lcfg, base, mesh, twin,
            {"check": {"batch": 1, "seq_len": 128}}, 3_000_000_019)
        chk = check(params)
        assert chk["finite"] and chk["loss_rel"] <= tol["loss_rel"], chk
        assert train_cell.correct({"check": chk}, tol), chk
        # a lower precision than the file states must fail. Serve: the
        # program's weights rounded to multiples of 1/8
        coarse = jax.tree.map(
            lambda a: (jnp.round(a * 8) / 8).astype(a.dtype)
            if a.ndim > 1 else a, params)
        bad = np.asarray(llama.forward(coarse, toks, cfg)[0])
        assert np.sqrt(((bad - ref) ** 2).mean() / (ref ** 2).mean()) > \
            tol.get("logits_rel_rms", 0.05)
        # Train: the program's base rounded to fp8, the reference's not
        exact = llama_ref.loss_and_adapter_grads
        monkeypatch.setattr(
            llama_ref, "loss_and_adapter_grads",
            lambda base, *rest: exact(params, *rest))
        for fp8 in (jnp.float8_e4m3fn, jnp.float8_e5m2):
            rounded = jax.tree.map(
                lambda a: a.astype(fp8).astype(a.dtype)
                if a.ndim > 1 else a, params)
            bad = check(rounded)
            assert not train_cell.correct({"check": bad}, tol), bad
            assert bad["grad_rel_rms_max"] > 4 * chk["grad_rel_rms_max"]
    finally:
        llama.PRESETS.pop(twin["name"], None)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_runs_end_to_end_at_rehearsal_size(cell_name, tmp_path):
    """The whole run of each cell on its twin: cluster, warm-up, window,
    the reference after it. Computed on the CPU, so what it found goes
    to stderr and the run fails, as any run without the chip does."""
    cell = manifest_mod.resolve(MANIFEST, cell_name)
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(JAX_PLATFORMS="cpu", TPU_VISIBLE_CHIPS=",".join(
        map(str, range(cell.chips))), XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={cell.chips}"))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--manifest",
         rehearsal.derive(str(tmp_path)), "--workload", cell_name,
         "--seed", "3000000019", "--seconds", "3", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    lines = [ln for ln in proc.stderr.splitlines()
             if ln.startswith('{"correct"')]
    assert lines, proc.stderr[-2000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # each number `correct` compared, beside its limit: last on the line,
    # and again as lines of their own on standard error
    assert list(result)[-1] == "compared" and result["compared"]
    for name, (value, limit) in result["compared"].items():
        assert 0 <= value <= limit, name
        assert f"benchmarks.run: compared {name} {value!r} limit " \
            f"{limit!r}" in proc.stderr


def test_no_accelerator_means_no_result():
    env = {k: v for k, v in os.environ.items()
           if k not in ("TPU_VISIBLE_CHIPS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_RUN"] = "1"   # the driver sets it; the benchmark ignores it
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELLS[0],
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=150)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{")]
