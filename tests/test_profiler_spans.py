"""The program's own names in a JAX profiler trace: `rayt.*` host spans
of the serve engine and the train StepRecorder, named scopes in the
device programs, and the request record's stamps. All on the CPU, and
nothing here times anything: what is checked is that the names and
fields are written, and nest as the readers (benchmarks/trace_spans.py)
expect."""

import asyncio
import os
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from benchmarks import trace_spans
from ray_tpu._internal import spawn
from ray_tpu._internal.profiler import span_type
from ray_tpu.models import llama
from ray_tpu.serve import request_context
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.train import telemetry

ENGINE_SPANS = ("admit", "prefill_chunk", "finish_prefill",
                "decode_dispatch", "token_sync", "emit", "wait")


def _host_spans(trace_dir) -> list:
    """[(line, name, start, end, stats)] of every rayt.* span written."""
    path = trace_spans.newest_xplane(str(trace_dir))
    assert path, "the profiler wrote no .xplane.pb"
    return [(i, ev[0], ev[1], ev[1] + ev[2], ev[3])
            for p in trace_spans.events_from_xplane(path)["planes"]
            for i, ln in enumerate(p["lines"]) for ev in ln["events"]]


def _trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def test_helper_is_the_profilers_own_span_and_nothing_else(tmp_path,
                                                           monkeypatch):
    """(e) no span store of ours: the helper is TraceAnnotation itself,
    and opening spans with no session leaves no thread and no file."""
    assert span_type() is jax.profiler.TraceAnnotation
    # put back afterwards: a later test of this worker spawns
    # `python -c "from ray_tpu..."` from the checkout it stands in
    monkeypatch.chdir(tmp_path)
    threads = threading.active_count()
    for i in range(100):
        with span_type()("rayt.engine.emit", active=i) as span:
            span.set_metadata(finished=0)
    assert threading.active_count() == threads
    assert os.listdir(tmp_path) == []
    rec = telemetry.StepRecorder("run", "exp")
    with rec.phase("step"):
        pass
    assert os.listdir(tmp_path) == []


def _traced_engine_run(tmp_path, max_new_tokens=4):
    """A debug engine run under a profiler session: a short prompt
    (one-shot prefill inside admit) and a chunked one (prefill_chunk
    from the engine loop), a few decode steps each. -> (engine, its
    stats() before the traced run, the streams)."""
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=256,
                    prompt_buckets=(16, 64), prefill_chunk=16,
                    prefix_cache_entries=0)

    async def one(rid, tokens):
        token = request_context._set_request_obs({"request_id": rid})
        try:
            return [t async for t in eng.generate(
                tokens, max_new_tokens=max_new_tokens)]
        finally:
            request_context._reset_request_obs(token)

    async def run():
        return await asyncio.gather(one("short", [5, 9, 11]),
                                    one("long", list(range(1, 41))))

    asyncio.run(run())          # compile outside the session
    before = eng.stats()
    jax.profiler.start_trace(str(tmp_path), profiler_options=_trace_options())
    try:
        outs = asyncio.run(run())
    finally:
        jax.profiler.stop_trace()
    return eng, before, outs


def test_engine_writes_every_span_into_the_profilers_trace(tmp_path):
    """(a) every engine span, with its fields, nested as the readers
    expect."""
    _, _, outs = _traced_engine_run(tmp_path)
    assert [len(o) for o in outs] == [4, 4]

    spans = _host_spans(tmp_path)
    by_name: dict = {}
    for line, name, start, end, stats in spans:
        by_name.setdefault(name, []).append((line, start, end, stats))
    for short in ENGINE_SPANS:
        assert "rayt.engine." + short in by_name, (short, sorted(by_name))
    for short in ("admit", "prefill_chunk", "finish_prefill"):
        ids = {s[3]["request_id"] for s in by_name["rayt.engine." + short]}
        assert ids == {"short", "long"}, (short, ids)
    admit = {s[3]["request_id"]: s[3] for s in by_name["rayt.engine.admit"]}
    assert admit["short"]["prompt_len"] == 3
    assert admit["short"]["bucket"] == 16
    assert admit["long"]["prompt_len"] == 40 and admit["long"]["bucket"] == 64
    # in order of start: each chunk runs on whichever executor thread is
    # free, and the trace lists spans thread by thread
    chunks = [s[3] for s in sorted(by_name["rayt.engine.prefill_chunk"],
                                   key=lambda s: s[1])
              if s[3]["request_id"] == "long"]
    # 24 pad slots: the first 16-token chunk is skipped, three are run
    assert [(c["pos"], c["chunk"], c["last"]) for c in chunks] == \
        [(16, 16, 0), (32, 16, 0), (48, 16, 1)]
    for s in by_name["rayt.engine.decode_dispatch"]:
        assert isinstance(s[3]["t_host"], float) and s[3]["active"] >= 1
        # at least the token each live row writes in the step
        assert s[3]["live_positions"] >= s[3]["active"]
    assert {s[3]["finished"] for s in by_name["rayt.engine.emit"]} >= {0, 1}
    # token_sync is a unit of its own: inside no other engine span
    for line, start, end, _ in by_name["rayt.engine.token_sync"]:
        around = [n for ln, n, s, e, _ in spans
                  if ln == line and n != "rayt.engine.token_sync"
                  and s <= start and end <= e]
        assert around == [], around
    # a one-shot prefill and its finish are parts of the admission
    a_line, a_start, a_end, _ = next(
        s for s in by_name["rayt.engine.admit"]
        if s[3]["request_id"] == "short")
    inner = [n for ln, n, s, e, st in spans if ln == a_line
             and a_start <= s and e <= a_end
             and st.get("request_id") == "short"]
    assert set(inner) == {"rayt.engine.admit", "rayt.engine.prefill_chunk",
                          "rayt.engine.finish_prefill"}


def test_the_wait_for_work_is_a_span_between_two_requests(tmp_path):
    """Two requests 0.2 s apart, the first long done when the second
    comes: the loop's wait on its empty queue is one `rayt.engine.wait`
    between them, on the event loop's own line, that holds no other
    engine span and has ended when the second admission starts (the
    reducer files what lies between the two as the hand-off)."""
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=64,
                    prompt_buckets=(16,), prefill_chunk=0,
                    prefix_cache_entries=0)

    async def one(rid, tokens):
        token = request_context._set_request_obs({"request_id": rid})
        try:
            return [t async for t in eng.generate(tokens, max_new_tokens=3)]
        finally:
            request_context._reset_request_obs(token)

    async def run():
        first = await one("first", [5, 9, 11])
        await asyncio.sleep(0.2)
        return first, await one("second", [5, 9, 12])

    asyncio.run(run())          # compile outside the session
    waited = eng.host_time()["host_us_wait"]
    jax.profiler.start_trace(str(tmp_path), profiler_options=_trace_options())
    try:
        asyncio.run(run())
    finally:
        jax.profiler.stop_trace()
    assert eng.host_time()["host_us_wait"] - waited >= 200_000
    spans = [s for s in _host_spans(tmp_path)
             if s[1].startswith(trace_spans.ENGINE_PREFIX)]
    admit = {s[4]["request_id"]: s for s in spans
             if s[1] == "rayt.engine.admit"}
    last_of_first = max(s[3] for s in spans if s[2] < admit["second"][2])
    waits = [s for s in spans if s[1] == "rayt.engine.wait"]
    # (the loop also waits before the first request, a moment, and
    # after the second, until its event loop closes)
    (between,) = [w for w in waits
                  if admit["first"][3] <= w[2] < admit["second"][2]]
    line, _, start, end, stats = between
    assert end - start >= 0.19e9 and stats == {}
    assert last_of_first == end <= admit["second"][2]
    assert admit["second"][2] - end < trace_spans.HANDOFF_MAX_NS
    assert not [s for s in spans if s is not between
                and s[2] < end and s[3] > start]
    assert {s[0] for s in waits} == {line} and line not in {
        s[0] for s in spans if s[1] != "rayt.engine.wait"}


def test_token_sync_of_a_step_follows_the_dispatch_of_the_next(tmp_path):
    """The decode pipeline in the trace: the token_sync span that reads
    step k opens after the decode_dispatch span of step k+1 has closed,
    whenever a row was live in step k+1; `decode_overlapped` counts
    exactly those reads."""
    eng, before, outs = _traced_engine_run(tmp_path, max_new_tokens=12)
    assert [len(o) for o in outs] == [12, 12]
    by_name: dict = {}
    for _, name, start, end, stats in sorted(_host_spans(tmp_path),
                                             key=lambda s: s[2]):
        by_name.setdefault(name.removeprefix("rayt.engine."), []).append(
            (start, end, stats))
    dispatch, sync, emit = (by_name[n] for n in
                            ("decode_dispatch", "token_sync", "emit"))
    # every dispatched step is read once, in order, and then emitted
    assert len(dispatch) == len(sync) == len(emit) >= 12
    overlapped = 0
    for k, (s_start, s_end, s_stats) in enumerate(sync):
        assert dispatch[k][1] <= s_start            # its own dispatch
        assert s_end <= emit[k][0]
        assert s_stats["active"] == dispatch[k][2]["active"] \
            == emit[k][2]["active"]
        if k + 1 < len(dispatch) and dispatch[k + 1][1] <= s_start:
            overlapped += 1
        else:
            # read with no later step dispatched: no row was live in
            # one, i.e. every row of step k got its last token from it
            assert emit[k][2]["finished"] == emit[k][2]["active"]
            assert k + 1 == len(dispatch) or emit[k][1] <= dispatch[k + 1][0]
    st = eng.stats()
    steps = st["batches"] - before["batches"]
    assert steps == len(dispatch)
    assert st["decode_overlapped"] - before["decode_overlapped"] == overlapped
    # the live depths of the traced steps are what stats() summed
    assert sum(d[2]["live_positions"] for d in dispatch) == \
        st["decode_kv_positions_live"] - before["decode_kv_positions_live"]
    assert overlapped >= steps - (st["prefills"] - before["prefills"]) - 1
    assert st["decode_rows_discarded"] == 0


def test_step_recorder_phases_and_report_are_spans(tmp_path):
    rec = telemetry.StepRecorder("run", "exp")
    jax.profiler.start_trace(str(tmp_path), profiler_options=_trace_options())
    try:
        for step in range(3):
            for phase in telemetry._PHASES:
                with rec.phase(phase):
                    pass
            with span_type()("rayt.train.report", step=step + 1):
                pass
            rec.end_step()
    finally:
        jax.profiler.stop_trace()
    seen: dict = {}
    for _, name, _, _, stats in _host_spans(tmp_path):
        seen.setdefault(name, []).append(stats["step"])
    assert seen == {
        **{"rayt.train." + p: [0, 1, 2] for p in telemetry._PHASES},
        "rayt.train.report": [1, 2, 3]}


def _scope_paths(lowered) -> set:
    """Every name path of the lowered module, without its last component
    (the primitive), jax's transformation wrappers taken off."""
    text = lowered.as_text(debug_info=True)
    paths = set()
    for m in re.finditer(r'loc\("([^"]+)"', text):
        parts = [trace_spans._core(c) for c in m.group(1).split("/")[:-1]]
        paths.add("/".join(parts))
    return paths


@pytest.mark.parametrize("phase,tokens", [("decode", None),
                                          ("prefill", (1, 16))])
def test_engine_step_names_its_phase_and_parts(phase, tokens):
    """(b) lowered, not compiled: the engine's step for a decode and for
    a prefill shape carries the phase and every part of the block."""
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=64,
                    prompt_buckets=(16,), prefill_chunk=0)
    eng._ensure_decode_cache()
    if tokens is None:
        cache, toks, temps = eng._decode_cache, eng._cur, eng._temps
    else:
        cache = llama.init_kv_cache(eng.cfg, 1, max_len=16)
        toks = jnp.zeros(tokens, jnp.int32)
        temps = jnp.zeros((1, 1), jnp.float32)
    paths = _scope_paths(eng._step_jit.lower(
        eng.params, cache, toks, eng._key, temps))
    other = "prefill" if phase == "decode" else "decode"
    assert not [p for p in paths if other in p.split("/")]
    for part in ("embed", "lm_head", "sample"):
        assert any(p.endswith(f"{phase}/{part}") or f"{phase}/{part}/" in p
                   for p in paths), part
    # the block is a scan body, lowered as a function of its own: its
    # paths are relative there and whole in the compiled module
    for part in ("attn_qkv", "kv_update", "attn", "attn_out", "mlp"):
        assert any(part in p.split("/") for p in paths), part
    assert trace_spans.scope_of(
        f"jit(step)/{phase}/while/body/closed_call/attn/mul") == \
        (phase, "attn", False)


def test_mixed_program_names_both_phases_and_files_each_part_once():
    """(b) lowered: the engine's mixed program (a chunk carrying the
    decode rows, PR 57) names its phases in the model: no path lies
    under both, the products (`attn_out`, `mlp`, `lm_head`, `embed`)
    are `prefill`'s alone, and `attn_qkv` (the rope), `attn` and the
    writes are under each: `scope_of` files an operation as it
    files the two programs'."""
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=64,
                    prompt_buckets=(16, 32), prefill_chunk=8)
    eng._ensure_decode_cache()
    small = llama.init_kv_cache(eng.cfg, 1, max_len=32)
    paths = _scope_paths(eng._mixed_jit.lower(
        eng.params, small, jnp.zeros((1, 8), jnp.int32), eng._decode_cache,
        eng._cur, eng._key, jnp.zeros((1, 1), jnp.float32), eng._temps))
    filed = {}
    for p in paths:
        phase, part, _ = trace_spans.scope_of(p + "/op")
        assert {"prefill", "decode"} - set(p.split("/")), p
        filed.setdefault(part, set()).add(phase)
    for part in ("embed", "lm_head", "attn_out", "mlp"):
        assert filed[part] == {"prefill"}, (part, filed[part])
    # (here the rows' K and V are written by XLA; on a TPU by the kernel)
    for part in ("attn_qkv", "attn", "kv_update", "sample"):
        assert filed[part] == {"prefill", "decode"}, (part, filed[part])


def test_lora_train_step_names_loss_optimizer_and_kernels():
    """(b) the LoRA step with the flash kernels (interpret mode here) and
    full remat: loss, optimizer, the parts, the kernels, the adapters'
    branch; jax itself marks the recomputed forward. The backward is one
    kernel under `flash_bwd_dkv` where the shape lets it be fused (every
    shape the repo runs), and `flash_bwd_dq` then names nothing."""
    from ray_tpu.ops.pallas import flash_attention as fa
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.recipes import build_lora_step

    mesh = build_mesh({"data": 1, "fsdp": 1, "tensor": 1}, jax.devices()[:1])
    step, state, _ = build_lora_step({
        "preset": "debug", "lora_rank": 4, "model_overrides": {
            "attn_impl": "flash", "remat_policy": "nothing",
            "max_seq_len": 128}},
        mesh)
    batch = {"tokens": jnp.zeros((2, 128), jnp.int32),
             "targets": jnp.zeros((2, 128), jnp.int32)}
    paths = _scope_paths(step.lower(state, batch))
    for name in ("loss", "optimizer", "embed", "ce", "attn_qkv", "attn",
                 "attn_out", "mlp", "lora", "flash_fwd", "flash_bwd_dkv",
                 trace_spans.RECOMPUTE):
        assert any(name in p.split("/") for p in paths), name
    cfg = llama.config_for("debug", max_seq_len=128)
    fused = fa.backward_path(128, cfg.head_dim, cfg.n_heads
                             // cfg.n_kv_heads, cfg.dtype) == "fused"
    assert fused != any("flash_bwd_dq" in p.split("/") for p in paths)
    assert trace_spans.scope_of(
        "jit(one_step)/loss/transpose(jvp())/while/body/closed_call/"
        "checkpoint/rematted_computation/attn_qkv/lora/dot_general") == \
        ("loss", "lora", True)


def test_engine_section_tiles_the_request_and_keeps_the_stamps():
    """(d) queue + prefill + decode is the engine's whole share."""
    obs = {"request_id": "r", "gen_start": 10.0, "admit": 10.25,
           "first_token": 11.0, "last_token": 13.5, "tokens": 6,
           "decode_steps": 5, "occupancy_sum": 2.5, "prefill_chunks": 3}
    eng = request_context.engine_section(obs)
    assert (eng["t_enqueue"], eng["t_admit"], eng["t_first"],
            eng["t_last"]) == (10.0, 10.25, 11.0, 13.5)
    assert eng["queue_s"] + eng["prefill_s"] + eng["decode_s"] == \
        eng["t_last"] - eng["t_enqueue"]
    assert eng["prefill_s"] == 0.75 and eng["ttft_s"] == 1.0
    assert eng["tpot_s"] == 0.5 and eng["prefill_chunks"] == 3
    assert "request_id" not in eng
    # not yet admitted, and never seen by the engine
    queued = request_context.engine_section({"gen_start": 1.0})
    assert queued["queue_s"] is None and queued["prefill_s"] is None
    assert request_context.engine_section({"request_id": "r"}) is None
    # a disaggregated pair's halves ship durations only, and the decode
    # pool's graft is no prefill
    half = request_context.engine_section({**obs, "pool": "decode"})
    assert "prefill_s" not in half and half["decode_s"] == 2.5
    assert not [k for k in half if k.startswith("t_")]


def test_records_of_a_real_engine_run_carry_the_stamps():
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=128,
                    prompt_buckets=(16, 64), prefill_chunk=16)
    obs = {"request_id": "abc"}

    async def run():
        token = request_context._set_request_obs(obs)
        try:
            return [t async for t in eng.generate(list(range(1, 41)),
                                                  max_new_tokens=3)]
        finally:
            request_context._reset_request_obs(token)

    assert len(asyncio.run(run())) == 3
    sec = request_context.engine_section(obs)
    assert sec["t_enqueue"] <= sec["t_admit"] <= sec["t_first"] <= \
        sec["t_last"]
    assert sec["prefill_chunks"] == 3 and sec["tokens"] == 3
    assert abs(sec["queue_s"] + sec["prefill_s"] + sec["decode_s"]
               - (sec["t_last"] - sec["t_enqueue"])) < 1e-9


def test_memory_gauge_adds_the_reserved_peak(monkeypatch):
    """The rayt_device_memory_* gauges' peak is what *_peak_hbm_gb
    reports: peak_bytes_in_use + peak_bytes_reserved."""
    class Dev:
        platform, id = "tpu", 0

        def memory_stats(self):
            return {"bytes_in_use": 5, "peak_bytes_in_use": 7,
                    "peak_bytes_reserved": 11}

    monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
    assert telemetry.device_memory_snapshot() == [
        {"device": "tpu:0", "bytes_in_use": 5, "peak_bytes": 18}]


def test_spawned_processes_key_their_compile_cache_on_metadata():
    """A cache filled before the scopes existed must not serve their
    programs: the names a trace shows come from the executable."""
    env = spawn.child_env("/pkg", base={})
    assert env[spawn.COMPILE_CACHE_METADATA_ENV] == "true"
    assert env[spawn.COMPILE_CACHE_ENV] == "/pkg/.jax_cache"
    kept = spawn.child_env("/pkg", base={
        spawn.COMPILE_CACHE_METADATA_ENV: "false"})
    assert kept[spawn.COMPILE_CACHE_METADATA_ENV] == "false"


# ------------------------------- a model whose step counts on the device
def _traced_sparse_moe_run(tmp_path):
    """The dots3_note twin (models/dots3_note.py: its step returns the
    token-expert pairs it computed and the held experts it hit) under a
    profiler session. -> (engine, stats() before the traced run,
    {span: [stats, ...]} in order of start)."""
    from ray_tpu.models import dots3_note

    cfg = dots3_note.Dots3NoteConfig(
        vocab_size=128, dim=32, hidden_dim=48, moe_hidden_dim=16,
        n_routed_experts=16, experts_first=4, experts_held=8,
        experts_per_tok=4, n_heads=2, qk_nope_dim=8, qk_rope_dim=4,
        v_head_dim=8, q_rank=16, kv_rank=12, swa_n_heads=2,
        swa_qk_nope_dim=8, swa_qk_rope_dim=4, swa_v_head_dim=8,
        swa_q_rank=16, swa_kv_rank=12, sliding_window=9, ring_multiple=4,
        index_n_heads=8, index_head_dim=8, index_topk=12, max_seq_len=96,
        dtype=jnp.float32, param_dtype=jnp.float32)
    return _traced_model_run(tmp_path, cfg)


def _traced_model_run(tmp_path, cfg):
    """An engine of two slots for model config `cfg` under a profiler
    session, a short prompt and a chunked one, six tokens each.
    -> (engine, stats() before the traced run, {span: [stats, ...]} in
    order of start)."""
    eng = LLMEngine(cfg, tp=1, max_batch=2, prompt_buckets=(16, 64),
                    prefill_chunk=16)

    async def run():
        async def one(tokens):
            return [t async for t in eng.generate(tokens, max_new_tokens=6)]
        return await asyncio.gather(one([5, 9, 11]), one(list(range(1, 41))))

    asyncio.run(run())          # compile outside the session
    before = eng.stats()
    jax.profiler.start_trace(str(tmp_path), profiler_options=_trace_options())
    try:
        asyncio.run(run())
    finally:
        jax.profiler.stop_trace()
    by_name: dict = {}
    for _, name, start, _, stats in sorted(_host_spans(tmp_path),
                                           key=lambda s: s[2]):
        by_name.setdefault(name.removeprefix("rayt.engine."), []).append(
            stats)
    return eng, before, by_name


@pytest.fixture(scope="module")
def sparse_moe_run(tmp_path_factory):
    return _traced_sparse_moe_run(tmp_path_factory.mktemp("sparse_moe"))


def test_emit_span_carries_what_the_step_counted_on_the_device(
        sparse_moe_run):
    """`expert_rows`, `experts_hit` and `expert_tiles` are decided inside
    the step and read with its tokens; each step's three are on the emit
    span that hands out its tokens, and stats() sums them."""
    eng, before, by_name = sparse_moe_run
    emit, dispatch = by_name["emit"], by_name["decode_dispatch"]
    assert len(emit) == len(dispatch) >= 5
    for e, d in zip(emit, dispatch):
        assert {"active", "finished", "expert_rows", "experts_hit",
                "expert_tiles"} <= set(e)
        # 4 expert layers, 4 of 16 experts a token, 8 held: at most 4 x 4
        # pairs a live row, and no more experts hit than pairs or held
        assert 0 <= e["expert_rows"] <= 16 * d["active"]
        assert e["experts_hit"] <= min(e["expert_rows"], 4 * 8)
        assert (e["expert_rows"] == 0) == (e["experts_hit"] == 0)
        # an expert hit walks one tile of 16 rows, and one more for each
        # 16 pairs it holds beyond them
        assert e["expert_tiles"] >= e["experts_hit"]
        assert e["expert_tiles"] <= e["experts_hit"] + e["expert_rows"] // 16
    st = eng.stats()
    assert sum(e["expert_rows"] for e in emit) == \
        st["moe_expert_rows"] - before["moe_expert_rows"] > 0
    assert sum(e["experts_hit"] for e in emit) == \
        st["moe_experts_hit"] - before["moe_experts_hit"] > 0
    assert sum(e["expert_tiles"] for e in emit) == \
        st["moe_expert_tiles"] - before["moe_expert_tiles"] > 0
    # a token is never read in a second host sync for them: one
    # token_sync a step, as for any model
    assert len(by_name["token_sync"]) == len(emit)


def test_dispatch_span_carries_the_modules_counters(sparse_moe_run):
    """The five counters of stats(): three from the live rows' ranges,
    on the decode_dispatch span beside `active` and `live_positions`."""
    eng, before, by_name = sparse_moe_run
    st = eng.stats()
    names = ("decode_index_positions_scored",
             "decode_latent_positions_attended",
             "decode_window_positions_attended")
    for d in by_name["decode_dispatch"]:
        assert {"active", "live_positions", "t_host", *names} <= set(d)
        # two full layers score every live position; three sliding
        # layers attend to at most the window of each live row
        assert d["decode_index_positions_scored"] == 2 * d["live_positions"]
        assert d["decode_latent_positions_attended"] <= \
            2 * min(d["live_positions"], 12 * d["active"])
        assert d["decode_window_positions_attended"] <= 3 * 9 * d["active"]
    for name in names:
        assert sum(d[name] for d in by_name["decode_dispatch"]) == \
            st[name] - before[name] > 0
    for name in names + ("moe_expert_rows", "moe_experts_hit",
                         "moe_expert_tiles"):
        assert isinstance(st[name], int)
    # a model that counts nothing of its own keeps its stats as they were
    plain = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=64,
                      prompt_buckets=(16,), prefill_chunk=0)
    assert not set(plain.stats()) & set(names + ("moe_expert_rows",))
    assert not any(k.startswith("prefill_") and k.endswith(("_visited",
                   "_visible")) for k in plain.stats())


def test_prefill_chunk_span_carries_what_the_chunks_attention_visits(
        sparse_moe_run):
    """`dots3_note.prefill_counters` of every prefill call, chunked or
    whole, on its prefill_chunk span beside `pos` and `chunk`, and
    summed into stats()."""
    eng, before, by_name = sparse_moe_run
    st = eng.stats()
    names = ("prefill_latent_keys_visited", "prefill_latent_keys_visible",
             "prefill_window_keys_visited", "prefill_window_keys_visible")
    chunks = by_name["prefill_chunk"]
    # 3 tokens in bucket 16, whole; 40 in bucket 64 from the chunk that
    # holds its first token (position 24): 16-31, 32-47, 48-63
    assert [(c["pos"], c["chunk"]) for c in chunks if c["chunk"] == 16] \
        == [(0, 16), (16, 16), (32, 16), (48, 16)]
    for c in chunks:
        assert {"pos", "chunk", "last", *names} <= set(c)
        # two full layers, three sliding: a real query sees at most
        # index_topk 12 and the window's 9, and no more than is visited
        assert c[names[1]] <= 2 * 12 * c["chunk"] and \
            c[names[3]] <= 3 * 9 * c["chunk"]
        assert c[names[0]] >= c[names[1]] and c[names[2]] >= c[names[3]]
    for name in names:
        assert sum(c[name] for c in chunks) == st[name] - before[name] > 0


def test_dispatch_span_carries_the_state_rows_a_step_updates(tmp_path):
    """`granite_hybrid.decode_counters` on the decode_dispatch span and
    summed in stats(): the states a step had to update (its live rows x
    the Mamba layers) and those it did (every row's)."""
    from ray_tpu.models import granite_hybrid

    cfg = granite_hybrid.GraniteHybridConfig(
        vocab_size=128, dim=32, hidden_dim=48, n_heads=2, n_kv_heads=1,
        layer_types=("mamba", "attention", "mamba"), mamba_n_heads=4,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
        max_seq_len=96, dtype=jnp.float32, param_dtype=jnp.float32)
    eng, before, by_name = _traced_model_run(tmp_path, cfg)
    st = eng.stats()
    names = ("decode_state_rows_live", "decode_state_rows_updated")
    dispatch = by_name["decode_dispatch"]
    assert dispatch
    for d in dispatch:
        assert {"active", "live_positions", "t_host", *names} <= set(d)
        assert d[names[0]] == 2 * d["active"] and d[names[1]] == 2 * 2
    for name in names:
        assert isinstance(st[name], int)
        assert sum(d[name] for d in dispatch) == st[name] - before[name] > 0
    assert st[names[1]] == 2 * 2 * st["batches"]
    # the short request ends first: some steps had one live row of two
    assert st[names[0]] < st[names[1]]


def test_spans_carry_what_a_window_and_its_summaries_gave_a_step(tmp_path):
    """`evabyte.decode_counters` on the decode_dispatch spans and
    `evabyte.prefill_counters` on the prefill_chunk spans, each summed
    in stats(): the window's rows and the summaries a step's queries
    attend to, and those it reads; `windows_folded` on both kinds."""
    from ray_tpu.models import evabyte

    cfg = evabyte.EvaByteConfig(
        vocab_size=128, dim=32, n_layers=2, n_heads=2, hidden_dim=48,
        max_seq_len=96, window_size=8, chunk_size=2, n_pred_heads=8,
        dtype=jnp.float32, param_dtype=jnp.float32)
    eng, before, by_name = _traced_model_run(tmp_path, cfg)
    st = eng.stats()
    decode = ("decode_window_positions_live", "decode_summaries_live",
              "decode_window_positions_read", "decode_summaries_read")
    prefill = ("prefill_window_keys_visible", "prefill_window_keys_visited",
               "prefill_summaries_visible", "prefill_summaries_visited")
    dispatch, chunks = by_name["decode_dispatch"], by_name["prefill_chunk"]
    assert dispatch and len(chunks) == 4
    for d in dispatch:
        assert {"active", "live_positions", "t_host", "windows_folded",
                "chunks_folded", *decode} <= set(d)
        assert 0 <= d["chunks_folded"] <= d["active"]
        # two layers: at most the window's 8 rows a live row, and on the
        # CPU both parts of every row read whole (48 summaries of 96)
        assert 2 * d["active"] <= d[decode[0]] <= 2 * 8 * d["active"]
        assert (d[decode[2]], d[decode[3]]) == (2 * 2 * 8, 2 * 2 * 48)
    for c in chunks:
        assert {"pos", "chunk", "last", "windows_folded", *prefill} <= set(c)
        assert c[prefill[0]] <= c[prefill[1]] == 2 * c["chunk"] * (
            8 + c["chunk"])
        assert c[prefill[2]] <= c[prefill[3]]
    for name, spans in [(n, dispatch) for n in decode] \
            + [(n, chunks) for n in prefill]:
        assert isinstance(st[name], int)
        assert sum(s[name] for s in spans) == st[name] - before[name] > 0
    # positions 8, 16, 24, 32 of the 40-byte prompt and 40 of its six
    # decode steps (3 + 5 of the short request crosses none)
    assert sum(s["windows_folded"] for s in dispatch + chunks) == \
        st["windows_folded"] - before["windows_folded"] == 5
    assert sum(s["windows_folded"] for s in dispatch) == 1
    # chunks of 2: the decode steps that wrote an odd position, 41 and 43
    # of the long request's 40 to 44 and 3, 5 and 7 of the short's 3 to 7
    assert "chunks_folded" not in chunks[0]
    assert sum(s["chunks_folded"] for s in dispatch) == \
        st["chunks_folded"] - before["chunks_folded"] == 5


# ------------------------------------------- the process's own log (PR 40)
@pytest.fixture
def fresh_log(monkeypatch):
    """A log of its own for the test: the listeners write to whichever
    the module holds, and the engine asks the module each time."""
    from ray_tpu._internal import profiler

    log = profiler.ProcessLog()
    monkeypatch.setattr(profiler, "_LOG", log)
    return log


def _stages_of(records, program, fun):
    """The stages logged under `program` for jax's function `fun`
    (`step` where it is traced, `jit(step)` from there on)."""
    return sorted(r["stage"] for r in records if r["program"] == program
                  and r["fun_name"] in (fun, f"jit({fun})"))


def test_engine_names_every_program_it_asks_for_by_its_site(fresh_log):
    """(a) of the log: two requests of one bucket (the benchmark's
    warm-up: alone, then again) ask for each program of that bucket
    under the site's name; a third asks for nothing; a new bucket asks
    for exactly its own programs; `last` is the newest record."""
    log = fresh_log
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=256,
                    prompt_buckets=(16, 64), prefill_chunk=16,
                    prefix_cache_entries=0)

    def serve(tokens):
        async def one():
            return [t async for t in eng.generate(tokens, max_new_tokens=4)]
        return asyncio.run(one())

    assert len(serve([5, 9, 11])) == 4 and len(serve([5, 9, 12])) == 4
    recs = log.records()
    once = ["compile", "lower", "trace"]
    assert _stages_of(recs, "finish_prefill[16]", "insert_row") == once
    assert _stages_of(recs, "finish_prefill[16]", "set_slot") == once
    assert _stages_of(recs, "admit[16]", "<lambda>") == once   # retire
    # `step` is one function behind both phases: the site tells them
    # apart. Each is asked for once: the arrays the host makes for the
    # very first call (the key, the token vector, the temperatures) are
    # placed as a step's outputs are (PR 57; ROADMAP S7)
    for site in ("prefill_chunk[16@16]", "decode_dispatch[2]"):
        assert _stages_of(recs, site, "step") == once, site
    assert not [r for r in recs if r["program"] == "unlabelled"]
    assert {r["stage"] for r in recs} == set(once)   # no cache here
    assert all(r["t"] >= log.t0 and r["seconds"] >= 0 for r in recs)

    asked = log.appended
    callbacks = log.callbacks
    assert len(serve([5, 9, 13])) == 4
    # a warm request: no record, and no listener was even called
    assert (log.appended, log.callbacks) == (asked, callbacks)

    assert len(serve(list(range(1, 41)))) == 4    # bucket 64, in chunks
    new = log.records()[asked:]
    # (`admit` makes the request's own cache with jnp's zeros, which a
    # process compiles once for a shape, whoever asks first)
    assert {"prefill_chunk[16@64]", "finish_prefill[64]"} <= {
        r["program"] for r in new} <= {
        "admit[64]", "prefill_chunk[16@64]", "finish_prefill[64]"}
    assert _stages_of(new, "finish_prefill[64]", "insert_row") == once
    # a chunked prompt's program is the mixed one (PR 57), asked for
    # once (the second chunk's call, whose caches are the first's
    # outputs, misses jit's fast path and finds the trace it has)
    got = _stages_of(new, "prefill_chunk[16@64]", "mixed")
    assert got.count("compile") == got.count("lower") == 1, got
    assert not _stages_of(new, "prefill_chunk[16@64]", "step")
    assert not _stages_of(new, "finish_prefill[64]", "set_slot")

    programs = eng.stats()["programs"]
    assert programs["last"] == log.records()[-1]
    assert programs["last"]["program"] == "finish_prefill[64]"
    compiles = [r for r in log.records() if r["stage"] == "compile"]
    assert programs["asked"] == len(compiles) == sum(
        p["count"] for p in programs["by_program"].values())
    assert programs["dropped"] == 0 and programs["unlabelled"] == 0
    assert programs["by_program"]["finish_prefill[16]"]["fun_names"] == {
        "jit(insert_row)": 1, "jit(set_slot)": 1}
    for key in ("trace_s", "lower_s", "compile_s", "cache_load_s"):
        assert programs[key] == pytest.approx(sum(
            r["seconds"] for r in log.records() if r["stage"] + "_s" == key))
    # per program asked for: when, and the totals up to it
    assert [p[2] for p in programs["timeline"]] == list(
        range(1, len(compiles) + 1))
    assert [p[0] for p in programs["timeline"]] == [r["t"] for r in compiles]

    # a stage outside any site keeps jax's name for the function
    def outside(x):
        return x + 1

    jax.jit(outside)(jnp.ones((3,)))
    last = eng.stats()["programs"]["last"]
    assert (last["program"], last["fun_name"], last["stage"]) == (
        "unlabelled", "jit(outside)", "compile")
    assert eng.stats()["programs"]["unlabelled_since_ready"] >= 1


def test_startup_phases_are_ordered_and_end_at_ready(fresh_log):
    """(b) of the log: each phase once, in order, none inside another,
    the last the first step's return; what the node manager said in the
    handshake becomes `spawn_wait` and `boot` before them."""
    from ray_tpu._internal import profiler

    log = fresh_log
    wall, perf = log.anchor
    log.spawned({"tpu": False, "lease_asked": wall - 2.0,
                 "spawned": wall - 0.5, "chip_wait_s": 1.25})
    eng = LLMEngine("debug", tp=1, max_batch=2, max_seq_len=64,
                    prompt_buckets=(16,), prefill_chunk=0,
                    prefix_cache_entries=0)
    assert "ready" not in eng.stats()["startup"]["phases"]

    async def one():
        return [t async for t in eng.generate([3, 4, 5], max_new_tokens=2)]

    assert len(asyncio.run(one())) == 2
    startup = eng.stats()["startup"]
    phases = startup["phases"]
    assert list(phases) == list(profiler.PHASES)
    flat = [t for name in profiler.PHASES for t in phases[name]]
    assert flat == sorted(flat)
    assert phases["spawn_wait"] == pytest.approx([-1.5, 0.0])
    assert phases["boot"][0] == 0.0 and startup["chip_wait_s"] == 1.25
    assert startup["process_start"] == pytest.approx(perf - 0.5)
    assert startup["anchor"] == {"wall": wall, "perf_counter": perf}
    assert "unlabelled" not in eng.stats()["programs"]["by_program"]
    # a second engine, and a later step, are no phases
    LLMEngine("debug", tp=1, max_batch=2, max_seq_len=64,
              prompt_buckets=(16,), prefill_chunk=0, prefix_cache_entries=0)
    asyncio.run(one())
    assert eng.stats()["startup"] == startup


def test_log_keeps_the_newest_records_and_counts_the_rest(fresh_log,
                                                          monkeypatch):
    from ray_tpu._internal import profiler

    log = fresh_log
    for i in range(profiler._MAX_RECORDS + 10):
        log.on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                        f"jit(f{i})")
    programs = log.programs()
    assert programs["asked"] == profiler._MAX_RECORDS + 10
    assert programs["dropped"] == 10
    assert len(log.records()) == profiler._MAX_RECORDS
    assert programs["last"]["fun_name"] == \
        f"jit(f{profiler._MAX_RECORDS + 9})"
    assert programs["compile_s"] == pytest.approx(
        0.5 * (profiler._MAX_RECORDS + 10))
    assert set(programs["by_program"]) == {"unlabelled"}
    # asked for inside a phase, under no site: the phase's
    with log.phase("engine_build"):
        log.on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                        "jit(zeros)")
    assert log.programs()["by_program"]["engine_build"]["count"] == 1


def test_a_loaded_program_is_a_hit_and_its_load_is_not_counted_twice(
        fresh_log):
    """The cache's events carry no name: they belong to the compile
    stage that ends after them in their thread, under its label."""
    log = fresh_log
    log.label("decode_dispatch[8]")
    try:
        log.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25,
                        "step")
        # (a lowering that began before a trace did holds that trace,
        # of a helper of its own: this one, of no length, began after)
        log.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                        0.0, "jit(step)")
        log.on_event("/jax/compilation_cache/compile_requests_use_cache")
        log.on_event("/jax/compilation_cache/cache_hits")
        log.on_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0)
        log.on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                        1.5)
        log.on_duration("/jax/core/compile/backend_compile_duration", 1.75,
                        "jit(step)")
        # compiled, and written to the cache: a miss
        log.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                        0.5, "jit(step)")
        log.on_event("/jax/compilation_cache/cache_misses")
        log.on_duration("/jax/core/compile/backend_compile_duration", 12.0,
                        "jit(step)")
    finally:
        log.label(None)
    got = [(r["stage"], r["fun_name"], r["seconds"], r["cache"])
           for r in log.records()]
    assert got == [("trace", "step", 0.25, None),
                   ("lower", "jit(step)", 0.0, None),
                   ("cache_load", "jit(step)", 1.5, "hit"),
                   ("compile", "jit(step)", 0.25, "hit"),
                   ("lower", "jit(step)", 0.5, None),
                   ("compile", "jit(step)", 12.0, "miss")]
    assert {r["program"] for r in log.records()} == {"decode_dispatch[8]"}
    totals = log.programs()
    assert (totals["asked"], totals["cache_hits"],
            totals["cache_misses"]) == (2, 1, 1)
    assert totals["cache_load_s"] == 1.5 and totals["compile_s"] == 12.25
    assert log.callbacks == 10


def test_only_the_outermost_of_nested_traces_is_recorded(fresh_log,
                                                        monkeypatch):
    """jnp's own functions are jitted, so a model step's trace holds
    thousands of traces that end before it: they are dropped as the
    enclosing one ends (from the end of the list, not by a scan of it:
    a scan a trace cost the hybrid cell 2.7 s of warm-up, PR 40), and
    traces of the lowering's own helpers with the lowering."""
    from ray_tpu._internal import profiler

    log = fresh_log
    clock = iter(range(100, 10_000))
    monkeypatch.setattr(profiler.time, "perf_counter",
                        lambda: float(next(clock)))
    trace = "/jax/core/compile/jaxpr_trace_duration"
    for _ in range(50):                       # end at 100..149, 0.5 long
        log.on_duration(trace, 0.5, "add")
    assert len(log._thread.traces) == 50
    log.on_duration(trace, 60.0, "step")      # ends at 150, began at 90
    assert [r["fun_name"] for r in log._thread.traces] == ["step"]
    log.on_duration(trace, 0.25, "_threefry_split")   # in the lowering
    log.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                    1.5, "jit(step)")         # ends at 152, began at 150.5
    assert [(r["stage"], r["fun_name"], r["seconds"])
            for r in log.records()] == [("trace", "step", 60.0),
                                        ("lower", "jit(step)", 1.5)]
    assert log._thread.traces == [] and log.callbacks == 53


def test_step_recorder_puts_the_log_on_its_step_records(fresh_log):
    """The first step record carries `startup` and `programs` whole; a
    later one what was asked for since, and only if anything was. The
    compile event is what the stages under the step's label cost."""
    log = fresh_log
    rec = telemetry.StepRecorder("run", "exp")
    out: list = []
    rec._pub.publish = out.append
    step = rec.wrap_jit(jax.jit(lambda x: x * 2 + 1), "lora_step")
    x5, x7 = jnp.ones((5,)), jnp.ones((7,))   # asked for under no site

    step(x5)
    rec.end_step(1)
    (event,), (first,) = ([r for r in out if r["kind"] == k]
                          for k in ("compile", "step"))
    under = [r for r in log.records() if r["program"] == "lora_step"]
    assert sorted(r["stage"] for r in under) == ["compile", "lower", "trace"]
    assert event["event"] == "compile" and event["compile_s"] == \
        pytest.approx(sum(r["seconds"] for r in under))
    assert list(first["startup"]["phases"]) == ["ready"]
    assert first["programs"]["by_program"]["lora_step"]["count"] == 1
    assert "choices" not in first["programs"]["by_program"]["lora_step"]
    assert first["programs"]["last"] == under[-1]

    del out[:]
    step(x5)
    rec.end_step(2)
    assert [r["kind"] for r in out] == ["step"]
    assert "programs" not in out[0] and "startup" not in out[0]

    del out[:]
    step(x7)
    rec.end_step(3)
    event, third = out
    assert (event["event"], event["prev_shape"]) == (
        "retrace", "(float32[5])") and "7" in event["shape"]
    assert "startup" not in third
    delta = third["programs"]
    assert delta["asked"] == 1 and delta["last"]["program"] == "lora_step"

    assert set(delta) == {"asked", "cache_hits", "cache_misses", "trace_s",
                          "lower_s", "compile_s", "cache_load_s", "last"}

    # the GCS keeps both sections on the step records it serves
    from ray_tpu.core.gcs_train_manager import GcsTrainManager

    mgr = GcsTrainManager()
    mgr.ingest([first, third])
    kept = {s["step"]: s
            for s in mgr.list_steps(run_id="run", limit=0)["steps"]}
    assert kept[1]["startup"] == first["startup"]
    assert kept[1]["programs"] == first["programs"]
    assert kept[3]["programs"] == delta and "startup" not in kept[3]

    # the recipe's own programs have a name too (the adapters', the
    # optimizer state's, its random weights'; ROADMAP B13), and a
    # caller's `init_params_fn` keeps the caller's: none here
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.recipes import build_lora_step

    mesh = build_mesh({"data": 1, "fsdp": 1, "tensor": 1}, jax.devices()[:1])
    config = {"preset": "debug", "lora_rank": 4,
              "model_overrides": {"max_seq_len": 64}}
    at = log.appended
    _, state, cfg = build_lora_step(config, mesh)
    own = log.records()[at:]
    assert {r["program"] for r in own} == {"build_lora_step"}
    assert {"compile", "lower", "trace"} <= {r["stage"] for r in own}

    def init_params_fn(cfg):
        return jax.jit(lambda k: llama.init_params(cfg, k))(
            jax.random.PRNGKey(7))

    at = log.appended
    build_lora_step({**config, "init_params_fn": init_params_fn,
                     "lora_rank": 2}, mesh)
    by_name = {r["fun_name"]: r["program"] for r in log.records()[at:]
               if r["stage"] == "compile"}
    assert by_name.pop("jit(<lambda>)") == "unlabelled"
    assert by_name and set(by_name.values()) == {"build_lora_step"}
    assert log.programs()["by_program"]["build_lora_step"]["count"] >= 2


@pytest.mark.parametrize("path", ["fused", "split"])
def test_first_step_record_says_which_backward_the_flash_kernel_took(
        fresh_log, monkeypatch, path):
    """The backward's path and tile are fixed from the shapes as the
    step is traced; the process's log keeps them under the site the
    trace ran under, so a train worker's first step record names them
    beside `lora_step`."""
    from ray_tpu.ops.pallas import flash_attention as fa

    if path == "split":     # what a dq scratch that does not fit takes
        monkeypatch.setattr(fa, "_DQ_VMEM_BYTES", 0)
    rec = telemetry.StepRecorder("run", "exp")
    out: list = []
    rec._pub.publish = out.append
    step = rec.wrap_jit(jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, True, None, 128,
                                           None).sum(),
        argnums=(0, 1, 2))), "lora_step")
    q, kv = jnp.ones((1, 256, 4, 64)), jnp.ones((1, 256, 2, 64))
    step(q, kv, kv)
    rec.end_step(1)
    (first,) = (r for r in out if r["kind"] == "step")
    assert first["programs"]["by_program"]["lora_step"]["choices"] == {
        "flash_backward": {"path": path, "block_q": 128,
                           "block_k": fa.default_blocks(256, 256)[1],
                           "n_rep": 2, "seq": 256, "head_dim": 64}}
