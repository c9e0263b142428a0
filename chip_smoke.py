#!/usr/bin/env python3
"""chip_smoke.py — prove that the Train and Serve paths still start on the chip.

Drives the system through the entry points a user calls, at the full
width and depth of the `1b` preset (dim 2048, 16 layers, 16/8 heads at
head_dim 128, vocab 32000, seq 2048), with random weights made from
--seed. This process never initialises a jax backend: the chip is held
by one cluster worker at a time, the one whose lease was granted TPU.

Default run (one chip), one JSON line per phase, in this order:

  cluster  rt.init() with the TPU resource autodetected; store flavour
  train    JaxTrainer(lora_finetune_loop) on a TPU-leased worker
  serve    serve.run(llm_app) + HTTP requests through the proxy, held
           against `llama.forward` inside the replica
  kernel   compiled flash fwd/bwd against xla_attention, on the chip
  cache    the train step compiled again in a fresh worker: a cache hit
  tooling  jax.profiler trace and block_until_ready, on the train step

--chips 4 runs only the four-chip comparisons (fsdp x tensor train step
against one device; tp=4 engine against tp=1), in one worker process
that holds all four chips.

Last line of stdout: {"ok": true, "device": {"platform", "kind",
"count"}} as jax reports the device from inside the workers. Exit code 0
only if every phase ran on platform "tpu" and passed; otherwise the
failing phases are named and the code is 1. With no accelerator the
`cluster` phase fails: there is no CPU fallback and no result line.

The --preset/--seq-len/--batch/--steps options exist to rehearse the
control flow on the CPU at a toy size (with TPU_VISIBLE_CHIPS faked and
JAX_PLATFORMS=cpu); such a run always exits non-zero.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")  # everything this script writes

# Stated tolerances -------------------------------------------------------
# Greedy tokens vs `llama.forward`: the engine's token must be the
# reference argmax, or sit within this many logit units of it (logits of
# a random-weight model have std ~1; bf16 rounding in two differently
# ordered programs can swap a near tie, a wrong mask or position cannot
# hide inside it).
GREEDY_LOGIT_TOL = 0.1
# Flash kernels vs xla_attention on bf16 inputs: max |a-b| / max |b|.
KERNEL_FWD_TOL = 2e-2
KERNEL_BWD_TOL = 4e-2
# Four-chip vs one-chip train loss, per step, relative.
MESH_LOSS_RTOL = 1e-2
# block_until_ready vs host read-back of the same steps, relative.
SYNC_AGREE_RTOL = 0.25


def emit(phase: str, ok: bool, **fields) -> bool:
    print(json.dumps({"phase": phase, "ok": bool(ok), **fields},
                     default=str), flush=True)
    return bool(ok)


# ======================================================================
# Code that runs inside cluster workers. These functions are shipped by
# value (they live in __main__), so a worker needs nothing of this file.
# ======================================================================
def _device_report() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pid": os.getpid(),
            "jax_platforms": os.environ.get("JAX_PLATFORMS"),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir}


def _memory_stats() -> list:
    """jax's memory_stats() of every device ({} where it has none)."""
    import jax

    return [dict(d.memory_stats() or {}) for d in jax.devices()]


def _peak_bytes() -> list:
    return [int(m.get("peak_bytes_in_use", 0)) for m in _memory_stats()]


def _bytes_in_use() -> list:
    return [int(m.get("bytes_in_use", 0)) for m in _memory_stats()]


class _CompileLog:
    """Counts, in this process, the programs jax asked XLA for and how
    many of them the persistent cache answered."""

    def __init__(self):
        import jax

        self.requests = 0       # programs built or fetched
        self.seconds: list[tuple] = []  # (compile-or-fetch time, name)
        self.cache_hits = 0

        def on_event(event, *a, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        def on_duration(event, secs, *a, **kw):
            # fires for every program, compiled or fetched from the cache
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1
                self.seconds.append((float(secs), str(kw.get("fun_name"))))

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        return {"programs": self.requests,
                "programs_from_cache": self.cache_hits,
                "programs_compiled": self.requests - self.cache_hits,
                "longest_compile_s": max(self.seconds,
                                         default=(0.0, ""))[0],
                "longest_three": sorted(self.seconds, reverse=True)[:3]}


def _fixed_batch_fn(batch: int, seq: int, vocab: int, seed: int):
    """One fixed seeded batch for every step: the recipe's default draws
    fresh random tokens each step, which gives the adapters nothing to
    learn and the loss no reason to fall."""
    def batch_fn(step, rank):
        import jax
        import jax.numpy as jnp

        toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq),
                                  0, vocab)
        return {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    return batch_fn


def _smoke_train_loop(config: dict):
    """`lora_finetune_loop` as a user would run it, followed by one more
    report of what only the worker can see."""
    from ray_tpu import train
    from ray_tpu.train.recipes import lora_finetune_loop

    log = _CompileLog()
    steps_seen = []
    inner = config["batch_fn"]

    def batch_fn(step, rank):
        # compiles seen when step i's batch is asked for = everything up
        # to and including step i-1
        steps_seen.append(log.requests)
        return inner(step, rank)

    lora_finetune_loop({**config, "batch_fn": batch_fn})
    after_warmup = (log.requests - steps_seen[2]
                    if len(steps_seen) > 2 else None)
    train.report({**_device_report(), **log.snapshot(),
                  "programs_after_step_2": after_warmup,
                  "peak_bytes_in_use": _peak_bytes(),
                  "memory_stats": _memory_stats()[0]})


def _kernel_parity(b: int, s: int, h: int, hk: int, d: int,
                   seed: int, blocks: tuple | None = None,
                   flash_attention=None) -> dict:
    """Compiled flash forward/backward against xla_attention, bf16, in
    tiles of `blocks` (block_q, block_k) or, None, in those the kernel's
    caller chooses from the shape, as the train step runs it.
    `flash_attention`: another tree's op held to the same reference
    (tools/flash_attention_probe.py)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import xla_attention
    from ray_tpu.ops.pallas import flash_attention as fa

    flash_attention = flash_attention or fa.flash_attention
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(k1, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(k2, (b, s, hk, d), jnp.bfloat16)
    v = jax.random.normal(k3, (b, s, hk, d), jnp.bfloat16)
    w = jax.random.normal(k4, (b, s, h, d), jnp.bfloat16)  # cotangent mix

    def run(attn):
        def loss(q, k, v):
            out = attn(q, k, v)
            return (out.astype(jnp.float32)
                    * w.astype(jnp.float32)).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out,) + tuple(grads)

    bq, bk = blocks or (None, None)
    flash = run(lambda q, k, v: flash_attention(q, k, v, True, None,
                                                bq, bk))
    ref = run(lambda q, k, v: xla_attention(q, k, v, causal=True))

    def rel(a, r):
        a = a.astype(jnp.float32)
        r = r.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))

    errs = {name: rel(a, r) for name, a, r in
            zip(("out", "dq", "dk", "dv"), flash, ref)}
    finite = all(bool(jnp.isfinite(a.astype(jnp.float32)).all())
                 for a in flash)
    text = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, True, None, bq, bk)).lower(q, k, v).compile().as_text()
    return {"shape": {"b": b, "s": s, "heads": h, "kv_heads": hk, "d": d,
                      "dtype": "bfloat16"},
            "blocks": list(blocks or fa.default_blocks(s, s)),
            "rel_err": errs, "finite": finite,
            "tpu_custom_call": "tpu_custom_call" in text,
            "tolerance": {"fwd": KERNEL_FWD_TOL, "bwd": KERNEL_BWD_TOL},
            "within": (finite and errs["out"] <= KERNEL_FWD_TOL
                       and all(errs[g] <= KERNEL_BWD_TOL
                               for g in ("dq", "dk", "dv")))}


def _with_timeout(fn, seconds: float):
    """-> ("ok", result) | ("hung", None) | ("error", repr). A hung call
    keeps its daemon thread; the worker is torn down with its lease."""
    box: dict = {}

    def go():
        try:
            box["result"] = fn()
        except BaseException as e:  # reported, and the phase fails
            box["error"] = repr(e)

    t = threading.Thread(target=go, daemon=True)
    t.start()
    t.join(seconds)
    if t.is_alive():
        return "hung", None
    if "error" in box:
        return "error", box["error"]
    return "ok", box["result"]


def _chip_probe(config: dict, kernel_shape: dict, work: str) -> dict:
    """Phases kernel, cache and tooling: one fresh TPU-leased worker."""
    import glob

    import jax

    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.parallel.spmd import shard_batch
    from ray_tpu.train.recipes import build_lora_step

    log = _CompileLog()
    out: dict = {"device": _device_report()}
    out["kernel"] = _kernel_parity(seed=config["seed"], **kernel_shape)

    # ---- cache: the recipe's train step, built and compiled, not run
    mesh = build_mesh({"data": -1})  # TrainContext.get_mesh() with no axes
    step, state, cfg = build_lora_step(config, mesh)
    batch = shard_batch(config["batch_fn"](0, 0), mesh)
    before = log.snapshot()
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    secs = time.perf_counter() - t0
    out["cache"] = {"lower_and_compile_s": secs, **{
        k: v - before[k] for k, v in log.snapshot().items()
        if k.startswith("programs")}}

    # ---- tooling, on that step
    state, aux = compiled(state, batch)  # warm-up
    jax.block_until_ready(aux["loss"])

    def timed(sync, n=3):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n):
            state, aux = compiled(state, batch)
        sync(aux["loss"])
        return (time.perf_counter() - t0) / n

    bur = timed(jax.block_until_ready)
    readback = timed(float)
    out["sync"] = {
        "block_until_ready_s_per_step": bur,
        "host_readback_s_per_step": readback,
        "verdict": ("worked" if abs(bur - readback)
                    <= SYNC_AGREE_RTOL * readback else "disagrees")}

    trace_dir = os.path.join(work, "trace")

    def trace():
        nonlocal state
        t0 = time.perf_counter()
        with jax.profiler.trace(trace_dir):
            for i in range(3):
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    state, aux = compiled(state, batch)
                    jax.block_until_ready(aux["loss"])
        return time.perf_counter() - t0

    status, secs = _with_timeout(trace, 240.0)
    prof: dict = {"verdict": {"ok": "worked"}.get(status, status),
                  "detail": secs}
    if status == "ok":
        planes = {}
        for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True):
            prof["xplane_bytes"] = os.path.getsize(path)
            data = jax.profiler.ProfileData.from_file(path)
            for plane in data.planes:
                n = sum(len(list(line.events)) for line in plane.lines)
                if n:
                    planes[plane.name] = n
        prof["events_by_plane"] = planes
        prof["traced_3_steps_s"] = secs
        if not any(p.startswith("/device:TPU") for p in planes):
            prof["verdict"] = "no device plane in the trace"
    out["profiler"] = prof
    out["peak_bytes_in_use"] = _peak_bytes()
    out["memory_stats"] = _memory_stats()[0]
    out["step_memory_analysis"] = {
        k: int(getattr(compiled.memory_analysis(), k)) for k in (
            "argument_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes")}
    return out


def _four_chip(config: dict, prompts: list, new_tokens: int) -> dict:
    """--chips 4: both comparisons in the one process that holds all four
    chips. (a) the LoRA train step on fsdp=2 x tensor=2 against the same
    steps on one device; (b) LLMEngine tp=4 against tp=1."""
    import asyncio
    import gc
    import re

    import jax

    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.parallel.spmd import shard_batch
    from ray_tpu.serve.llm import LLMEngine, greedy_reference_check
    from ray_tpu.train.recipes import build_lora_step

    out: dict = {"device": _device_report()}
    devices = jax.devices()

    def collectives(text: str) -> dict:
        found = re.findall(
            r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", text)
        return {k: found.count(k) for k in sorted(set(found))}

    def shard_devices(arr) -> list:
        return sorted({s.device.id for s in arr.addressable_shards})

    def train_steps(mesh, n):
        step, state, _ = build_lora_step(config, mesh)
        batch = shard_batch(config["batch_fn"](0, 0), mesh)
        t0 = time.perf_counter()
        compiled = step.lower(state, batch).compile()
        info = {"compile_s": time.perf_counter() - t0,
                "collectives": collectives(compiled.as_text()),
                "bytes_in_use": _bytes_in_use(),
                "wq_shard_devices": shard_devices(
                    state["frozen"]["layers"]["wq"]),
                "wq_shard_shape": list(
                    state["frozen"]["layers"]["wq"]
                    .addressable_shards[0].data.shape)}
        losses = []
        for _ in range(n):
            state, aux = compiled(state, batch)
            losses.append(float(aux["loss"]))
        info["losses"] = losses
        return info

    steps = config["steps"]
    a4 = train_steps(build_mesh({"fsdp": 2, "tensor": 2}, devices), steps)
    gc.collect()
    a1 = train_steps(build_mesh({"data": 1}, devices[:1]), steps)
    gc.collect()
    rel = [abs(x - y) / abs(y) for x, y in zip(a4["losses"], a1["losses"])]
    out["train"] = {
        "mesh": {"fsdp": 2, "tensor": 2}, "four": a4, "one": a1,
        "loss_rel_diff": rel, "tolerance": MESH_LOSS_RTOL,
        "within": (max(rel) <= MESH_LOSS_RTOL
                   and len(a4["wq_shard_devices"]) == 4
                   and all(b > 0 for b in a4["bytes_in_use"]))}

    def engine_tokens(tp):
        eng = LLMEngine(config["preset"], tp=tp,
                        max_seq_len=config["seq_len"],
                        seed=config["seed"])
        info = {"bytes_in_use": _bytes_in_use(),
                "wq_shard_devices": shard_devices(
                    eng.params["layers"]["wq"])}

        async def gen(p):
            return [t async for t in eng.generate(
                p, max_new_tokens=new_tokens)]

        async def all_prompts():
            return [await gen(p) for p in prompts]

        info["tokens"] = asyncio.run(all_prompts())
        info["vs_forward"] = [greedy_reference_check(eng, p, g)
                              for p, g in zip(prompts, info["tokens"])]
        eng._ensure_decode_cache()
        info["decode_collectives"] = collectives(
            eng._step_jit.lower(eng.params, eng._decode_cache, eng._cur,
                                eng._key, eng._temps).compile().as_text())
        return info

    b4 = engine_tokens(4)
    gc.collect()
    b1 = engine_tokens(1)
    same = b4["tokens"] == b1["tokens"]
    margins = [c["max_margin"] for e in (b4, b1) for c in e["vs_forward"]]
    for e in (b4, b1):
        for c in e["vs_forward"]:
            c.pop("reference")
    out["serve"] = {
        "tp4": b4, "tp1": b1, "same_tokens": same,
        "max_margin_vs_forward": max(margins),
        "tolerance": GREEDY_LOGIT_TOL,
        # where a bf16 near tie splits the two meshes the tokens differ
        # from there on (same_tokens says so); what is required is that
        # both are greedy decodings of the reference within tolerance
        "within": (max(margins) <= GREEDY_LOGIT_TOL
                   and len(b4["wq_shard_devices"]) == 4
                   and all(b > 0 for b in b4["bytes_in_use"]))}
    out["peak_bytes_in_use"] = _peak_bytes()
    return out


def _plain_worker_view() -> dict:
    """What a worker with no TPU lease is handed (it must not import jax
    to find out: the answer is its environment)."""
    from ray_tpu._native import load_shm_lib, native_unavailable_reason

    native = load_shm_lib() is not None
    return {"jax_platforms": os.environ.get("JAX_PLATFORMS"),
            "shm_mode": os.environ.get("RAYT_SHM_MODE"),
            "native_store_loads": native,
            "native_unavailable": native_unavailable_reason(),
            "compile_cache_dir": os.environ.get(
                "JAX_COMPILATION_CACHE_DIR")}


class _Pinger:
    def ping(self):
        return _device_report()


# ======================================================================
# The driver side.
# ======================================================================
def _tpu_worker_pids() -> list:
    from ray_tpu import state_api

    return sorted(w["pid"] for w in state_api.list_workers()
                  if w.get("tpu"))


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def _wait(cond, timeout: float, every: float = 0.2) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(every)
    return cond()


def phase_cluster(rt, want_chips: int) -> bool:
    rt.init()  # as the README's quick start: nothing passed in
    res = rt.cluster_resources()
    tpu = {k: v for k, v in res.items() if k.startswith("TPU")}
    if res.get("TPU", 0) < want_chips:
        from ray_tpu._internal.accelerators import detect_tpu_slice

        info = detect_tpu_slice(use_metadata=False)
        return emit("cluster", False,
                    error=f"the node advertises TPU={res.get('TPU', 0):g}, "
                          f"this run needs {want_chips}: no accelerator "
                          "was detected from the environment or /dev",
                    detected=(info.__dict__ if info else None),
                    resources=res)
    view = rt.get(rt.remote(num_cpus=0)(_plain_worker_view).remote(),
                  timeout=120)
    ok = view["jax_platforms"] == "cpu"
    return emit("cluster", ok, resources=tpu, cpus=res.get("CPU"),
                object_store=("native arena (shm_store.cpp, built with g++)"
                              if view["shm_mode"] == "native"
                              else "python shm segments (native store "
                              f"unavailable: {view['native_unavailable']})"),
                worker_without_lease=view,
                driver_jax_platforms=os.environ.get("JAX_PLATFORMS"))


def phase_train(args, config: dict) -> tuple[bool, dict]:
    from ray_tpu import state_api
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.config import CheckpointConfig

    seen: set = set()
    stop = threading.Event()

    def watch():
        while not stop.is_set():
            try:
                seen.update(_tpu_worker_pids())
            except Exception:
                pass
            stop.wait(0.5)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    t0 = time.perf_counter()
    try:
        result = JaxTrainer(
            _smoke_train_loop, train_loop_config=config,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            run_config=RunConfig(
                name="chip_smoke", storage_path=os.path.join(WORK, "train"),
                checkpoint_config=CheckpointConfig(num_to_keep=2)),
        ).fit()
    finally:
        stop.set()
        watcher.join(5)
    wall = time.perf_counter() - t0
    m = result.metrics or {}
    pid = m.get("pid")

    # the recipe's own telemetry (train/telemetry.py -> GCS train manager)
    runs = state_api.list_train_runs(experiment="chip_smoke")
    run_id = runs[0]["run_id"] if runs else None
    steps = sorted(state_api.list_train_steps(run_id=run_id, limit=0),
                   key=lambda s: s["step"]) if run_id else []
    summary = (state_api.summarize_train_runs(run_id=run_id) or {}
               ).get("runs", {}).get(run_id, {}) if run_id else {}
    losses = [s.get("loss") for s in steps]
    step_s = [s["stages"].get("step_s") for s in steps]
    ckpt = result.checkpoint
    ckpt_files = (sorted(os.listdir(os.path.join(ckpt.path, "rank_0")))
                  if ckpt is not None and os.path.isdir(
                      os.path.join(ckpt.path, "rank_0")) else [])
    gone = pid is not None and _wait(
        lambda: not _pid_alive(pid) and not _tpu_worker_pids(), 30)
    finite = bool(losses) and all(
        l is not None and math.isfinite(l) for l in losses)
    ok = (m.get("platform") == "tpu" and finite
          and len(losses) >= args.steps and losses[-1] < losses[0]
          and bool(ckpt_files) and gone and seen == {pid}
          and summary.get("retrace_count", 0) == 0
          and m.get("programs_after_step_2") == 0)
    emit("train", ok,
         device={k: m.get(k) for k in ("platform", "kind", "count")},
         preset=config["preset"], seq_len=config["seq_len"],
         batch_size=config["batch_size"], steps=len(losses),
         losses=losses, first_loss=losses[0] if losses else None,
         last_loss=losses[-1] if losses else None,
         step_s=step_s, step_s_after_warmup=step_s[2:],
         wall_s_per_step_after_warmup=[s["wall_s"] for s in steps[2:]],
         recipe_compile_events=summary.get("compile_count"),
         recipe_retraces=summary.get("retrace_count"),
         programs=m.get("programs"),
         programs_from_cache=m.get("programs_from_cache"),
         programs_compiled=m.get("programs_compiled"),
         programs_after_step_2=m.get("programs_after_step_2"),
         longest_compile_s=m.get("longest_compile_s"),
         longest_three=m.get("longest_three"),
         compile_cache_dir=m.get("compile_cache_dir"),
         peak_bytes_in_use=m.get("peak_bytes_in_use"),
         memory_stats=m.get("memory_stats"),
         telemetry_memory_peak_bytes=summary.get("memory_peak_bytes"),
         checkpoint_files=ckpt_files, fit_wall_s=wall,
         worker={"pid": pid, "jax_platforms": m.get("jax_platforms"),
                 "tpu_workers_seen_during_fit": sorted(seen),
                 "exited_before_next_phase": gone})
    return ok, m


def _post_stream(port: int, app: str, payload: dict, marks: dict):
    """One streamed request through the proxy; arrival time of every
    SSE token lands in marks["t"], the tokens in marks["tokens"]."""
    marks.update(t=[], tokens=[], sent=time.perf_counter())
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", f"/{app}?stream=1", body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        marks["status"] = resp.status
        marks["request_id"] = resp.getheader("X-Rayt-Request-Id")
        marks["content_type"] = resp.getheader("Content-Type")
        while True:
            line = resp.readline()
            if not line:
                break
            if line.startswith(b"data:"):
                item = json.loads(line[5:])
                if isinstance(item, dict) and "token" in item:
                    marks["t"].append(time.perf_counter())
                    marks["tokens"].append(item["token"])
                else:
                    marks["error"] = item
    except Exception as e:
        marks["error"] = repr(e)
    finally:
        conn.close()
        marks["done"] = time.perf_counter()


def _timing(marks: dict) -> dict:
    t = marks["t"]
    gaps = [b - a for a, b in zip(t, t[1:])]
    return {"tokens": len(t),
            "ttft_s": (t[0] - marks["sent"]) if t else None,
            "gaps_s": gaps,
            "total_s": marks.get("done", 0) - marks["sent"]}


def phase_serve(rt, args, prompts: dict) -> tuple[bool, dict]:
    from ray_tpu import serve, state_api
    from ray_tpu.serve.llm import llm_app

    t0 = time.perf_counter()
    port = serve.start(request_timeout_s=900.0)
    handle = serve.run(llm_app(args.preset, max_seq_len=args.seq_len,
                               seed=args.seed),
                       name="llm", timeout=900.0)

    def call(method, *a):
        return handle.options(method_name=method).remote(*a).result(
            timeout=900)

    dev = call("device_report")
    ready_s = time.perf_counter() - t0  # replica up, weights on device
    tpu_pids = _tpu_worker_pids()

    # a second TPU lease on the one-chip node must wait, not run on CPU
    second = rt.remote(num_tpus=1, num_cpus=0)(_Pinger).remote()
    try:
        rt.get(second.ping.remote(), timeout=8)
        second_lease = "ran"  # two processes were given one chip
    except Exception as e:
        second_lease = ("pending" if "imeout" in type(e).__name__
                        else f"failed: {e!r}")
    rt.kill(second)

    # warm every program the measured requests use; cold = with compile
    cold = {}
    for name in ("warm_short", "warm_long"):
        m: dict = {}
        _post_stream(port, "llm", {"tokens": prompts[name],
                                   "max_new_tokens": 4}, m)
        cold[name] = {"prompt_len": len(prompts[name]), **_timing(m),
                      "error": m.get("error")}
    before = call("stats")
    programs_warm = call("device_report")["step_programs"]

    # A streams; B (a 1024-bucket prompt, prefilled in chunks) and C are
    # sent while A is mid-decode
    def stream(name, new_tokens):
        marks: dict = {}
        return marks, threading.Thread(target=_post_stream, args=(
            port, "llm", {"tokens": prompts[name],
                          "max_new_tokens": new_tokens}, marks))

    a, ta = stream("a", args.new_tokens_a)
    b, tb = stream("b", 8)
    c, tc = stream("c", 8)
    ta.start()
    _wait(lambda: len(a.get("t", ())) >= 4 or "done" in a, 600, 0.002)
    a_at_b = len(a["t"])
    tb.start()
    _wait(lambda: len(a.get("t", ())) >= a_at_b + 4 or "done" in a, 600,
          0.002)
    a_at_c = len(a["t"])
    tc.start()
    for t in (ta, tb, tc):
        t.join(900)
    after = call("stats")

    checks = {name: call("reference_check", prompts[name], m["tokens"])
              for name, m in (("a", a), ("b", b))
              if m.get("tokens")}
    for chk in checks.values():
        chk.pop("reference")

    # the replica's own view of each request (serve/request_context.py)
    engine = {}
    for name, m in (("a", a), ("b", b), ("c", c)):
        rid = m.get("request_id")
        rec = None
        if rid:
            _wait(lambda: state_api.get_serve_request(rid) is not None, 10)
            rec = state_api.get_serve_request(rid)
        engine[name] = (rec or {}).get("engine")

    # A's tokens that arrived between B being sent and B's first token:
    # decode steps interleaved with B's prefill chunks
    a_during_b_prefill = (sum(1 for t in a["t"]
                              if b["sent"] <= t <= b["t"][0])
                          if b.get("t") else 0)
    b_chunks = (engine["b"] or {}).get("prefill_chunks")
    streamed = all(m.get("status") == 200
                   and (m.get("content_type") or "").startswith(
                       "text/event-stream")
                   and "error" not in m for m in (a, b, c))
    greedy_ok = (set(checks) == {"a", "b"} and all(
        chk["max_margin"] <= GREEDY_LOGIT_TOL for chk in checks.values()))
    admitted_mid_decode = (
        bool(c.get("t")) and bool(a.get("t"))
        and a_at_c < len(a["t"]) and c["t"][0] < a["t"][-1])
    ok = (dev["platform"] == "tpu" and streamed and greedy_ok
          and len(a["tokens"]) == args.new_tokens_a
          and len(b["tokens"]) == 8 and len(c["tokens"]) == 8
          and (b_chunks or 0) >= 4 and a_during_b_prefill >= 2
          and admitted_mid_decode and second_lease != "ran"
          and tpu_pids == [dev["pid"]])
    mem = dev.pop("memory")
    final = call("device_report")
    emit("serve", ok, device=dev, ready_s=ready_s, http_port=port,
         tpu_workers=tpu_pids, second_tpu_lease=second_lease,
         cold_request_s=cold,
         requests={
             "a": {"prompt_len": len(prompts["a"]), **_timing(a)},
             "b": {"prompt_len": len(prompts["b"]),
                   "sent_when_a_had": a_at_b, **_timing(b)},
             "c": {"prompt_len": len(prompts["c"]),
                   "sent_when_a_had": a_at_c, **_timing(c)}},
         engine_records=engine,
         a_tokens_during_b_prefill=a_during_b_prefill,
         b_prefill_chunks=b_chunks,
         c_admitted_mid_decode=admitted_mid_decode,
         greedy_vs_forward={"tolerance_logits": GREEDY_LOGIT_TOL,
                            **checks},
         decode_steps=after["batches"] - before["batches"],
         prefills=after["prefills"] - before["prefills"],
         prefill_chunks=(after["prefill_chunks"]
                         - before["prefill_chunks"]),
         engine_stats=after,
         step_programs={"after_warm_up": programs_warm,
                        "after_requests": final["step_programs"]},
         peak_bytes_in_use=[m_.get("peak_bytes_in_use") for m_ in
                            final["memory"]],
         bytes_in_use_at_start=[m_.get("bytes_in_use") for m_ in mem])
    serve.shutdown()
    gone = _wait(lambda: not _pid_alive(dev["pid"])
                 and not _tpu_worker_pids(), 60)
    if not gone:
        ok = emit("serve", False,
                  error="the replica's process outlived serve.shutdown()")
    return ok, dev


def phase_probe(rt, args, config: dict, train_metrics: dict) -> tuple:
    from ray_tpu.models.llama import PRESETS

    p = PRESETS[args.preset]
    shape = {"b": 2, "s": args.seq_len, "h": p["n_heads"],
             "hk": p["n_kv_heads"], "d": p["dim"] // p["n_heads"]}
    probe = rt.remote(num_tpus=1)(_chip_probe)
    out = rt.get(probe.remote(config, shape, WORK), timeout=1000)
    dev = out["device"]
    on_tpu = dev["platform"] == "tpu"

    k = out["kernel"]
    ok_k = emit("kernel", on_tpu and k["within"]
                and k["tpu_custom_call"], device=dev, **k)

    c = out["cache"]
    ok_c = emit(
        "cache", on_tpu and c["programs"] == 1
        and c["programs_from_cache"] == 1,
        compile_cache_dir=dev["compile_cache_dir"],
        first={"where": "train phase worker",
               **{k: train_metrics.get(k) for k in (
                   "programs", "programs_from_cache", "programs_compiled",
                   "longest_compile_s")}},
        second={"where": f"fresh worker pid {dev['pid']}, train step only",
                "hit": c["programs_from_cache"] == 1, **c})

    ok_t = emit("tooling", on_tpu
                and out["profiler"]["verdict"] == "worked"
                and out["sync"]["verdict"] == "worked",
                profiler=out["profiler"], block_until_ready=out["sync"],
                peak_bytes_in_use=out["peak_bytes_in_use"],
                memory_stats=out["memory_stats"],
                step_memory_analysis=out["step_memory_analysis"])
    return ok_k and ok_c and ok_t, dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    # rehearsal sizes (see the module docstring); the defaults are the run
    ap.add_argument("--preset", default="1b")
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=6)
    args = ap.parse_args()
    args.new_tokens_a = 48

    try:
        import ray_tpu as rt
        from ray_tpu.models.llama import PRESETS
    except ImportError as e:
        print(f"chip_smoke: the ray_tpu package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2

    import random

    vocab = PRESETS[args.preset]["vocab_size"]
    rng = random.Random(args.seed)

    def prompt(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    long_n = min(900, args.seq_len // 2 - 16)  # > 768: four 256-chunks
    prompts = {"warm_short": prompt(20), "warm_long": prompt(long_n),
               "a": prompt(24), "b": prompt(long_n), "c": prompt(17)}
    config = {"preset": args.preset, "seq_len": args.seq_len,
              "batch_size": args.batch, "steps": args.steps,
              "report_every": 1, "seed": args.seed,
              "model_overrides": {"max_seq_len": args.seq_len},
              "batch_fn": _fixed_batch_fn(args.batch, args.seq_len, vocab,
                                          args.seed)}

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    failed: list = []
    devices: list = []
    try:
        if not phase_cluster(rt, args.chips):
            print("chip_smoke: FAILED in phase cluster", file=sys.stderr)
            return 1
        if args.chips == 4:
            four = rt.remote(num_tpus=4)(_four_chip)
            out = rt.get(four.remote(
                {**config, "steps": 5}, [prompts["a"], prompts["b"]], 8),
                timeout=3000)
            devices.append(out["device"])
            for name in ("train", "serve"):
                if not emit(f"four_chip_{name}", out[name].pop("within"),
                            **out[name]):
                    failed.append(f"four_chip_{name}")
            emit("four_chip_memory", True,
                 peak_bytes_in_use=out["peak_bytes_in_use"])
        else:
            ok, m = phase_train(args, config)
            devices.append(m)
            if not ok:
                failed.append("train")
            ok, dev = phase_serve(rt, args, prompts)
            devices.append(dev)
            if not ok:
                failed.append("serve")
            ok, dev = phase_probe(rt, args, config, m)
            devices.append(dev)
            if not ok:
                failed.append("kernel/cache/tooling")
    finally:
        try:
            rt.shutdown()
        finally:
            shutil.rmtree(WORK, ignore_errors=True)

    # this process asked for no device: it has no backend to hold a chip
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            failed.append("parent initialised a jax backend")
    if not all(d.get("platform") == "tpu" and d.get("count") == args.chips
               for d in devices):
        failed.append("a phase did not run on %d tpu device(s): %s" % (
            args.chips, [(d.get("platform"), d.get("count"))
                         for d in devices]))
    if failed:
        print("chip_smoke: FAILED in phase " + "; ".join(failed),
              file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d["platform"], "kind": d["kind"],
        "count": d["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
