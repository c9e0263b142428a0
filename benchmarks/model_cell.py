"""The serve cell of a configuration that names its own helper module
(its file's "model" key; benchmarks/model_deployment.py), from the
command's own process: benchmarks/serve_cell.py's closed-loop run with
the differences a file that may not be edited leaves no other way to
make. The deployment class it binds takes the model from the
configuration file, so this driver binds none and a later cell of
another model needs no driver of its own. The traffic file gives its
cycle as explicit `shapes` ([[prompt tokens, output tokens], ...]): the
lengths are the cell's definition and not quantiles of a distribution,
and its `schedule_seed` fixes their order for every seed. The check's
samples are one finished request of each shape that fits `check_len`,
the longest first. The window's cut, the metrics and `info` are
serve_cell's own; `correct` is the helper's (its own limits beside
serve_cell's two).
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import random
import threading
import time

import numpy as np

from benchmarks.client import ClosedLoop, Stream, post_stream
from benchmarks.manifest import Cell
from benchmarks.serve_cell import (attempted_failed, info,  # noqa: F401
                                   start_cluster)
from benchmarks.traffic import Request


def _helper(config: dict):
    return importlib.import_module("benchmarks." + config["model"])


def correct(obs: dict, tol: dict) -> bool:
    return _helper(obs["config"]).correct(obs, tol)


def closed_loop(spec: dict, vocab: int, seed: int):
    """An endless iterator over cycles of exactly the file's `shapes`,
    prompts and outputs shuffled apart anew in each cycle as
    traffic.closed_loop's strata are. The shuffles are drawn from the
    file's `schedule_seed`, so every seed meets the same lengths in the
    same order, as every seed of an open loop meets one schedule
    (benchmarks/traffic.py); the seed draws the token ids."""
    rng = np.random.default_rng([int(seed), 2])
    order = np.random.default_rng([int(spec["schedule_seed"]), 4])
    index = 0
    while True:
        prompts = [int(p) for p, _ in spec["shapes"]]
        outputs = [int(o) for _, o in spec["shapes"]]
        order.shuffle(prompts)
        order.shuffle(outputs)
        for p, o in zip(prompts, outputs):
            yield Request(index=index, due_s=0.0, max_new_tokens=o,
                          tokens=rng.integers(1, vocab, size=p).tolist(),
                          stratum="cycle")
            index += 1


def check_samples(streams: list, window: tuple, seed: int, chk: dict) -> list:
    """The requests the check reads: of those that ended whole once the
    window had begun (inside it first, then in the drain behind it), one
    of each prompt length that fits `check_len`, the longest first, up
    to `samples`; the seed draws which."""
    t0, t1 = window
    done = [s for s in streams if s.done is not None and s.error is None
            and s.done >= t0 and len(s.tokens) == s.req.max_new_tokens
            and len(s.req.tokens) <= int(chk["check_len"])]
    random.Random(seed).shuffle(done)
    done.sort(key=lambda s: s.done > t1)
    by_len: dict = {}
    for s in done:
        by_len.setdefault(len(s.req.tokens), s)
    picked = [by_len[n] for n in sorted(by_len, reverse=True)]
    return picked[:int(chk["samples"])]


def run(cell: Cell, seed: int, seconds: float, trace: bool, work: str,
        t_process: float) -> dict:
    # a program from before the model existed cannot run this cell: say
    # so at once, before a cluster is up
    module = _helper(cell.config).PROGRAM_MODULE
    if importlib.util.find_spec(module) is None:
        raise RuntimeError(f"this program has no {module}: it cannot run "
                           + cell.name)
    from ray_tpu import serve, state_api
    from ray_tpu.serve.deployment import deployment

    from benchmarks.model_deployment import BenchModelService

    tr = cell.traffic
    if tr["kind"] != "serve_closed":
        raise ValueError("model_cell runs closed loops only")
    vocab = int(cell.config["vocab_size"])
    setup = {"imports_s": time.perf_counter() - t_process}
    t = time.perf_counter()
    rt = start_cluster(cell.chips)
    setup["cluster_s"] = time.perf_counter() - t

    t = time.perf_counter()
    port = serve.start(request_timeout_s=900.0)
    # every caller's request may be at the replica at once; a cold start
    # compiles for minutes inside the replica's constructor, where the
    # default probe (5 s, twice) would kill it
    dep = deployment(BenchModelService,
                     max_ongoing_requests=max(64, 2 * int(tr["clients"])),
                     health_check_timeout_s=600.0,
                     health_check_failure_threshold=3,
                     ray_actor_options={"num_tpus": cell.chips})
    handle = serve.run(dep.bind(cell.config, seed, tr["engine"]),
                       name="llm", timeout=1100.0)

    def call(method, *a, timeout=900):
        return handle.options(method_name=method).remote(*a).result(
            timeout=timeout)

    rep0 = call("bench_report")
    setup["replica_up_s"] = time.perf_counter() - t
    setup.update({"replica_" + k: v for k, v in rep0["setup"].items()})
    obs: dict = {"device": {k: rep0[k]
                            for k in ("platform", "kind", "count")}}

    # ---- warm every shape this cell's traffic uses: one at a time (each
    # compiles or loads alone), then all at once, so that every program
    # also meets arguments left by another kind of step
    t = time.perf_counter()
    rng = np.random.default_rng([int(seed), 3])

    def warm_stream(i, w):
        return Stream(Request(-1 - i, 0.0, rng.integers(
            1, vocab, size=w["prompt_len"]).tolist(),
            w["max_new_tokens"], "warm"), time.perf_counter())

    warm = [warm_stream(i, w) for i, w in enumerate(tr["warm"])]
    for s in warm:
        post_stream(port, "llm", s)
    again = [warm_stream(i, w) for i, w in enumerate(tr["warm"])]
    threads = [threading.Thread(target=post_stream, args=(port, "llm", s))
               for s in again]
    for th in threads:
        th.start()
        time.sleep(0.15)
    for th in threads:
        th.join(600)
    for s in warm + again:
        if s.error or len(s.tokens) != s.req.max_new_tokens:
            raise RuntimeError(
                f"warm-up request of {len(s.req.tokens)} tokens failed: "
                f"{s.error!r}, {len(s.tokens)} tokens")
    setup["warm_shapes_s"] = time.perf_counter() - t

    # ---- the load: lead-in, then the window, cut out of the stamps
    lead = float(tr["lead_s"])
    loop = ClosedLoop(port, "llm", closed_loop(tr, vocab, seed),
                      int(tr["clients"]))
    loop.begin()
    t0 = loop.start + lead
    t1 = t0 + seconds
    time.sleep(max(0.0, t0 - time.perf_counter()))
    before = call("bench_report")
    setup["lead_s"] = lead
    obs["setup_s"] = t0 - t_process
    if trace:
        # the last trace_s of the window are profiled; stopping the
        # profiler takes many seconds, so it is stopped after the window
        time.sleep(max(0.0, t1 - float(tr["trace_s"])
                       - time.perf_counter()))
        call("trace_start", os.path.join(work, "trace"))
    time.sleep(max(0.0, t1 - time.perf_counter()))
    after = call("bench_report")
    if trace:
        obs["traced"] = call("trace_stop")
    # the load goes on until a first token has arrived after the window:
    # the rate reads the prompt counter on both sides of each edge
    drain = t1 + float(tr.get("drain_first_tokens_s", 0.0))
    while time.perf_counter() < drain and not any(
            s.t and s.t[0] >= t1 for s in list(loop.streams)):
        time.sleep(0.05)
    loop.stop()
    time.sleep(0.3)   # tokens already on the wire
    streams = list(loop.streams)
    with open(os.path.join(work, "stamps.json"), "w") as f:
        json.dump({"window": [t0, t1], "streams": [
            {"due": s.due, "sent": s.sent, "done": s.done, "t": s.t,
             "prompt_len": len(s.req.tokens), "error": s.error}
            for s in streams]}, f, default=str)
    obs.update(window=(t0, t1), streams=streams, before=before, after=after,
               setup=setup, traffic=tr, config=cell.config)

    # ---- outside the window: the reference, the records, the trace
    chk = tr["check"]
    samples = [{"tokens": s.req.tokens, "generated": s.tokens}
               for s in check_samples(streams, (t0, t1), seed, chk)]
    obs["checks"] = call("reference_check", samples, int(chk["check_len"]),
                         int(chk["decode_tokens"])) if samples else []
    if trace:
        obs["trace"] = call("trace_reduce")
        # records flow on the metrics cadence: the newest lag by a beat
        # of every request that ended inside the window: one lives
        # longer than the window, so few also began in it
        records = {}
        deadline = time.monotonic() + 10.0
        for s in (s for s in streams if s.done is not None
                  and s.error is None and t0 <= s.done <= t1):
            while s.request_id and s.request_id not in records:
                rec = state_api.get_serve_request(s.request_id)
                if rec is not None:
                    records[s.request_id] = rec
                elif time.monotonic() > deadline:
                    break
                else:
                    time.sleep(0.1)
        obs["records"] = records
    obs["memory_peak_bytes"] = call("bench_report")["memory_peak_bytes"]
    serve.shutdown()
    rt.shutdown()
    return obs
