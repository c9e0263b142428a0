"""The serve cell of the hybrid (Mamba-2 + attention) family, from the
command's own process. benchmarks/serve_cell.py's run with the one
difference a file that may not be edited leaves no other way to make:
the deployment class it binds (BenchHybridService, which hands the
engine its config as data and checks against the family's own plain
reference). Closed loop only. The window's cut, the metrics and `info`
are serve_cell's own; `correct` adds the recurrent state's limit to
serve_cell's two.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random
import threading
import time

import numpy as np

from benchmarks import ssm_ops, traffic as traffic_mod
from benchmarks.client import ClosedLoop, Stream, post_stream
from benchmarks.manifest import Cell
from benchmarks import serve_cell
from benchmarks.serve_cell import (attempted_failed, info,  # noqa: F401
                                   start_cluster)
from benchmarks.traffic import Request
from benchmarks.yardsticks import Yardsticks

# what benchmarks/readers/model.py reads for the hybrid cell (its
# configuration names no helper): the whole step's operations. The
# state update's roofline is its own entry (readers/ssm.py)
YARDSTICKS = Yardsticks(
    flops_per_token=ssm_ops.flops_per_token,
    attn_scopes={"decode": ["attn"], "prefill": ["attn"]})


def correct(obs: dict, tol: dict) -> bool:
    """serve_cell's two limits, and the one this family's state brings:
    the recurrent state the checked steps left, against the reference
    scan's (hybrid_deployment.state_errors)."""
    return serve_cell.correct(obs, tol) and all(
        c["state_rel_rms"] <= tol["state_rel_rms"] for c in obs["checks"])


def compared(obs: dict, tol: dict) -> dict:
    return {**serve_cell.compared(obs, tol), **serve_cell.worst(
        obs["checks"], {"state_rel_rms": "state_rel_rms"}, tol)}


def run(cell: Cell, seed: int, seconds: float, trace: bool, work: str,
        t_process: float) -> dict:
    # a program from before the model existed cannot run this cell: say
    # so at once, before a cluster is up
    if importlib.util.find_spec("ray_tpu.models.granite_hybrid") is None:
        raise RuntimeError("this program has no ray_tpu.models."
                           "granite_hybrid: it cannot run " + cell.name)
    from ray_tpu import serve, state_api
    from ray_tpu.serve.deployment import deployment

    from benchmarks.hybrid_deployment import BenchHybridService

    tr = cell.traffic
    if tr["kind"] != "serve_closed":
        raise ValueError("hybrid_cell runs closed loops only")
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
    dep = deployment(BenchHybridService,
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
    loop = ClosedLoop(port, "llm", traffic_mod.closed_loop(tr, vocab, seed),
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
    done = [s for s in streams if s.done is not None and s.error is None
            and s.t and t0 <= s.t[0] and s.done <= t1
            and len(s.tokens) == s.req.max_new_tokens]
    random.Random(seed).shuffle(done)
    chk = tr["check"]
    samples = [{"tokens": s.req.tokens, "generated": s.tokens}
               for s in done[:int(chk["samples"])]]
    obs["checks"] = call("reference_check", samples, int(chk["check_len"]),
                         int(chk["decode_tokens"])) if samples else []
    if trace:
        obs["trace"] = call("trace_reduce")
        # records flow on the metrics cadence: the newest lag by a beat
        records = {}
        deadline = time.monotonic() + 10.0
        for s in done:
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
