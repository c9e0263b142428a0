"""A serve cell, from the command's own process: cluster, the
deployment, warm-up, the load, the window, the comparison with the
reference. Returns the observations the metric readers take their
numbers from. This process never touches jax: the replica holds the
chip.
"""

from __future__ import annotations

import json
import math
import os
import random
import threading
import time

import numpy as np

from benchmarks import attention_ops, traffic as traffic_mod
from benchmarks.client import ClosedLoop, OpenLoop, Stream, post_stream
from benchmarks.manifest import Cell
from benchmarks.traffic import Request
from benchmarks.yardsticks import Yardsticks

# what benchmarks/readers/model.py reads for the cells this driver runs
# (the `llama` family; their configurations name no helper): the whole
# step's operations, of a closed loop's cycle
YARDSTICKS = Yardsticks(
    flops_per_token=attention_ops.serve_flops_per_token,
    attn_scopes={"decode": ["attn"], "prefill": ["attn"]})


class NoAccelerator(RuntimeError):
    pass


def start_cluster(chips: int):
    import ray_tpu as rt

    rt.init()
    have = rt.cluster_resources().get("TPU", 0)
    if have < chips:
        raise NoAccelerator(f"the node advertises TPU={have:g}, this cell "
                            f"needs {chips}")
    return rt


def run(cell: Cell, seed: int, seconds: float, trace: bool, work: str,
        t_process: float) -> dict:
    from ray_tpu import serve, state_api
    from ray_tpu.serve.deployment import deployment

    from benchmarks.deployment import BenchLlamaService

    tr = cell.traffic
    vocab = int(cell.config["vocab_size"])
    setup = {"imports_s": time.perf_counter() - t_process}
    t = time.perf_counter()
    rt = start_cluster(cell.chips)
    setup["cluster_s"] = time.perf_counter() - t

    t = time.perf_counter()
    port = serve.start(request_timeout_s=900.0)
    # a cold start compiles for minutes inside the replica's constructor;
    # the default probe (5 s, twice) would kill it there
    dep = deployment(BenchLlamaService, max_ongoing_requests=64,
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
    if tr["kind"] == "serve_open":
        reqs = traffic_mod.open_loop(tr, vocab, seed, seconds)
        loop = OpenLoop(port, "llm", reqs)
        obs["offered"] = traffic_mod.shape_summary(
            [r for r in reqs if r.stratum == "window"],
            tr["engine"]["prompt_buckets"])
    else:
        loop = ClosedLoop(port, "llm", traffic_mod.closed_loop(
            tr, vocab, seed), int(tr["clients"]))
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
               setup=setup, traffic=tr)

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


# ------------------------------------------------------------------------
# What the end-to-end metrics and the result line take from the stamps.
# ------------------------------------------------------------------------
def window_gaps(obs: dict) -> np.ndarray:
    """Gaps between consecutive tokens of one stream, in seconds, whose
    later token arrived inside the window. The first token is no gap."""
    t0, t1 = obs["window"]
    gaps = []
    for s in obs["streams"]:
        t = s.t
        gaps.extend(b - a for a, b in zip(t, t[1:]) if t0 <= b <= t1)
    return np.asarray(gaps)


def itl_p90_ms(obs: dict) -> float:
    return float(np.percentile(window_gaps(obs), 90) * 1e3)


def serve_tokens_per_s(obs: dict) -> float:
    """Tokens the replica got through in the window, over the window.
    A generated token is counted when it arrives. Prompt tokens answered
    are a counter the client can read only at first tokens, where it
    steps by a whole prompt (3,500 tokens are 4% of a 30 s window's
    count, so the rate would step with where an edge falls between two
    readings). Its value at each edge of the window is therefore
    interpolated linearly between the readings on either side, which
    counts of the prompt answered just after an edge the share of the
    time since the reading before it that lies on each side. No reading
    before the window's start, or none after its end by the time the
    load stops (`drain_first_tokens_s` in the traffic file): no result."""
    t0, t1 = obs["window"]
    firsts = sorted((s.t[0], len(s.req.tokens))
                    for s in obs["streams"] if s.t)
    times = [a for a, _ in firsts]
    if not times or times[0] > t0 or times[-1] < t1:
        raise RuntimeError("no first token on each side of the window: "
                           "the prompt counter cannot be read at its edges")
    cum = np.cumsum([n for _, n in firsts])

    def prompts_answered_by(t):
        k = int(np.searchsorted(times, t, side="right"))   # readings <= t
        if k == len(times):
            return float(cum[-1])
        step = cum[k] - cum[k - 1]
        return float(cum[k - 1] + step * (t - times[k - 1])
                     / (times[k] - times[k - 1]))

    generated = sum(1 for s in obs["streams"] for x in s.t if t0 <= x <= t1)
    return (prompts_answered_by(t1) - prompts_answered_by(t0)
            + generated) / (t1 - t0)


def judged_ttft_s(obs: dict) -> list:
    """Open loop: the time to first token, from when it was due, of each
    request due inside the window; inf for one that erred or had no
    first token within ttft_limit_s of being due (one still inside its
    limit when the run stopped is not judged)."""
    t0, t1 = obs["window"]
    limit = float(obs["traffic"]["ttft_limit_s"])
    stop = max((s.t[-1] for s in obs["streams"] if s.t), default=t1)
    out = []
    for s in obs["streams"]:
        if not t0 <= s.due <= t1:
            continue
        if s.error is not None:
            out.append(np.inf)
        elif s.t:
            ttft = s.t[0] - s.due
            out.append(ttft if ttft <= limit else np.inf)
        elif stop - s.due > limit:
            out.append(np.inf)
    return out


def attempted_failed(obs: dict) -> tuple:
    """Open loop: requests due inside the window; failed if they erred
    or missed ttft_limit_s (`judged_ttft_s`). Closed loop: requests sent
    inside the window; failed if they erred."""
    if obs["traffic"]["kind"] == "serve_open":
        ttft = judged_ttft_s(obs)
        return len(ttft), ttft.count(np.inf)
    t0, t1 = obs["window"]
    sent = [s for s in obs["streams"]
            if s.sent is not None and t0 <= s.sent <= t1]
    return len(sent), sum(1 for s in sent if s.error is not None)


def correct(obs: dict, tol: dict) -> bool:
    checks = obs["checks"]
    return bool(checks) and all(
        c["finite"] and c["logits_rel_rms"] <= tol["logits_rel_rms"]
        and c["token_max_margin"] <= tol["token_margin_logits"]
        for c in checks)


def worst(checks: list, names: dict, tol: dict) -> dict:
    """{limit's name: [the largest reading over the checks, the limit]}
    for `names` {limit's name: the reading's key in a check}. For
    printing only (run.py: last on the result line and on standard
    error); `correct` decides check by check. A reading that is no
    finite number in any check, which `correct` fails, reads None."""
    out = {}
    for name, key in names.items() if checks else ():
        vals = [float(c[key]) for c in checks]
        out[name] = [max(vals) if all(map(math.isfinite, vals)) else None,
                     tol[name]]
    return out


def compared(obs: dict, tol: dict) -> dict:
    return worst(obs["checks"], {"logits_rel_rms": "logits_rel_rms",
                                 "token_margin_logits": "token_max_margin"},
                 tol)


def stats_in_window(obs: dict) -> dict:
    """What `LLMEngine.stats()` counted between the window's two ends
    (`bench_report` at both): every counter that is a whole number at
    both, as its difference, those of the process's `programs` log
    under "programs" (by site: the keys the window added to or touched);
    and under "at_end" what is a level and no count (`cache_bytes`,
    `active_slots`, the prefix store's depth, `tp`) and the process's
    `startup` phases."""
    before, after = (obs[end].get("stats") or {}
                     for end in ("before", "after"))
    levels = ("active_slots", "tp", "prefix_entries",
              "prefix_cache_entries")

    def counted(a: dict, b: dict) -> dict:
        return {k: b[k] - a.get(k, 0) for k in b
                if type(b[k]) is int and type(a.get(k, 0)) is int}

    out = {k: v for k, v in counted(before, after).items()
           if k not in levels}
    out["at_end"] = {k: after[k] for k in levels + ("cache_bytes", "startup")
                     if k in after}
    progs = [s.get("programs") or {} for s in (before, after)]
    out["programs"] = counted(*progs)
    was, now = (p.get("by_program") or {} for p in progs)
    by_site = {site: counted(was.get(site, {}), tot)
               for site, tot in now.items()}
    out["programs"]["by_program"] = {site: c for site, c in by_site.items()
                                     if any(c.values())}
    return out


def info(obs: dict) -> dict:
    """What the result line cannot hold: set-up breakdown, offered and
    achieved rate, the gap histogram by mode, the checks, and what the
    engine counted inside the window (`stats_in_window`)."""
    t0, t1 = obs["window"]
    tr = obs["traffic"]
    seconds = t1 - t0
    gaps = window_gaps(obs) * 1e3
    out = {"setup": obs["setup"], "device": obs["device"],
           "checks": obs["checks"], "programs": {
               k: [obs["before"][k], obs["after"][k]]
               for k in ("programs", "step_programs")},
           "stats": stats_in_window(obs)}
    in_win = [s for s in obs["streams"] if t0 <= s.due <= t1]
    firsts = [s for s in obs["streams"] if s.t and t0 <= s.t[0] <= t1]
    out["requests_due_in_window"] = len(in_win)
    out["first_tokens_in_window"] = len(firsts)
    out["achieved_first_tokens_per_s"] = len(firsts) / seconds
    out["tokens_streamed_in_window"] = int(len(gaps))
    if tr["kind"] == "serve_open":
        out["offered_rate_per_s"] = tr["rate_per_s"]
        out["offered"] = obs["offered"]
        in_flight = lambda at: sum(
            1 for s in obs["streams"] if s.sent is not None
            and s.sent <= at and (s.done is None or s.done > at))
        out["in_flight_at_window_start"] = in_flight(t0)
        out["in_flight_at_window_end"] = in_flight(t1)
        late = [s.sent - s.due for s in obs["streams"] if s.sent]
        out["lateness_max_ms"] = max(late) * 1e3 if late else None
    if len(gaps):
        bare = float(np.median(gaps))
        hist, lo = {}, 0.0
        for name, edge in tr.get("gap_modes_ms", []):
            hist[name] = int(((gaps > lo) & (gaps <= bare + edge)).sum())
            lo = bare + edge
        hist["beyond"] = int((gaps > lo).sum())
        out["gap_ms"] = {"median": bare, "p90": float(np.percentile(
            gaps, 90)), "p99": float(np.percentile(gaps, 99)),
            "max": float(gaps.max()), "by_mode": hist}
        if len(hist) > 1:   # the share of gaps that are not bare steps
            out["gap_ms"]["beyond_first_mode_share"] = 100.0 * (
                1.0 - next(iter(hist.values())) / len(gaps))
    for s in obs["streams"]:   # one request's durations, to check by hand
        eng = ((obs.get("records") or {}).get(s.request_id) or {}
               ).get("engine") or {}
        if s.t and eng.get("ttft_s") is not None:
            out["proxy_overhead_sample"] = {
                "request_id": s.request_id,
                "client_ttft_from_send_s": s.t[0] - s.sent,
                "engine_ttft_s": eng["ttft_s"],
                "engine_queue_s": eng.get("queue_s"),
                "prompt_len": len(s.req.tokens)}
            break
    ttft = [s.t[0] - s.due for s in firsts]
    if ttft:
        out["ttft_s"] = {"p50": float(np.median(ttft)),
                         "max": float(max(ttft))}
    if tr["kind"] == "serve_open":   # every request due in the window
        out["ttft_due_s"] = [round(x, 5) if x < np.inf else None
                             for x in sorted(judged_ttft_s(obs))]
    return out
