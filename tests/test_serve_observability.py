"""Serve request-path observability (ISSUE 16): per-request latency
waterfalls, TTFT/TPOT accounting, engine phase metrics, and the GCS
serve-state store behind `rayt list requests` / `rayt serve status`.

Covers: the GcsServeManager contract (coalescing in either arrival
order, per-app oldest-first eviction, tail-biased sampling, purge on
app delete, engine counter deltas incl. replica restart), the E2E
acceptance path (one HTTP request -> a coalesced GCS record whose proxy
stages tile the end-to-end time, CLI waterfall rendering, stitched otel
trace spanning proxy + replica pids), the streaming-accounting fixes
(client-facing TTFT at the first SSE chunk, ``stream_aborted`` on
client disconnect), `/-/admission` endpoint coverage, and gRPC-proxy
parity (same record shape + request id as HTTP).
"""

import json
import time
import urllib.request

import pytest

import ray_tpu as rt
from ray_tpu import serve, state_api


@pytest.fixture
def serve_cluster(local_cluster):
    yield local_cluster
    serve.shutdown()


# --------------------------------------------- GcsServeManager contract
def _mgr(**kw):
    from ray_tpu.core.gcs_serve_manager import GcsServeManager

    return GcsServeManager(**kw)


def _proxy_final(rid, app="app", e2e=0.010, outcome="ok", **extra):
    rec = {"kind": "request", "side": "proxy", "final": True,
           "request_id": rid, "app": app, "proto": "http",
           "outcome": outcome, "e2e_s": e2e,
           "stages": {"admission_s": 0.2 * e2e, "router_s": 0.0,
                      "dispatch_s": 0.8 * e2e},
           "pid_proxy": 101, "start_ts": 1.0, "ts": 1.0}
    rec.update(extra)
    return rec


def _replica_partial(rid, app="app", **extra):
    rec = {"kind": "request", "side": "replica", "request_id": rid,
           "app": app, "deployment": "Dep", "pid_replica": 202,
           "ts": 1.0,
           "replica_stages": {"queue_s": 0.001, "service_s": 0.008}}
    rec.update(extra)
    return rec


def test_manager_coalesces_either_arrival_order():
    m = _mgr()
    # proxy final first, replica partial late
    m.ingest(_proxy_final("r1"))
    m.ingest(_replica_partial("r1"))
    # replica partial first, proxy final closes it out
    m.ingest([_replica_partial("r2"), _proxy_final("r2")])
    for rid in ("r1", "r2"):
        rec = m.get(rid)
        assert rec is not None, rid
        assert rec["stages"]["admission_s"] is not None
        assert rec["replica_stages"]["service_s"] == 0.008
        assert rec["pid_proxy"] == 101 and rec["pid_replica"] == 202
    assert m.num_requests() == 2
    # an unfinalized partial stays pending, not listed
    m.ingest(_replica_partial("r3"))
    assert m.get("r3") is None and m.num_requests() == 2


def test_manager_get_by_hex_prefix():
    m = _mgr()
    m.ingest(_proxy_final("deadbeef" * 4))
    assert m.get("deadbeef")["request_id"] == "deadbeef" * 4


def test_manager_per_app_eviction_oldest_first():
    m = _mgr(max_requests=4)
    for i in range(5):
        m.ingest(_proxy_final(f"big{i}", app="big"))
    m.ingest(_proxy_final("small0", app="small"))
    # the flood app gave up its OLDEST records; the small app's record
    # survives even though it arrived last
    assert m.get("small0") is not None
    assert m.get("big0") is None and m.get("big4") is not None
    assert m.dropped_counts()["big"] == 2
    assert "small" not in m.dropped_counts()
    out = m.list(app="big")
    assert out["total"] == 3 and out["dropped"]["big"] == 2


def test_manager_tail_biased_sampling():
    m = _mgr(sample=0.0)
    # warmup window (<20 per app) keeps everything; spread the e2e
    # values so the p90 threshold sits above the fast path
    for i in range(20):
        m.ingest(_proxy_final(f"w{i}", e2e=0.001 * (i + 1)))
    assert m.num_requests() == 20
    # post-warmup happy-path records below the p90 are sampled OUT...
    m.ingest(_proxy_final("fast", e2e=0.005))
    assert m.get("fast") is None
    assert m.sampled_counts()["app"] == 1
    # ...but errors/sheds and the slowest decile are ALWAYS retained
    m.ingest(_proxy_final("bad", e2e=0.010, outcome="error"))
    m.ingest(_proxy_final("shed1", e2e=0.001, outcome="shed"))
    m.ingest(_proxy_final("abort", e2e=0.002, outcome="stream_aborted"))
    m.ingest(_proxy_final("slow", e2e=5.0))
    for rid in ("bad", "shed1", "abort", "slow"):
        assert m.get(rid) is not None, rid
    # a late replica partial for a sampled-out id must not resurrect it
    m.ingest(_replica_partial("fast"))
    assert m.get("fast") is None


def test_manager_purge_on_app_delete():
    m = _mgr()
    m.ingest(_proxy_final("a1", app="gone"))
    m.ingest(_replica_partial("p1", app="gone"))       # pending partial
    m.ingest(_proxy_final("k1", app="kept"))
    m.ingest({"kind": "app_deleted", "app": "gone"})
    assert m.get("a1") is None and m.get("k1") is not None
    assert m.num_requests() == 1
    assert "gone" not in m.dropped_counts()


def test_manager_derives_data_plane_families():
    """Tentpole (PR 19): every finalized record feeds the data-plane
    counters — prefix-cache routing outcome, per-proxy admission
    attribution (sheds never held a slot), and KV handoff bytes tagged
    by edge kind, counted once per coalesced record."""
    m = _mgr()
    m.ingest(_proxy_final("d1", proxy="http-1", prefix_cache="hit"))
    m.ingest(_replica_partial(
        "d1", engine={"kv_handoff_bytes": 4096, "kv_handoff_edge": "shm"}))
    m.ingest(_proxy_final("d2", proxy="http-0", prefix_cache="spill"))
    m.ingest(_proxy_final("d3", proxy="http-0", outcome="shed"))
    recs = m.drain_metric_records()
    prefix = [r for r in recs
              if r["name"] == "rayt_serve_prefix_cache_total"]
    assert sorted(r["tags"]["outcome"] for r in prefix) == \
        ["hit", "spill"]
    assert all(r["tags"]["app"] == "app" for r in prefix)
    admitted = [r for r in recs
                if r["name"] == "rayt_serve_proxy_admitted_total"]
    # the shed record ("d3") must NOT count as admitted
    assert sorted(r["tags"]["proxy"] for r in admitted) == \
        ["http-0", "http-1"]
    kv = [r for r in recs
          if r["name"] == "rayt_serve_kv_handoff_bytes_total"]
    assert len(kv) == 1 and kv[0]["value"] == 4096.0
    assert kv[0]["tags"] == {"edge_kind": "shm"}


def test_manager_coalesces_disagg_pools_into_one_waterfall():
    """Satellite: a disaggregated request's two replica partials
    (prefill pool: prefill phases; decode pool: decode phases) coalesce
    into ONE engine waterfall under the proxy-minted request id,
    whichever flush cadence lands first — neither half's structural
    gaps may clobber the other's real values."""
    prefill = _replica_partial(
        "w1", deployment="PrefillWorker",
        engine={"queue_s": 0.001, "prefill_s": 0.02, "prefill_chunks": 2,
                "prefix_cache": "hit", "prefix_hit_tokens": 16,
                "kv_handoff_bytes": 4096, "kv_handoff_edge": "shm"})
    decode = _replica_partial(
        "w1", deployment="DecodeLlamaService",
        engine={"queue_s": 0.002, "tokens": 6, "decode_steps": 6,
                "ttft_s": 0.01, "decode_s": 0.05, "tpot_s": 0.01,
                "occupancy_mean": 0.5})
    for order in ((prefill, decode), (decode, prefill)):
        m = _mgr()
        for part in order:
            m.ingest(dict(part, engine=dict(part["engine"])))
        m.ingest(_proxy_final("w1", proxy="http-0"))
        eng = m.get("w1")["engine"]
        assert eng["prefill_s"] == 0.02 and eng["prefill_chunks"] == 2
        assert eng["decode_steps"] == 6 and eng["tokens"] == 6
        assert eng["prefix_cache"] == "hit"
        assert eng["kv_handoff_bytes"] == 4096
        assert eng["kv_handoff_edge"] == "shm"
    # the pending partial went too: a late final can't finalize it with
    # the deleted app's stale fields... (it just starts a fresh record)
    out = m.list(app="gone")
    assert out["total"] == 0


def test_manager_engine_counter_deltas_and_restart():
    m = _mgr()

    def report(prefills, chunks, steps, occ=0.5):
        return {"kind": "engine", "app": "a", "deployment": "D",
                "replica": "pid-7", "prefills": prefills,
                "prefill_chunks": chunks, "decode_steps": steps,
                "occupancy": occ, "ts": 1.0}

    def drain_counters():
        out = {}
        for r in m.drain_metric_records():
            if r["kind"] == "counter":
                out[r["name"]] = out.get(r["name"], 0) + r["value"]
        return out

    m.ingest(report(10, 40, 100))
    c = drain_counters()
    assert c["rayt_serve_engine_prefills_total"] == 10
    assert c["rayt_serve_engine_prefill_chunks_total"] == 40
    assert c["rayt_serve_engine_decode_steps_total"] == 100
    # cumulative report -> delta emission
    m.ingest(report(15, 55, 160))
    c = drain_counters()
    assert c["rayt_serve_engine_prefills_total"] == 5
    assert c["rayt_serve_engine_decode_steps_total"] == 60
    # a counter going BACKWARD means the engine restarted: the new
    # cumulative value IS the delta (no negative emission)
    m.ingest(report(3, 8, 20))
    c = drain_counters()
    assert c["rayt_serve_engine_prefills_total"] == 3
    assert c["rayt_serve_engine_decode_steps_total"] == 20


def test_engine_loop_counters_become_operator_series():
    """The engine loop's own account (LLMEngine.host_time: stalled hops,
    the wait for work, prompt tokens) rides the same report: counts as
    they are, microseconds as seconds, differences of consecutive
    reports, and no record where nothing was counted."""
    from ray_tpu.util.builtin_metrics import serve_engine_metric_records

    recs = serve_engine_metric_records(
        "a", "D", "pid-7", loop_stalls=2, loop_stall_us=5_900_000,
        host_us_wait=250_000, prompt_tokens=7000, ts=1.0)
    assert {r["name"]: (r["kind"], r["value"], r["tags"]) for r in recs} == {
        name: ("counter", value, {"app": "a", "deployment": "D"})
        for name, value in (
            ("rayt_serve_engine_stalls_total", 2.0),
            ("rayt_serve_engine_stall_seconds_total", 5.9),
            ("rayt_serve_engine_wait_seconds_total", 0.25),
            ("rayt_serve_engine_prompt_tokens_total", 7000.0))}
    assert serve_engine_metric_records("a", "D", "pid-7") == []

    m = _mgr()
    report = {"kind": "engine", "app": "a", "deployment": "D",
              "replica": "pid-7", "prefills": 1, "prefill_chunks": 1,
              "decode_steps": 1, "ts": 1.0}
    m.ingest(dict(report, loop_stalls=1, loop_stall_us=2_950_000,
                  host_us_wait=100_000, prompt_tokens=3000))
    m.drain_metric_records()
    m.ingest(dict(report, loop_stalls=1, loop_stall_us=2_950_000,
                  host_us_wait=400_000, prompt_tokens=6500))
    got = {r["name"]: r["value"] for r in m.drain_metric_records()}
    assert got == {"rayt_serve_engine_wait_seconds_total":
                   pytest.approx(0.3),
                   "rayt_serve_engine_prompt_tokens_total": 3500.0}


def test_manager_derives_histograms_before_sampling():
    """Prometheus series must be unskewed by retention: a sampled-out
    record still contributes its ttft/tpot/queue-wait observations."""
    m = _mgr(sample=0.0)
    for i in range(20):
        m.ingest(_proxy_final(f"w{i}", e2e=0.001 * (i + 1)))
    m.drain_metric_records()
    m.ingest(_proxy_final("fast", e2e=0.005, ttft_s=0.004, tpot_s=0.001))
    assert m.get("fast") is None  # sampled out of the store...
    names = [r["name"] for r in m.drain_metric_records()]
    assert "rayt_serve_ttft_s" in names  # ...but the series saw it
    assert "rayt_serve_tpot_s" in names
    assert "rayt_serve_queue_wait_s" in names


# ---------------------------------------------------- E2E: HTTP -> GCS
def _wait_record(rid, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rec = state_api.get_serve_request(rid)
        if rec is not None:
            return rec
        time.sleep(0.25)
    raise AssertionError(f"no GCS record for request {rid}")


def test_unary_request_waterfall_record(serve_cluster):
    """Acceptance: one HTTP request yields a coalesced GCS record whose
    proxy stages sum to within 10% of the recorded end-to-end time,
    carrying both the proxy and replica sides."""
    port = serve.start(http_port=0)

    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return {"echo": payload}

    serve.run(Echo.bind(), name="wf")
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/wf", data=json.dumps({"x": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        rid = resp.headers.get("X-Rayt-Request-Id")
        resp.read()
    assert rid and len(rid) == 32

    rec = _wait_record(rid)
    assert rec["app"] == "wf" and rec["outcome"] == "ok"
    assert rec["proto"] == "http"
    stages = rec["stages"]
    ssum = sum(v for v in stages.values() if v is not None)
    assert abs(ssum - rec["e2e_s"]) <= 0.1 * rec["e2e_s"] + 1e-4, (
        stages, rec["e2e_s"])
    # replica partial coalesced in: queue/service nest inside dispatch
    assert rec["replica_stages"]["service_s"] is not None
    assert rec["pid_proxy"] != rec["pid_replica"]

    # the per-request latency waterfall renders through the CLI path
    out = state_api.list_serve_requests(slow=True, detail=True)
    assert any(r["request_id"] == rid for r in out["requests"])


def test_cli_renders_request_waterfall(serve_cluster, capsys):
    """`rayt list requests --slow` + `rayt serve status` stage table."""
    from ray_tpu.scripts.cli import _print_requests, _print_serve_waterfall

    port = serve.start(http_port=0)

    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return "ok"

    serve.run(Echo.bind(), name="cliapp")
    for _ in range(3):
        req = urllib.request.Request(f"http://127.0.0.1:{port}/cliapp",
                                     data=b"{}")
        urllib.request.urlopen(req, timeout=30).read()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        out = state_api.list_serve_requests(slow=True, detail=True)
        if out["total"] >= 3:
            break
        time.sleep(0.25)
    assert out["total"] >= 3
    _print_requests(out)
    text = capsys.readouterr().out
    assert "admission" in text and "dispatch" in text, text
    assert "replica[" in text, text  # the replica nest rendered
    assert "proxy=" in text, text   # admitting fleet member rendered
    assert "matched" in text

    _print_serve_waterfall(state_api.summarize_serve_requests())
    text = capsys.readouterr().out
    assert "cliapp" in text and "admission_s" in text, text
    assert "p99" in text and "e2e" in text


def test_streaming_ttft_tpot_and_latency_series(serve_cluster):
    """Satellite: streaming requests get honest latency accounting —
    TTFT stamped at the FIRST SSE chunk, TPOT from inter-chunk gaps,
    totals at stream END, and the stream lands in the
    rayt_serve_request_latency_s series (deployment=_proxy_stream)."""
    port = serve.start(http_port=0)

    @serve.deployment
    class Chat:
        async def __call__(self, payload):
            import asyncio

            for i in range(6):
                if i:
                    await asyncio.sleep(0.01)
                yield {"tok": i}

    serve.run(Chat.bind(), name="sse")
    req = urllib.request.Request(f"http://127.0.0.1:{port}/sse?stream=1",
                                 data=b"{}")
    with urllib.request.urlopen(req, timeout=30) as resp:
        rid = resp.headers.get("X-Rayt-Request-Id")
        body = resp.read().decode()
    assert rid and body.count("data:") == 6

    rec = _wait_record(rid)
    assert rec["outcome"] == "ok" and rec["chunks"] == 6
    # TTFT is the first chunk, NOT stream end: with 5 paced inter-chunk
    # gaps of 10ms the old end-of-stream accounting would put ttft
    # within a hair of e2e; the fixed one leaves the pacing out
    assert rec["ttft_s"] is not None and rec["tpot_s"] is not None
    assert rec["e2e_s"] - rec["ttft_s"] >= 0.03, rec
    assert rec["stages"]["stream_s"] >= 0.03, rec
    assert rec["tpot_s"] >= 0.005, rec

    # the histogram series saw the stream (deployment=_proxy_stream)
    from ray_tpu.core.object_ref import get_core_worker

    cw = get_core_worker()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        snap = cw.io.run(cw.gcs.conn.call("metrics_snapshot"))
        rows = [m for m in snap
                if m.get("name") == "rayt_serve_request_latency_s"
                and m.get("tags", {}).get("deployment") == "_proxy_stream"]
        if rows and rows[0].get("count", 0) >= 1:
            break
        time.sleep(0.25)
    assert rows, "stream never reached rayt_serve_request_latency_s"


def test_stream_abort_records_aborted_outcome(serve_cluster):
    """Satellite: a client that disconnects mid-stream produces a
    ``stream_aborted`` record (always retained) instead of a phantom
    'ok' with a truncated latency."""
    import http.client

    port = serve.start(http_port=0)

    @serve.deployment
    class Slow:
        async def __call__(self, payload):
            import asyncio

            for i in range(50):
                await asyncio.sleep(0.05)
                yield {"tok": i}

    serve.run(Slow.bind(), name="abort")
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/abort?stream=1", body=b"{}")
    resp = conn.getresponse()
    rid = resp.getheader("X-Rayt-Request-Id")
    assert rid
    resp.read(16)   # take the first chunk...
    conn.sock.close()  # ...then hang up mid-stream
    conn.close()

    deadline = time.monotonic() + 20
    rec = None
    while time.monotonic() < deadline:
        rec = state_api.get_serve_request(rid)
        if rec is not None and rec.get("outcome"):
            break
        time.sleep(0.5)
    assert rec is not None, "no record for aborted stream"
    assert rec["outcome"] == "stream_aborted", rec
    assert rec["chunks"] >= 1 and rec["ttft_s"] is not None


def test_admission_endpoint_snapshot(serve_cluster):
    """Satellite: /-/admission exposes the live admission-window state
    (admitted/window/totals per app) the waterfall's admission stage is
    measured against."""
    port = serve.start(http_port=0)

    @serve.deployment(max_ongoing_requests=2)
    class Echo:
        def __call__(self, payload):
            return "ok"

    serve.run(Echo.bind(), name="adm")
    for _ in range(3):
        req = urllib.request.Request(f"http://127.0.0.1:{port}/adm",
                                     data=b"{}")
        urllib.request.urlopen(req, timeout=30).read()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/-/admission", timeout=30) as resp:
        snap = json.loads(resp.read())
    assert "adm" in snap, snap
    e = snap["adm"]
    assert e["admitted_total"] >= 3 and e["window"] >= 1, e
    assert e["admitted"] == 0  # nothing in flight now
    assert e["shed_total"] == 0
    # sharded-ingress fleet keys: which member answered, how many are
    # live, and this member's share of the cluster window
    assert snap["proxy_id"] == "http-0", snap
    assert snap["live_proxies"] >= 1, snap
    assert e["window"] <= e["cluster_window"], e


def test_grpc_proxy_request_id_and_record_parity(serve_cluster):
    """Satellite: the gRPC ingress mints the same request id (surfaced
    as x-rayt-request-id initial metadata) and publishes records of the
    SAME shape as the HTTP proxy — one store, both protocols."""
    grpc = pytest.importorskip("grpc")

    gport = serve.start_grpc(grpc_port=0)
    hport = serve.start(http_port=0)

    @serve.deployment
    class Echo:
        def __call__(self, payload):
            if isinstance(payload, dict) and payload.get("n"):
                def gen():
                    for i in range(int(payload["n"])):
                        yield {"tok": i}
                return gen()
            return {"echo": payload}

    serve.run(Echo.bind(), name="gobs")
    chan = grpc.insecure_channel(f"127.0.0.1:{gport}")
    predict = chan.unary_unary(
        "/rayt.serve.Serve/Predict",
        request_serializer=lambda b: b, response_deserializer=lambda b: b)
    resp, call = predict.with_call(
        json.dumps({"app": "gobs", "payload": "hi"}).encode(), timeout=30)
    assert json.loads(resp) == {"echo": "hi"}
    md = {k: v for k, v in call.initial_metadata()}
    rid = md.get("x-rayt-request-id")
    assert rid and len(rid) == 32, md
    # the gRPC ingress names its fleet member like the HTTP proxy's
    # X-Rayt-Proxy-Id response header
    assert md.get("x-rayt-proxy-id") == "grpc-0", md

    # streaming leg too
    stream = chan.unary_stream(
        "/rayt.serve.Serve/PredictStream",
        request_serializer=lambda b: b, response_deserializer=lambda b: b)
    items = list(stream(
        json.dumps({"app": "gobs", "payload": {"n": 3}}).encode(),
        timeout=30))
    assert len(items) == 3
    chan.close()

    # HTTP sibling for the shape comparison
    req = urllib.request.Request(f"http://127.0.0.1:{hport}/gobs",
                                 data=json.dumps("hi").encode())
    with urllib.request.urlopen(req, timeout=30) as r:
        hrid = r.headers["X-Rayt-Request-Id"]
        assert r.headers["X-Rayt-Proxy-Id"] == "http-0"
        r.read()

    grec = _wait_record(rid)
    hrec = _wait_record(hrid)
    assert grec["proto"] == "grpc" and hrec["proto"] == "http"
    assert grec["outcome"] == "ok"
    # both records attribute the serving fleet member
    assert grec["proxy"] == "grpc-0" and hrec["proxy"] == "http-0"
    # same record shape: the gRPC record carries every key the HTTP one
    # does (both tiled by the shared _finish_record path)
    missing = set(hrec) - set(grec) - {"proto"}
    assert not missing, missing
    ssum = sum(v for v in grec["stages"].values() if v is not None)
    assert abs(ssum - grec["e2e_s"]) <= 0.1 * grec["e2e_s"] + 1e-4, grec
    # the streaming gRPC call recorded chunked output
    out = state_api.list_serve_requests(app="gobs", detail=True)
    assert any(r.get("chunks") == 3 and r["proto"] == "grpc"
               for r in out["requests"]), out["requests"]


def test_replica_stats_export_engine_counters(serve_cluster):
    """Satellite: replica.get_stats() exports the hosted engine's
    cumulative counters (duck-typed on the `engine` attribute — the
    same contract the throttled GCS engine reports use)."""
    @serve.deployment
    class Host:
        def __init__(self):
            class _Eng:
                batches = 7
                prefills = 3
                prefill_chunks = 5
                max_batch = 4
                _slots = [object(), None, None, None]
            self.engine = _Eng()

        def __call__(self, payload):
            return "ok"

    h = serve.run(Host.bind(), name="engstats")
    assert h.remote(1).result(timeout=30) == "ok"
    h._refresh(force=True)
    stats = rt.get(h._replicas[0].get_stats.remote(), timeout=30)
    eng = stats["engine"]
    assert eng["batches"] == 7 and eng["prefills"] == 3
    assert eng["prefill_chunks"] == 5
    assert eng["active_slots"] == 1 and eng["max_batch"] == 4


def test_multiplex_affinity_metric_and_model_id_in_record(serve_cluster):
    """Multiplexed requests stamp the model id into their record and
    bump the rayt_serve_affinity_total counter (hit/spill/cold)."""
    port = serve.start(http_port=0)

    @serve.deployment
    class Mux:
        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            return model_id

        async def __call__(self, payload):
            return await self.get_model(
                serve.get_multiplexed_model_id())

    serve.run(Mux.bind(), name="muxobs")

    def call(mid):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/muxobs", data=b"{}",
            headers={"serve_multiplexed_model_id": mid})
        with urllib.request.urlopen(req, timeout=30) as r:
            r.read()
            return r.headers["X-Rayt-Request-Id"]

    call("m1")            # cold
    rid = call("m1")      # hit
    rec = _wait_record(rid)
    assert rec["model_id"] == "m1"
    assert rec.get("affinity") in ("hit", "cold", "spill"), rec

    from ray_tpu.core.object_ref import get_core_worker

    cw = get_core_worker()
    deadline = time.monotonic() + 15
    rows = []
    while time.monotonic() < deadline:
        snap = cw.io.run(cw.gcs.conn.call("metrics_snapshot"))
        rows = [m for m in snap
                if m.get("name") == "rayt_serve_affinity_total"]
        if sum(m.get("value", 0) for m in rows) >= 2:
            break
        time.sleep(0.25)
    results = {m["tags"].get("result") for m in rows}
    assert "hit" in results, rows


# ------------------------------------------- otel stitching (subprocess)
@pytest.mark.timeout(240)
def test_request_trace_stitched_across_pids(tmp_path):
    """Acceptance: one traced HTTP request produces ONE otel trace whose
    spans come from >=2 processes (proxy + replica) — the W3C carrier
    rides the handle envelope. Subprocess so RAYT_TRACING_DIR reaches
    every cluster process from boot."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import json, time, urllib.request
        import ray_tpu as rt
        from ray_tpu import serve

        rt.init(num_cpus=4)
        port = serve.start(http_port=0)

        @serve.deployment
        class Echo:
            def __call__(self, payload):
                return "ok"

        serve.run(Echo.bind(), name="traced")
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/traced", data=b"{}")
        with urllib.request.urlopen(req, timeout=30) as resp:
            rid = resp.headers["X-Rayt-Request-Id"]
            resp.read()
        time.sleep(2.5)  # span + record flush cadence
        serve.shutdown()
        rt.shutdown()
        print(json.dumps({"rid": rid}))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo"
    env["JAX_PLATFORMS"] = "cpu"
    env["RAYT_TRACING_DIR"] = str(tmp_path / "spans")
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, env=env,
                       timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    rid = json.loads(r.stdout.strip().splitlines()[-1])["rid"]

    from ray_tpu._internal import otel

    spans = otel.read_spans(str(tmp_path / "spans"))
    mine = [s for s in spans
            if s.get("attributes", {}).get("request_id") == rid]
    assert mine, "no spans tagged with the request id"
    traces = {}
    for s in mine:
        traces.setdefault(s["trace_id"], set()).add(s["pid"])
    # ONE trace, spanning at least the proxy and replica processes
    assert len(traces) == 1, traces
    assert len(next(iter(traces.values()))) >= 2, traces
    names = {s["name"] for s in mine}
    assert "serve.proxy.request" in names, names
    assert any("serve.replica" in n for n in names), names
