"""Microbenchmark regression gate (ref analog: release/microbenchmark/
nightly runs of python/ray/_private/ray_perf.py:93).

Tier-1 holds what a loaded, shared host can still tell: every benchmark
ran, and the fast paths keep their order over the slow ones. The absolute
floors (`FLOORS`, ~2-3x below what an idle box of this class reads) are
marked slow: they catch a reintroduced poll loop or a lease-per-task
path, but only on a box that runs nothing else.
"""

from __future__ import annotations

import pytest

import ray_tpu as rt
from ray_tpu._internal.perf import run_microbenchmarks


@pytest.fixture(scope="module")
def ray_cluster():
    ctx = rt.init(num_cpus=8)
    yield ctx
    rt.shutdown()

# ~2-3x below the rates an idle box of this class reads (1-core sandbox):
# tight enough to catch a real regression (a reintroduced poll loop, a
# lease-per-task path), loose enough for an idle box's own noise.
FLOORS = {
    # control-plane fastpath floors (function-table + batched leases +
    # direct-channel pipelining): an idle box reads ~2200-4000 for the
    # task/sync-actor rates — a regression to per-submit cloudpickle,
    # a lease RPC per task, or a loop round-trip per completion lands
    # back at well under 1100/s and trips these by a wide margin
    "tasks_per_second": 1100.0,
    # ~2.5x below an idle reading of 3417 (a reintroduced
    # lease-RPC-per-task path lands ~700)
    "tasks_per_second_burst": 1300.0,
    "actor_calls_sync_per_second": 1500.0,
    "actor_calls_async_per_second": 1500.0,
    "async_actor_calls_per_second": 1500.0,
    "put_small_per_second": 10000.0,
    # zero-copy object plane (idle ~8.8 GB/s put+get, ~1000 GB/s
    # repeated get): floors sit far above the pre-zero-copy 0.45 GB/s
    # copy-tax plateau, so a reintroduced bytes() copy on the get or
    # frame path trips the gate even on a noisy shared box
    "put_get_gigabytes_per_second": 1.0,
    "get_gigabytes_per_second": 25.0,
    # per-call fallback executor, ~2.5x below an idle 689.9
    "dag_percall_ticks_per_second": 275.0,
    # compiled-DAG execution plane (idle ~3600 ticks/s, ~2.0 GB/s
    # at 1 MiB payloads, ~11000 DCN ticks/s): a reintroduced
    # pickle+join+bytes() copy on the tick path lands back at ~750
    # ticks/s and ~0.5 GB/s through the DAG; a per-item RPC round-trip
    # on the DCN channel lands at ~2000/s — all trip these floors wide
    "dag_channel_ticks_per_second": 1200.0,
    "dag_channel_gigabytes_per_second": 0.7,
    "dag_dcn_ticks_per_second": 3000.0,
    # device edges (idle ~77000 same-client ticks/s — the jax.Array
    # OBJECT handoff, no serialize on the hot path — and ~1.7 GB/s raw
    # shard bytes through the shm-backed transport framing incl. the
    # device_put rebuild): a reintroduced serialize/deserialize round
    # trip on the same-client path lands back at ~3000/s (the shm
    # ring's tick rate) and trips the floor by an order of magnitude
    "dag_device_ticks_per_second": 25000.0,
    "dag_device_gigabytes_per_second": 0.6,
}


# single-thread pure-Python spin rate of the box the floors were sized
# on (~27M loop-iterations/s). The floor gate only judges the substrate
# when the box itself is delivering at least a reasonable fraction of
# that — a shared host that is externally loaded to a fraction of its
# speed (observed: 5x degradations lasting minutes) turns any static
# floor into noise.
_NOMINAL_SPIN = 27e6


def _spin_rate() -> float:
    import time

    n = 1_000_000
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        x = 0
        for i in range(n):
            x += i
        best = max(best, n / (time.perf_counter() - t0))
    return best


def _rates(duration: float) -> dict:
    return {r["benchmark"]: r["rate_per_s"]
            for r in run_microbenchmarks(duration=duration)}


@pytest.mark.timeout(180)
def test_microbenchmark_floors(ray_cluster):
    """What holds on a host that five other test processes share: every
    benchmark the floors name ran to a rate, and each fast path keeps
    its order over the path it replaced. The margins are host facts, not
    code facts: channel over per-call read ~7x on the 1-core box the
    floors were sized on, and reads 2.5-2.8x on an idle 8-core host and
    2.3-3.1x with six copies of this file running at once (the spinning
    channel path loses a third of its rate under load, the RPC-bound
    per-call path none); device edge over shm ring reads 55-80x in both.
    A fall back to the slower path reads 1.0x, so the bounds sit between
    that and the loaded readings."""
    rows = _rates(0.5)
    dead = [name for name in FLOORS if not rows.get(name, 0.0) > 0.0]
    assert not dead, f"no rate for {dead}; all rates: {rows}"
    ratio = rows["dag_channel_ticks_per_second"] / \
        rows["dag_percall_ticks_per_second"]
    assert ratio >= 1.5, f"channel DAG only {ratio:.1f}x per-call path"
    # ISSUE 12 acceptance: a same-client device edge beats the shm ring
    # on ticks/s for jax.Array payloads — no serialize/deserialize round
    # trip on the hot path
    dev_ratio = rows["dag_device_ticks_per_second"] / \
        rows["dag_channel_ticks_per_second"]
    assert dev_ratio >= 2.0, \
        f"device edge only {dev_ratio:.1f}x the shm ring tick rate"


@pytest.mark.slow
def test_microbenchmark_absolute_floors(ray_cluster):
    """The gate for an idle box: absolute host rates."""
    rows = _rates(0.5)

    def under_floor():
        return {name: (rows.get(name), floor)
                for name, floor in FLOORS.items()
                if rows.get(name, 0.0) < floor}

    failures = under_floor()
    if failures:
        # one steadier re-measure before judging: a 0.5s window can eat
        # a transient stall (worker boot, GC) worth 2-3x; a real
        # regression fails both passes
        rows = _rates(1.0)
        failures = under_floor()
    if failures and _spin_rate() < 0.4 * _NOMINAL_SPIN:
        pytest.skip(
            "host degraded (external load): pure-Python spin rate "
            f"{_spin_rate() / 1e6:.1f}M ops/s < 40% of nominal — "
            f"floor check not meaningful (measured: {rows})")
    assert not failures, (
        f"microbenchmark regression: rate < floor for {failures}; "
        f"all rates: {rows}")


def test_task_event_recording_overhead():
    """Instrumentation-overhead gate: lifecycle event recording rides
    the submit/execute hot path (~4 transitions per task: PENDING_ARGS,
    SCHEDULED, DISPATCHED at the driver; RUNNING/terminal at the
    worker), so its per-record cost must stay in the microsecond range
    and the disabled path must be a near-free attribute check."""
    import time

    from ray_tpu._internal.tracing import TaskEventBuffer

    def per_record_cost(enabled: bool) -> float:
        buf = TaskEventBuffer("w" * 40, "n" * 40, enabled=enabled)
        n = 20_000
        best = float("inf")
        for _ in range(3):  # best-of-3 to shed CI scheduling noise
            t0 = time.perf_counter()
            for i in range(n):
                buf.record_transition(
                    task_id="x" * 40, name="bench", kind="task",
                    state="RUNNING", job_id="y" * 8, attempt=0)
            best = min(best, (time.perf_counter() - t0) / n)
            buf.drain()
        return best

    on, off = per_record_cost(True), per_record_cost(False)
    # generous floors for 1-core shared CI boxes (measured ~1-3us / ~0.1us)
    assert off < 10e-6, f"disabled recording costs {off * 1e6:.1f}us"
    assert on < 50e-6, f"enabled recording costs {on * 1e6:.1f}us"
    # a full submit's worth of lifecycle events must stay well under the
    # ~1ms per-task budget implied by the tasks_per_second floor above
    assert 4 * (on - off) < 200e-6, (
        f"lifecycle events add {4 * (on - off) * 1e6:.0f}us per submit")


def test_sched_trace_recording_overhead():
    """Scheduling decision-trace overhead gate (ISSUE 11 CI leg): with
    recording ON — the default, so test_microbenchmark_absolute_floors
    above already measures the tasks_per_second_burst floor WITH the
    tracer and event emitters active (the full 1300/s floor is strictly
    stronger than the required 90%) — the only per-lease hot-path cost
    is _record_decision's coalescing dict update; report publishing
    rides the 1s heartbeat, amortized to ~zero per decision. The burst
    floor implies a ~770µs/lease budget; 10% of that is 77µs, so the
    record must stay well under it. Disabled must be one attribute
    check."""
    import time

    from ray_tpu._internal.config import get_config
    from ray_tpu._internal.ids import NodeID
    from ray_tpu.core.node_manager import NodeManager

    assert get_config().cluster_events_enabled, (
        "cluster_events_enabled must default ON so the burst floor "
        "above gates the integrated cost of decision-trace recording")

    def per_record_cost(enabled: bool) -> float:
        nm = NodeManager.__new__(NodeManager)
        nm._cluster_events_enabled = enabled
        nm._sched_decisions = {}
        nm._sched_dirty = False
        nm.node_id = NodeID.random()
        demand = {"CPU": 1.0}
        n = 20_000
        best = float("inf")
        for _ in range(3):  # best-of-3 to shed CI scheduling noise
            t0 = time.perf_counter()
            for i in range(n):
                nm._record_decision(demand, None, "granted")
            best = min(best, (time.perf_counter() - t0) / n)
            nm._sched_decisions.clear()
        return best

    on, off = per_record_cost(True), per_record_cost(False)
    assert off < 10e-6, f"disabled recording costs {off * 1e6:.1f}us"
    assert on < 30e-6, (
        f"decision-trace recording costs {on * 1e6:.1f}us/lease — "
        "over the 77us (10% of burst budget) bar")


def test_object_state_reporting_overhead():
    """Object-state reporting must cost <5% of the put_small budget.

    With reporting ON (the default — so
    test_microbenchmark_absolute_floors above already gates put_small's
    10000/s floor with it enabled), the
    only per-put cost is the creation-callsite capture + site record:
    delta publishing rides the 1s flush loop, amortized to ~zero per
    put. The 10000/s floor implies a 100µs/put budget; 5% of that is
    5µs, so the capture must stay well under it. The disabled path is a
    single attribute check."""
    import time

    from ray_tpu._internal.ids import ObjectID, TaskID, JobID
    from ray_tpu.core.core_worker import _capture_callsite

    sites: dict = {}
    tid = TaskID.for_normal_task(JobID.random())
    n = 20_000
    best = float("inf")
    for _ in range(3):  # best-of-3 to shed CI scheduling noise
        t0 = time.perf_counter()
        for i in range(n):
            # what CoreWorker.put adds with reporting on: one capture +
            # one dict store keyed by the fresh oid
            sites[ObjectID.for_put(tid, i)] = (_capture_callsite(),
                                               t0)
        best = min(best, (time.perf_counter() - t0) / n)
        sites.clear()
    assert best < 5e-6, (
        f"object-state capture costs {best * 1e6:.2f}µs/put — over 5% "
        "of the 100µs/put budget implied by the put_small floor")


def test_serve_request_record_overhead():
    """Serve request-record capture overhead gate (ISSUE 16): with
    recording ON — the default, so the serve-load floors already run
    with the waterfall instrumentation active — the proxy's per-request
    cost is ONE _finish_record call: assemble the stage dict + a
    lock-protected list append on the batched recorder (the publish
    itself rides the metrics flush cadence, amortized to ~zero per
    request). Follows the sched-trace convention: the capture must stay
    under 30us so even a 1ms request spends <3% on observability."""
    import time

    from ray_tpu._internal.config import get_config
    from ray_tpu.serve import request_context as rc
    from ray_tpu.serve.proxy import ProxyActor

    assert get_config().serve_requests_enabled, (
        "serve_requests_enabled must default ON so the serve-load "
        "floors gate the integrated cost of request-record capture")

    class _FakeCW:  # recorder target: buffer only, flush coro discarded
        gcs = object()

        def _spawn_from_thread(self, coro):
            coro.close()

    fake = _FakeCW()
    rc._recorder._core_worker = lambda: fake
    try:
        n = 20_000
        best = float("inf")
        for _ in range(3):  # best-of-3 to shed CI scheduling noise
            with rc._recorder._lock:
                rc._recorder._buf.clear()
            t0 = time.perf_counter()
            for i in range(n):
                ctx = {"request_id": "x" * 32, "start_ts": 1.0,
                       "router_s": 1e-4, "replica": "r",
                       "affinity": "hit"}
                ProxyActor._finish_record(
                    ctx, "bench", "ok", t0=0.0, t1=1e-4, t_first=2e-4,
                    t_end=3e-4, model_id="m", ttft_s=2e-4, tpot_s=1e-5,
                    chunks=4)
            best = min(best, (time.perf_counter() - t0) / n)
        with rc._recorder._lock:
            assert len(rc._recorder._buf) >= n  # records actually taken
            rc._recorder._buf.clear()
    finally:
        del rc._recorder._core_worker  # restore the class staticmethod
    assert best < 30e-6, (
        f"request-record capture costs {best * 1e6:.1f}us/request — "
        "over the 30us observability budget")


def test_train_step_record_overhead():
    """Train step-waterfall capture overhead gate (ISSUE 17): with
    recording ON — the default, so the corpus_pretrain floors in
    test_ingest_train already run with the waterfall instrumentation
    active — a full step's observability cost is four phase brackets +
    one end_step: timestamps, a dict build, and a lock-protected list
    append on the batched publisher (the publish rides the flush
    cadence, amortized to ~zero per step). Budget: < 50us per step, so
    even a 1ms CPU step spends < 5% on observability."""
    import time

    from ray_tpu._internal.config import get_config
    from ray_tpu.train.telemetry import StepRecorder

    assert get_config().train_state_enabled, (
        "train_state_enabled must default ON so the train-loop floors "
        "gate the integrated cost of step-record capture")

    class _FakeCW:  # recorder target: buffer only, flush coro discarded
        gcs = object()

        def _spawn_from_thread(self, coro):
            coro.close()

    rec = StepRecorder("b" * 32, "perf-gate", rank=0)
    fake = _FakeCW()
    rec._pub._core_worker = lambda: fake
    rec.end_step(0)  # open the wall clock
    n = 20_000
    best = float("inf")
    for _ in range(3):  # best-of-3 to shed CI scheduling noise
        with rec._pub._lock:
            rec._pub._buf.clear()
        t0 = time.perf_counter()
        for i in range(n):
            rec.begin_phase("data_wait")
            rec.end_phase()
            rec.begin_phase("h2d")
            rec.end_phase()
            rec.begin_phase("step")
            rec.end_phase()
            rec.begin_phase("ckpt_block")
            rec.end_phase()
            rec.end_step(i + 1, tokens=128, loss=0.5)
        best = min(best, (time.perf_counter() - t0) / n)
    with rec._pub._lock:
        assert len(rec._pub._buf) >= n  # records actually taken
        rec._pub._buf.clear()
    assert best < 50e-6, (
        f"step-record capture costs {best * 1e6:.1f}us/step — over the "
        "50us observability budget")


_DAG_TICKS = """
import sys, time
import ray_tpu as rt
from ray_tpu.dag import InputNode

rt.init(num_cpus=4)

@rt.remote
class Echo:
    def apply(self, x):
        return x

e1, e2 = Echo.remote(), Echo.remote()
with InputNode() as inp:
    out = e2.apply.bind(e1.apply.bind(inp))
dag = out.experimental_compile(channels=True)
dag.execute(0).get(timeout=60)
print("TICKS ready", flush=True)
for _ in sys.stdin:         # one window of ticks a line
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.5:
        dag.execute(1).get(timeout=60)
        n += 1
    print("TICKS", n / (time.perf_counter() - t0), flush=True)
dag.teardown()
rt.shutdown()
"""


@pytest.mark.timeout(240)
def test_dag_observability_overhead(tmp_path):
    """Instrumentation-overhead gate for the DAG plane: channel ticks/s
    with the FULL observability stack enabled (per-channel stats, always
    on; dag_state registration + per-second reports; per-tick
    distributed tracing, a span export per tick per process) against
    the same DAG with the last two off. What a loaded, shared host can
    still tell is the RATIO, so both DAGs are alive at once, each in a
    cluster of its own (tracing is decided once per process, from
    RAYT_TRACING_DIR at boot), and take windows of ticks in turns; the
    best window of each side is compared."""
    import os
    import subprocess
    import sys

    def start(observed: bool):
        env = dict(os.environ)
        env["PYTHONPATH"] = "/root/repo"
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("RAYT_TRACING_DIR", None)
        if observed:
            env["RAYT_TRACING_DIR"] = str(tmp_path / "spans")
        env["RAYT_DAG_STATE_ENABLED"] = "1" if observed else "0"
        with open(tmp_path / f"observed-{observed}.err", "w") as err:
            return subprocess.Popen(
                [sys.executable, "-c", _DAG_TICKS], env=env, text=True,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err)

    def said(proc) -> str:
        return (tmp_path / f"observed-{proc is on}.err").read_text()[-2000:]

    def answer(proc) -> str:
        for line in proc.stdout:
            if line.startswith("TICKS "):
                return line.split(None, 1)[1]
        raise AssertionError(said(proc))

    def window(proc) -> float:
        proc.stdin.write("\n")
        proc.stdin.flush()
        return float(answer(proc))

    on, off = start(True), start(False)
    try:
        assert answer(on).strip() == answer(off).strip() == "ready"
        rates = {on: [], off: []}
        for pair in ((on, off), (off, on)) * 3:
            for proc in pair:
                rates[proc].append(window(proc))
        for proc in (on, off):
            proc.stdin.close()
            assert proc.wait(timeout=60) == 0, said(proc)
    finally:
        for proc in (on, off):
            proc.kill()
            proc.wait(timeout=10)
    ratio = max(rates[on]) / max(rates[off])
    # per-tick tracing alone takes about half of the ticks (a span
    # written to its file per tick per process: best over best reads
    # 0.50-0.55 on an idle host), dag_state under a tenth
    assert ratio >= 0.4, (
        f"observability-on DAG ticks {rates[on]} /s against "
        f"{rates[off]} /s with it off: best over best {ratio:.2f} "
        "(instrumentation overhead regression)")
    # the tracing side-channel actually ran: per-tick spans exported
    from ray_tpu._internal import otel

    spans = otel.read_spans(str(tmp_path / "spans"))
    assert any(s["name"] == "dag.execute" for s in spans)


def test_lease_reuse_faster_than_fresh_lease(ray_cluster):
    """Back-to-back same-shape tasks must reuse the cached lease (ref:
    normal_task_submitter.cc:291): serial round-trips with reuse should
    comfortably beat a conservative no-reuse bound."""
    import time

    @rt.remote
    def f(x):
        return x

    rt.get(f.remote(0))  # warm worker + lease
    t0 = time.perf_counter()
    n = 50
    for i in range(n):
        rt.get(f.remote(i))
    dt = time.perf_counter() - t0
    # 50 serial calls at sub-ms lease-reused latency; allow wide margin
    assert dt < 5.0, f"50 serial tasks took {dt:.2f}s — lease reuse broken?"
