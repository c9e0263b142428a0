"""On-demand worker profiling (ref analog:
dashboard/modules/reporter/profile_manager.py — the reference attaches
py-spy/memray to live workers via ptrace; here the worker samples
ITSELF on request, no ptrace and no extra dependency).

Two probes, both RPC-triggered against any live worker:

* :func:`sample_cpu` — a sampling wall/CPU profiler: a thread polls
  ``sys._current_frames()`` at `interval_s` for `duration_s`, folding
  stacks into collapsed form ("a;b;c count" — flamegraph.pl /
  speedscope input). Cooperative sampling sees exactly what py-spy's
  GIL-holder view sees for pure-Python work.
* :func:`sample_memory` — tracemalloc window: enables tracing for
  `duration_s` and reports the top allocation sites by net new bytes
  (the memray-lite answer to "what is this worker allocating?").

And one name for the JAX profiler's host span, :func:`span_type`: what
the serve engine and the train StepRecorder open around their units of
work, so that a `jax.profiler` trace of the process that holds the chip
shows them on the device operations' own clock.

Beside it, the process's own log (:class:`ProcessLog`, one a process,
:func:`process_log`): the phases of the process's start-up, and every
program the process asks XLA for, by the name of the site that asked
(:func:`site_type`). It is written when the process starts and when jax
traces, lowers, compiles or loads a program; a warm step writes nothing.
It also sums the pauses of the process's garbage collector (`gc_s`, by
`gc.callbacks`). `LLMEngine.stats()` and the train step records show it.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import sys
import threading
import time
import traceback


def span_type():
    """`jax.profiler.TraceAnnotation` itself, imported on first use (a
    train controller or a driver that never touches jax imports this
    module too). `with span_type()("rayt.engine.admit", slot=3):` is a
    TraceMe: with no profiler session active it is a flag check (under a
    microsecond, no file, no buffer, no thread); under one, the event
    lands in the `/host:` plane of the same .xplane.pb as the device
    operations, with the keyword arguments as its stats. Names start
    with `rayt.`; arguments are plain ints, floats and short strings
    that the caller already holds."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


# ------------------------------------------------- the process's own log
# jax.monitoring's names (jax 0.9.0: _src/dispatch.py, _src/compiler.py,
# _src/compilation_cache.py) for the way from a call to an executable
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
# fired inside the `compile` stage, with no name: a hit is an executable
# fetched from the persistent cache, a miss one compiled and written to
# it (jax writes only what took a second to compile: a smaller program
# is compiled anew in every process and fires neither)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_TOTAL = {"hit": "cache_hits", "miss": "cache_misses"}
UNLABELLED = "unlabelled"
_MAX_RECORDS = 4096
# phases are recorded until the first step has returned
PHASES = ("spawn_wait", "boot", "backend", "engine_build", "ready")


def no_programs() -> dict:
    """The totals of a log that has recorded nothing."""
    return {"asked": 0, "cache_hits": 0, "cache_misses": 0, "trace_s": 0.0,
            "lower_s": 0.0, "compile_s": 0.0, "cache_load_s": 0.0}


class ProcessLog:
    """What one process did to start, and every program it asked XLA
    for. All times are this process's `time.perf_counter()`, the clock
    of the request records and of the `decode_dispatch` spans' `t_host`;
    `anchor` is one (`time.time()`, `perf_counter()`) pair read together,
    by which another process of the host is joined.

    A program record is {"program", "fun_name", "stage", "seconds",
    "cache", "t"}: `program` is the label of the site under which the
    stage fired in its thread (`site_type`, or the open phase; jax
    compiles in the calling thread, inside the call), `fun_name` what
    jax calls the function, `stage` one of trace, lower, compile,
    cache_load, `t` when the stage ended. The four stages' seconds do
    not overlap: of the traces nested in one another only the outermost
    is recorded, when its program is lowered (one that never is, under
    `eval_shape`, is not), and a `compile` that fetched from the cache
    counts what is left beside the `cache_load`. `asked` counts the
    `compile` stages: every time XLA was asked for an executable,
    compiled or loaded.
    """

    def __init__(self):
        self.anchor = (time.time(), time.perf_counter())
        self.t0 = self.anchor[1]         # the process's start
        self.leased_chips = False        # started for a lease on chips
        self.chip_wait_s = 0.0
        self.callbacks = 0               # listener calls, of any event
        self.appended = 0                # records written, dropped or not
        self.gc_s = 0.0                  # in the collector, all generations
        self._gc_start = None
        self._lock = threading.Lock()
        self._records: collections.deque = collections.deque(
            maxlen=_MAX_RECORDS)
        self._totals = no_programs()
        self._by_program: dict = {}
        self._choices: dict = {}         # {program: {what: choice}}
        # per program asked for: [t, the four stages' seconds so far,
        # asked so far], so that a reader can cut the totals at a time
        self._timeline: collections.deque = collections.deque(maxlen=1024)
        self._unlabelled_since_ready = 0
        self._phases: dict = {}
        self._open_phase = None
        self.is_ready = False            # the first step has returned
        self._thread = threading.local()

    # ------------------------------------------------------- start-up
    def spawned(self, info: dict):
        """What the node manager knows and this worker cannot, from the
        reply to its registration (wall clock, one host): when the lease
        that caused the worker was asked for, how long the chips of a
        dead process held its spawn back, when it was started."""
        wall, perf = self.anchor
        asked, started = (perf + (float(info[k]) - wall)
                          for k in ("lease_asked", "spawned"))
        self.t0 = started
        self.leased_chips = bool(info.get("tpu"))
        self.chip_wait_s = float(info.get("chip_wait_s") or 0.0)
        self._phases["spawn_wait"] = [min(asked, started), started]
        self._phases["boot"] = [started, time.perf_counter()]

    @contextlib.contextmanager
    def phase(self, name: str):
        """Records [start, end] of a start-up phase, once: the first
        wins, and none is recorded after `ready`. Programs asked for
        inside it, under no site, are named for it."""
        if name in self._phases or self.is_ready:
            yield
            return
        start = time.perf_counter()
        self._open_phase = name
        try:
            yield
        finally:
            self._open_phase = None
            self._phases.setdefault(name, [start, time.perf_counter()])
            self.is_ready = "ready" in self._phases

    def backend_up(self, first=None):
        """The process's first touch of the device backend, with the
        import of jax where it is the first, as phase `backend`; the
        listeners are registered on the way. `first`, where given, is
        called inside the phase BEFORE the touch and its result
        returned: what imports the modules the process will run (a
        replica loading its callable). The order is kept as a process
        that knows nothing of this has it, imports and then the backend:
        with the backend up first the hybrid cell's executables loaded
        from the cache in their slow mode, 4.5 s for 2.3, in seven runs
        of seven (PERF.md, PR 40; the cause is not known). A later call
        only calls `first`."""
        if "backend" in self._phases:
            return first() if first else None
        with self.phase("backend"):
            listen()
            out = first() if first else None
            import jax

            jax.devices()
        return out

    def startup(self) -> dict:
        """The phases as {name: [start, end]} in seconds since the
        process's start, in order; `anchor` as read; `process_start` on
        this process's perf_counter."""
        t0 = self.t0
        return {"phases": {n: [a - t0, b - t0]
                           for n, (a, b) in self._phases.items()},
                "anchor": {"wall": self.anchor[0],
                           "perf_counter": self.anchor[1]},
                "process_start": t0, "chip_wait_s": self.chip_wait_s}

    # ------------------------------------------------------- programs
    def label(self, program) -> object:
        """Sets this thread's label and returns the one it replaces."""
        prev = getattr(self._thread, "label", None)
        self._thread.label = program
        return prev

    @contextlib.contextmanager
    def labelled(self, program):
        """This thread's label for the block: a site with no span, for
        programs asked for outside any traced stretch (a recipe's
        set-up). Yields the label it replaces."""
        outer = self.label(program)
        try:
            yield outer
        finally:
            self.label(outer)

    def on_gc(self, phase: str):
        """`gc.callbacks`: a collection runs in the thread that caused
        it, under the interpreter's lock, and stops every other."""
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        elif self._gc_start is not None:
            self.gc_s += now - self._gc_start
            self._gc_start = None

    def chose(self, what: str, **choice):
        """A choice the code being traced made from its shapes, which
        fixes what the program will run (a kernel's path, its tile):
        kept under the label of the site the trace runs under, like its
        stages, as `by_program[...]["choices"][what]`; the newest of a
        name stands."""
        program = (getattr(self._thread, "label", None) or self._open_phase
                   or UNLABELLED)
        with self._lock:
            self._choices.setdefault(program, {})[what] = choice

    def on_event(self, event: str):
        self.callbacks += 1
        if event in _CACHE_EVENTS:
            self._thread.cache = _CACHE_EVENTS[event]

    def on_duration(self, event: str, seconds: float, fun_name=None):
        self.callbacks += 1
        stage = _STAGES.get(event)
        if stage is None:
            return
        now = time.perf_counter()
        th = self._thread
        rec = {"program": getattr(th, "label", None) or self._open_phase
               or UNLABELLED, "fun_name": fun_name, "stage": stage,
               "seconds": seconds, "cache": None, "t": now}
        # traces held back, in the order they ended: each began after
        # the one before it ended, so those that began inside this stage
        # are the last ones (popped: a wide trace holds thousands)
        held = getattr(th, "traces", None)
        if held is None:
            held = th.traces = []
        while held and held[-1]["t"] - held[-1]["seconds"] >= now - seconds:
            held.pop()
        if stage == "trace":
            # every jitted function called in another's trace is traced
            # too, and ends before it (jnp's own are jitted: hundreds in
            # one model step). The outermost holds their time: keep that
            # one, until the program is lowered
            held.append(rec)
            return
        recs = [rec]
        if stage == "lower":   # which traces helpers of its own
            recs, th.traces = held + recs, []
            th.fun_name = fun_name
        elif stage == "cache_load":
            rec["cache"], th.loaded_s = "hit", seconds
            rec["fun_name"] = getattr(th, "fun_name", None)
        else:   # what a compile that loaded did beside the load
            rec["cache"], th.cache = getattr(th, "cache", None), None
            rec["seconds"] = max(0.0, seconds - getattr(th, "loaded_s", 0.0))
            th.loaded_s = 0.0
        with self._lock:
            for r in recs:
                self._append(r)

    def _append(self, rec: dict):
        program, stage = rec["program"], rec["stage"]
        self._records.append(rec)
        self.appended += 1
        by = self._by_program.setdefault(
            program, {**no_programs(), "fun_names": {}})
        for tot in (self._totals, by):
            tot[stage + "_s"] += rec["seconds"]
            if stage == "compile":
                tot["asked"] += 1
                if rec["cache"]:
                    tot[_CACHE_TOTAL[rec["cache"]]] += 1
        if stage == "compile":
            name = rec["fun_name"]
            by["fun_names"][name] = by["fun_names"].get(name, 0) + 1
            self._timeline.append([
                rec["t"], sum(self._totals[s + "_s"]
                              for s in _STAGES.values()),
                self._totals["asked"]])
            if program == UNLABELLED and self.is_ready:
                self._unlabelled_since_ready += 1

    def records(self) -> list:
        with self._lock:
            return list(self._records)

    def programs(self) -> dict:
        """The totals, `by_program` (the same per name, `asked` under
        `count`, how often each function was asked for under it, and
        the `choices` its traces reported),
        `last`, the newest record whole, `timeline`: for each of the
        newest 1024 programs asked for, [t, the four stages' seconds up
        to it, `asked` up to it], and `callbacks`: how often jax called
        a listener, for any event (what the log costs a warm step: the
        count stands still)."""
        with self._lock:
            by = {}
            for name, tot in self._by_program.items():
                tot = dict(tot, fun_names=dict(tot["fun_names"]))
                tot["count"] = tot.pop("asked")
                if name in self._choices:
                    tot["choices"] = {w: dict(c) for w, c in
                                      self._choices[name].items()}
                by[name] = tot
            return {**self._totals,
                    "dropped": self.appended - len(self._records),
                    "callbacks": self.callbacks,
                    "unlabelled": by.get(UNLABELLED, {}).get("count", 0),
                    "unlabelled_since_ready": self._unlabelled_since_ready,
                    "by_program": by,
                    "timeline": [list(p) for p in self._timeline],
                    "last": dict(self._records[-1]) if self._records
                    else None}


_LOG = ProcessLog()
_listening = False
_listen_lock = threading.Lock()


def process_log() -> ProcessLog:
    return _LOG


def _on_event(event, **kw):
    _LOG.on_event(event)


def _on_duration(event, seconds, fun_name=None, **kw):
    _LOG.on_duration(event, seconds, fun_name)


def _on_gc(phase, info):
    _LOG.on_gc(phase)


def listen():
    """Registers the two listeners with jax.monitoring and the one with
    the garbage collector, once a process: where it first touches the
    backend (`ProcessLog.backend_up`), or on first use in a process that
    never does."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        gc.callbacks.append(_on_gc)
        _listening = True


_Site = None


def site_type():
    """`span_type()` that also names, for its thread and while it is
    open, the programs asked for inside it: `with site_type()(
    "rayt.engine.admit", "admit[64]", slot=3):` is the same TraceMe, and
    a stage that fires under it is recorded as program `admit[64]`. The
    label is a thread-local store; an inner site's label gives way to
    the outer's again when it closes."""
    global _Site
    if _Site is None:
        listen()

        class _Site(span_type()):
            def __init__(self, name, program, **kwargs):
                super().__init__(name, **kwargs)
                self._program = program

            def __enter__(self):
                self._outer = _LOG.label(self._program)
                return super().__enter__()

            def __exit__(self, *exc):
                _LOG.label(self._outer)
                return super().__exit__(*exc)

    return _Site


def sample_cpu(duration_s: float = 5.0, interval_s: float = 0.01,
               max_frames: int = 64) -> dict:
    """Collapsed-stack samples of every thread in this process."""
    duration_s = min(float(duration_s), 120.0)
    interval_s = max(float(interval_s), 0.001)
    counts: dict[str, int] = {}
    samples = 0
    me = threading.get_ident()
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        t0 = time.monotonic()
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue  # the profiler's own sampling loop
            stack = traceback.extract_stack(frame, limit=max_frames)
            key = names.get(ident, str(ident)) + ";" + ";".join(
                f"{f.name} ({f.filename.rsplit('/', 1)[-1]}:{f.lineno})"
                for f in stack)
            counts[key] = counts.get(key, 0) + 1
        samples += 1
        time.sleep(max(0.0, interval_s - (time.monotonic() - t0)))
    return {
        "type": "cpu_samples",
        "duration_s": duration_s,
        "interval_s": interval_s,
        "num_samples": samples,
        "stacks": counts,  # collapsed-stack -> hit count
    }


def sample_memory(duration_s: float = 5.0, top_n: int = 25) -> dict:
    """Net new allocations over a tracemalloc window, by source line."""
    import tracemalloc

    duration_s = min(float(duration_s), 120.0)
    was_tracing = tracemalloc.is_tracing()
    if not was_tracing:
        tracemalloc.start(16)
    try:
        before = tracemalloc.take_snapshot()
        time.sleep(duration_s)
        after = tracemalloc.take_snapshot()
    finally:
        if not was_tracing:
            tracemalloc.stop()
    stats = after.compare_to(before, "lineno")
    top = [{
        "location": str(st.traceback[0]) if st.traceback else "?",
        "size_diff_bytes": st.size_diff,
        "count_diff": st.count_diff,
        "size_bytes": st.size,
    } for st in stats[:top_n]]
    return {
        "type": "memory_window",
        "duration_s": duration_s,
        "top_allocations": top,
        "total_new_bytes": sum(s.size_diff for s in stats
                               if s.size_diff > 0),
    }


def render_collapsed(result: dict) -> str:
    """cpu_samples result -> flamegraph.pl collapsed-stack text."""
    return "\n".join(f"{stack} {count}"
                     for stack, count in sorted(
                         result.get("stacks", {}).items(),
                         key=lambda kv: -kv[1]))


def render_top(result: dict, n: int = 15) -> str:
    """Human summary: hottest leaf functions by inclusive samples."""
    leaf_counts: dict[str, int] = {}
    for stack, count in result.get("stacks", {}).items():
        leaf = stack.rsplit(";", 1)[-1]
        leaf_counts[leaf] = leaf_counts.get(leaf, 0) + count
    total = max(1, sum(leaf_counts.values()))
    lines = [f"{result.get('num_samples', 0)} samples over "
             f"{result.get('duration_s', 0)}s"]
    for leaf, count in sorted(leaf_counts.items(),
                              key=lambda kv: -kv[1])[:n]:
        lines.append(f"{100 * count / total:5.1f}%  {leaf}")
    return "\n".join(lines)
