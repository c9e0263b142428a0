"""Readers of the program's own account of its start-up: the `startup`
and `programs` sections of the log in the process that holds the chip
(ray_tpu/_internal/profiler.ProcessLog). A serve cell finds them in
`LLMEngine.stats()` at the window's two ends (`obs["before"|"after"]
["stats"]`); a train cell on its step records (`obs["steps"]`: whole on
the first, afterwards what was asked for since). A program without the
log (the parent of the PR that brought it) has neither: every reader
then returns None."""

from __future__ import annotations

STAGES = ("trace_s", "lower_s", "compile_s", "cache_load_s")
# the program's phases that end before its first step
PHASES = ("spawn_wait", "boot", "backend", "engine_build")
# what the benchmark stamps as its own doing before the window
BENCHMARK_STAMPS = ("imports_s", "cluster_s", "lead_s", "replica_weights_s",
                    "weights_s")


def _stage_s(totals: dict) -> float:
    return float(sum(totals.get(k, 0.0) for k in STAGES))


def _log(obs: dict):
    """-> None, or {"startup", "before": the programs section as the
    window started, "in_window": programs asked for inside it}."""
    if "before" in obs:
        before = (obs["before"].get("stats") or {})
        after = (obs.get("after") or {}).get("stats") or {}
        if "startup" not in before or "programs" not in before \
                or "programs" not in after:
            return None
        return {"startup": before["startup"], "before": before["programs"],
                "in_window": after["programs"]["asked"]
                - before["programs"]["asked"]}
    steps = sorted((s for s in obs.get("steps") or ()),
                   key=lambda s: s["step"])
    first = next((s for s in steps if "startup" in s and "programs" in s),
                 None)
    if first is None:
        return None
    warm = int(obs["warmup_steps"])        # records count from 1
    before = dict(first["programs"])
    in_window = 0
    for s in steps:
        delta = s.get("programs")
        if s is first or not delta:
            continue
        if s["step"] <= warm:
            for k in STAGES + ("asked", "cache_hits", "cache_misses"):
                before[k] = before[k] + delta[k]
        else:
            in_window += delta["asked"]
    return {"startup": first["startup"], "before": before,
            "in_window": in_window}


def _phase_s(log: dict, *names) -> float:
    phases = log["startup"]["phases"]
    return float(sum(phases[n][1] - phases[n][0]
                     for n in names if n in phases))


def _from_log(read):
    """A reader of (obs, log) as a reader of obs: None without the log."""
    def reader(obs: dict):
        log = _log(obs)
        return None if log is None else read(obs, log)
    reader.__doc__ = read.__doc__
    return reader


@_from_log
def worker_s(obs, log):
    """Lease asked to worker registered, for the worker that holds the
    chips: phases `spawn_wait` and `boot`."""
    return _phase_s(log, "spawn_wait", "boot")


@_from_log
def chip_wait_s(obs, log):
    """The part of `spawn_wait` spent waiting for chips that a dead
    process still held."""
    return float(log["startup"]["chip_wait_s"])


@_from_log
def backend_s(obs, log):
    return _phase_s(log, "backend")


@_from_log
def programs_s(obs, log):
    """Seconds in the four stages over every program the process asked
    for before the window's start."""
    return _stage_s(log["before"])


@_from_log
def programs_asked(obs, log):
    return log["before"]["asked"]


@_from_log
def cache_misses(obs, log):
    return log["before"]["cache_misses"]


@_from_log
def programs_in_window(obs, log):
    return log["in_window"]


def _in_lead_s(obs: dict, log: dict) -> float:
    """Seconds of the programs asked for during a serve cell's lead-in,
    from the log's `timeline`: its `t` is the replica's perf_counter,
    which on one host is the clock of `obs["window"]` too."""
    lead = float(obs["setup"].get("lead_s", 0.0))
    if not lead or "window" not in obs:
        return 0.0
    start = obs["window"][0] - lead
    at_start = [s for t, s, _ in log["before"].get("timeline", ())
                if t <= start]
    return _stage_s(log["before"]) - (at_start[-1] if at_start else 0.0)


@_from_log
def unaccounted_s(obs, log):
    """`setup_s` less the program's phases, less the programs asked for
    at its sites, less what the benchmark stamps as its own doing.
    Nothing is taken off twice: programs asked for inside a phase are in
    that phase already, those asked for during the lead-in are in
    `lead_s`, and the unlabelled ones are the benchmark's weight
    initialisation, which its stamp holds (in a train cell also the
    recipe's adapters and optimizer state, which so stay in the rest)."""
    by = log["before"].get("by_program") or {}
    elsewhere = _in_lead_s(obs, log) + sum(
        _stage_s(by.get(n, {}))
        for n in ("unlabelled", "backend", "engine_build"))
    stamps = sum(float(obs["setup"].get(k, 0.0)) for k in BENCHMARK_STAMPS)
    return (float(obs["setup_s"]) - _phase_s(log, *PHASES)
            - (_stage_s(log["before"]) - elsewhere) - stamps)
