"""One run of one cell.

  python3 -m benchmarks.run --workload <name> --seed <n> \\
      --seconds <run_seconds> --trace <0|1>

Starts the cluster, brings the cell up, warms the cell's own shapes,
measures for --seconds, holds the outputs against the plain reference,
and prints two lines: first `{"info": ...}` (what the result cannot
hold: the set-up breakdown, offered and achieved rate, the gap
histogram, the checks, what the engine counted in the window), last the
result: `correct`, `attempted`, `failed`, `metrics`, `device`, with
--trace 1 `breakdown`, and last `compared`: each number `correct`
compared beside its limit, which are also the run's last lines on
standard error.

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 a short stretch of the window is profiled and the metrics are
the cell's per-layer metrics.

No chip, no result: the run fails unless every device the cell computed
on was a TPU and there were as many as the cell asks for. (--manifest
names another manifest: the CPU rehearsal's, which benchmarks/rehearsal.py
derives, or a rate sweep's; a rehearsal prints what it found to stderr
and still fails.)
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from benchmarks import manifest as manifest_mod  # noqa: E402


def _reader(spec: str):
    module, fn = spec.rsplit(".", 1)
    return getattr(importlib.import_module("benchmarks.readers." + module),
                   fn)


def end_to_end(cell, obs: dict) -> dict:
    out = {}
    for m in cell.end_to_end:
        mod = importlib.import_module("benchmarks.end_to_end." + m["name"])
        out[m["name"]] = {"value": float(mod.read(obs)), "unit": m["unit"]}
    return out


def per_layer(cell, obs: dict) -> dict:
    """Every per-layer metric of the cell that finds something to read.
    A reader learns whose run it reads from `obs["cell"]`, and the
    cell's files from `obs["config"]` and `obs["traffic"]`, not from
    its metric file and whatever the driver put into `obs`."""
    obs = {**obs, "cell": cell.name, "config": cell.config,
           "traffic": cell.traffic}
    out = {}
    for m in cell.per_layer:
        spec = m["file"]
        value = _reader(spec["reader"])(obs, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def causes(e: BaseException) -> list:
    """The chain of exceptions behind `e`, nearest first: what it was
    raised from, else what was being handled when it was raised."""
    out = []
    while len(out) < 16:
        e = e.__cause__ or (None if e.__suppress_context__
                            else e.__context__)
        if e is None or e in out:
            break
        out.append(e)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--manifest", default=manifest_mod.DEFAULT_MANIFEST)
    args = ap.parse_args(argv)

    manifest = manifest_mod.load(args.manifest)
    cell = manifest_mod.resolve(manifest, args.workload)
    driver = importlib.import_module("benchmarks." + cell.traffic["driver"])

    # everything this run writes: inside the checkout, fixed name, emptied
    work = os.path.join(manifest_mod.ROOT, ".bench_work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        obs = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                         work, T_PROCESS)
    except Exception as e:
        print(f"benchmarks.run: {cell.name} failed: {e!r}", file=sys.stderr)
        for cause in causes(e):
            print(f"benchmarks.run:   because of {cause!r}", file=sys.stderr)
        try:
            import ray_tpu as rt

            rt.shutdown()
        except Exception:
            pass
        return 1
    trace = obs.get("trace")
    if trace and trace.get("recorded"):
        with open(os.path.join(work, "recorded_trace.json"), "w") as f:
            json.dump(trace.pop("recorded"), f)

    attempted, failed = driver.attempted_failed(obs)
    device = dict(obs["device"])
    device["memory_peak_bytes"] = int(obs["memory_peak_bytes"])
    tol = cell.config["tolerances"]
    result = {"correct": bool(driver.correct(obs, tol)),
              "attempted": int(attempted), "failed": int(failed)}
    info = {"workload": cell.name, "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace,
            **driver.info(obs)}
    if args.trace:
        result["metrics"] = per_layer(cell, obs)
        if trace and not trace.get("error"):
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
            info["trace_info"] = {k: trace.get(k) for k in (
                "lines", "xplane_bytes", "busy_s_by_device",
                "collective_s", "collective_exposed_s")}
            info["traced"] = obs.get("traced")
    else:
        result["metrics"] = end_to_end(cell, obs)
    result["device"] = device
    # each number `correct` compared beside its limit: last on the line
    result["compared"] = driver.compared(obs, tol)

    on_chip = (device["platform"] == "tpu" and device["count"] == cell.chips)
    out = sys.stdout if on_chip else sys.stderr
    print(json.dumps({"info": info}, default=str), file=out, flush=True)
    print(json.dumps(result), file=out, flush=True)
    for name, (value, limit) in result["compared"].items():
        print(f"benchmarks.run: compared {name} {value!r} limit {limit!r}",
              file=sys.stderr)
    if not on_chip:
        print(f"benchmarks.run: computed on {device['count']} "
              f"{device['platform']} device(s), the cell asks for "
              f"{cell.chips} tpu: no result", file=sys.stderr)
        return 1
    if args.trace and "busy_s" not in device:
        print("benchmarks.run: the traced run has no device trace",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
