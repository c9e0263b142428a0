"""IMPALA scale bench: N rollout runners + aggregators + learner, records
steady-state samples/s into RL_BENCH.json (BASELINE config #4 shape at
CI scale; ref harness discipline: rllib release smoke tests).

Usage: python tools/rl_scale_bench.py [num_runners] [iters]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU measurement, whatever the host has
# A 1-core CI box boots ~40 jax-importing worker processes serially; the
# production timeouts would declare them dead mid-boot and thrash.
os.environ.setdefault("RAYT_WORKER_STARTUP_TIMEOUT_S", "900")
os.environ.setdefault("RAYT_LEASE_TIMEOUT_S", "600")
os.environ.setdefault("RAYT_RPC_REQUEST_TIMEOUT_S", "300")
os.environ.setdefault("RAYT_NODE_DEATH_TIMEOUT_S", "300")
os.environ.setdefault("RAYT_ACTOR_SCHEDULING_DEADLINE_S", "1800")
os.environ.setdefault("RAYT_ACTOR_CREATION_PUSH_TIMEOUT_S", "1200")


def _bench_body(num_runners: int, iters: int) -> dict:
    from ray_tpu.rl.impala import IMPALAConfig

    algo = IMPALAConfig(
        env="CartPole-v1",
        num_env_runners=num_runners,
        num_envs_per_runner=2,
        rollout_fragment_length=32,
        num_aggregators=4,
        train_batch_size=2048,
        max_requests_in_flight=2,
        boot_wave=4,
        call_timeout_s=600.0,
        seed=0).build()
    # warmup: let the pipeline fill
    r = algo.train()
    t0 = time.perf_counter()
    steps0 = r["num_env_steps_sampled"]
    last = r
    for _ in range(iters):
        last = algo.train()
    dt = time.perf_counter() - t0
    steps = last["num_env_steps_sampled"] - steps0
    out = {
        "bench": "impala_scale",
        "num_env_runners": num_runners,
        "num_envs_per_runner": 2,
        "host_cores": os.cpu_count(),
        "iterations": iters,
        "env_steps": steps,
        "samples_per_s": round(steps / dt, 1),
        "episode_return_mean": last["episode_return_mean"],
        "learner_updates_total": last["training_iteration"],
    }
    algo.stop()
    return out


def main():
    import ray_tpu as rt

    num_runners = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 10

    # resource fiction on a small box: the point is control-plane scale
    # (N actors pipelining through aggregators), not per-core throughput
    rt.init(num_cpus=max(num_runners + 8, os.cpu_count() or 1),
            resources={"TPU": 8})
    try:
        out = _bench_body(num_runners, iters)
    except BaseException:
        try:  # diagnosis: which actor (if any) never became ALIVE?
            from ray_tpu import state_api

            for a in state_api.list_actors():
                if a.get("state") != "ALIVE":
                    print("NOT-ALIVE ACTOR:", a, file=sys.stderr)
            print("STATUS:", state_api.cluster_status(), file=sys.stderr)
            s = state_api.summary()
            print("RESOURCES total:", s.get("resources_total"),
                  file=sys.stderr)
            print("RESOURCES avail:", s.get("resources_available"),
                  file=sys.stderr)
        except Exception as e:
            print("state dump failed:", e, file=sys.stderr)
        raise
    finally:
        rt.shutdown()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "RL_BENCH.json")
    existing = {}
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
    existing["impala_scale"] = out
    with open(path, "w") as f:
        json.dump(existing, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
