"""IMPALA image-RL bench: a >=64-runner rollout fleet streaming PIXEL
observations through aggregators into a CNN V-trace learner, recording
samples/s AND a committed learning curve (mean return >= the threshold)
into RL_BENCH.json under "impala_image".

This is BASELINE config #4's shape ("IMPALA Atari, 256 CPU rollout
actors + TPU learner group") at the scale this host supports: Catch-v0
stands in for ALE (no gym/ALE in the image; same [H, W, C] CNN path —
ref: rllib/benchmarks/ppo/benchmark_atari_ppo.py:37 committed reward
targets).

Usage: python tools/rl_image_bench.py [num_runners] [max_minutes]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU measurement, whatever the host has
os.environ.setdefault("RAYT_WORKER_STARTUP_TIMEOUT_S", "900")
os.environ.setdefault("RAYT_LEASE_TIMEOUT_S", "600")
os.environ.setdefault("RAYT_RPC_REQUEST_TIMEOUT_S", "300")
os.environ.setdefault("RAYT_NODE_DEATH_TIMEOUT_S", "300")
os.environ.setdefault("RAYT_ACTOR_SCHEDULING_DEADLINE_S", "1800")
os.environ.setdefault("RAYT_ACTOR_CREATION_PUSH_TIMEOUT_S", "1200")

RETURN_THRESHOLD = 0.8   # committed: random ~-0.8, perfect play = 1.0


def _bench_body(num_runners: int, max_minutes: float) -> dict:
    from ray_tpu.rl.impala import IMPALAConfig
    from ray_tpu.rl.module import CNNModuleConfig

    algo = IMPALAConfig(
        env="Catch-v0",
        num_env_runners=num_runners,
        num_envs_per_runner=2,
        rollout_fragment_length=32,
        num_aggregators=4,
        train_batch_size=2048,
        lr=3e-3,
        max_requests_in_flight=2,
        boot_wave=8,
        call_timeout_s=600.0,
        seed=0).build()
    assert isinstance(algo.module_cfg, CNNModuleConfig)
    r = algo.train()  # pipeline fill
    t0 = time.perf_counter()
    steps0 = r["num_env_steps_sampled"]
    curve = []
    best = -1.0
    deadline = time.monotonic() + max_minutes * 60
    last = r
    while time.monotonic() < deadline:
        last = algo.train()
        ret = last["episode_return_mean"]
        best = max(best, ret)
        curve.append(round(ret, 3))
        if best >= RETURN_THRESHOLD:
            break
    dt = time.perf_counter() - t0
    steps = last["num_env_steps_sampled"] - steps0
    out = {
        "bench": "impala_image",
        "env": "Catch-v0 (pixel [10,10,1] obs, CNN module)",
        "num_env_runners": num_runners,
        "num_envs_per_runner": 2,
        "host_cores": os.cpu_count(),
        "env_steps": steps,
        "samples_per_s": round(steps / dt, 1),
        "episode_return_best": round(best, 3),
        "return_threshold": RETURN_THRESHOLD,
        "threshold_reached": best >= RETURN_THRESHOLD,
        "learner_updates_total": last["training_iteration"],
        "return_curve_tail": curve[-20:],
    }
    algo.stop()
    return out


def main():
    import ray_tpu as rt

    num_runners = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    max_minutes = float(sys.argv[2]) if len(sys.argv) > 2 else 20.0

    # resource fiction on a small box: the point is control-plane scale
    rt.init(num_cpus=max(num_runners + 8, os.cpu_count() or 1),
            resources={"TPU": 8})
    try:
        out = _bench_body(num_runners, max_minutes)
    finally:
        rt.shutdown()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "RL_BENCH.json")
    doc = {}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    doc["impala_image"] = out
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
