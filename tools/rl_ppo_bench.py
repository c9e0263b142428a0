"""PPO CartPole benchmark: records learner throughput (samples/s) and
the return curve into RL_BENCH.json under "ppo_cartpole".

BASELINE config #1 (rllib/tuned_examples PPO on CartPole-v1) artifact:
the reference's tuned example targets return >=150 on CartPole; this
records both the sustained sample rate through the sample -> GAE ->
update -> broadcast loop and the learning curve that proves the rate
is of a learning run, not a no-op loop.

Usage: python tools/rl_ppo_bench.py [num_runners] [iters]
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


def _bench_body(num_runners: int, iters: int) -> dict:
    from ray_tpu.rl.ppo import PPOConfig

    algo = PPOConfig(
        env="CartPole-v1",
        num_env_runners=num_runners,
        num_envs_per_runner=8,
        rollout_fragment_length=128,
        minibatch_size=1024,
        num_epochs=6,
        entropy_coeff=0.003,
        lr=4e-4,
        seed=0).build()
    r = algo.train()  # warmup: compile the learner update
    curve = [r["episode_return_mean"]]
    steps = 0
    t0 = time.perf_counter()
    for _ in range(iters):
        r = algo.train()
        steps += r["num_env_steps_sampled"]
        curve.append(r["episode_return_mean"])
    dt = time.perf_counter() - t0
    out = {
        "bench": "ppo_cartpole",
        "num_env_runners": num_runners,
        "num_envs_per_runner": 8,
        "rollout_fragment_length": 128,
        "host_cores": os.cpu_count(),
        "iterations": iters,
        "env_steps": steps,
        "samples_per_s": round(steps / dt, 1),
        "episode_return_mean_final": r["episode_return_mean"],
        "episode_return_best": max(curve),
        "return_curve": [round(c, 1) for c in curve],
    }
    algo.stop()
    return out


def main():
    import ray_tpu as rt

    num_runners = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 20

    rt.init(num_cpus=max(num_runners + 4, os.cpu_count() or 1))
    try:
        out = _bench_body(num_runners, iters)
    finally:
        rt.shutdown()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "RL_BENCH.json")
    existing = {}
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
    existing["ppo_cartpole"] = out
    with open(path, "w") as f:
        json.dump(existing, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
