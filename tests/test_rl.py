"""RL library tests: env physics, GAE, fault-tolerant fleet, PPO learning
(ref analogs: rllib tests + tuned_examples learning assertions)."""

import numpy as np
import pytest

from ray_tpu.rl.env import CartPoleVectorEnv
from ray_tpu.rl.learner import compute_gae


def test_cartpole_env_basics():
    env = CartPoleVectorEnv(num_envs=4, seed=0)
    obs = env.reset(0)
    assert obs.shape == (4, 4)
    total_done = 0
    for _ in range(300):
        obs, rew, term, trunc, _ = env.step(np.random.randint(0, 2, 4))
        assert obs.shape == (4, 4) and rew.shape == (4,)
        total_done += int((term | trunc).sum())
    # random policy falls over well before 300 steps
    assert total_done > 0


def test_cartpole_balancing_vs_random():
    """A crude hand policy (push toward the pole lean) survives longer
    than random — sanity-checks the dynamics' sign conventions."""
    def run(policy):
        env = CartPoleVectorEnv(num_envs=8, seed=1)
        obs = env.reset(1)
        lengths = []
        steps = np.zeros(8)
        for _ in range(200):
            acts = policy(obs)
            obs, _, term, trunc, _ = env.step(acts)
            done = term | trunc
            steps += 1
            for i in np.nonzero(done)[0]:
                lengths.append(steps[i])
                steps[i] = 0
        return np.mean(lengths) if lengths else 200.0

    rng = np.random.RandomState(0)
    random_len = run(lambda obs: rng.randint(0, 2, len(obs)))
    lean_len = run(lambda obs: (obs[:, 2] > 0).astype(int))
    assert lean_len > random_len


def test_gae_matches_naive():
    T, N = 5, 2
    rng = np.random.RandomState(0)
    rewards = rng.randn(T, N).astype(np.float32)
    values = rng.randn(T, N).astype(np.float32)
    dones = np.zeros((T, N), bool)
    dones[2, 0] = True
    last = rng.randn(N).astype(np.float32)
    gamma, lam = 0.9, 0.8
    adv, ret = compute_gae(rewards, values, dones, last, gamma, lam)

    # naive per-env recursion
    for n in range(N):
        gae = 0.0
        next_v = last[n]
        expect = np.zeros(T)
        for t in range(T - 1, -1, -1):
            nonterm = 0.0 if dones[t, n] else 1.0
            delta = rewards[t, n] + gamma * next_v * nonterm - values[t, n]
            gae = delta + gamma * lam * nonterm * gae
            expect[t] = gae
            next_v = values[t, n]
        np.testing.assert_allclose(adv[:, n], expect, rtol=1e-5)
    np.testing.assert_allclose(ret, adv + values, rtol=1e-6)


def test_fault_tolerant_actor_manager(local_cluster):
    import ray_tpu as rt
    from ray_tpu.rl.actor_manager import FaultTolerantActorManager

    @rt.remote
    class W:
        def __init__(self):
            self.n = 0

        def work(self):
            self.n += 1
            return self.n

        def ping(self):
            return True

    actors = [W.remote() for _ in range(3)]
    mgr = FaultTolerantActorManager(actors)
    assert mgr.foreach(lambda a: a.work.remote()) == [1, 1, 1]
    rt.kill(actors[1])
    results = mgr.foreach(lambda a: a.work.remote(), timeout=30)
    assert len(results) == 2  # dead actor dropped, marked unhealthy
    assert mgr.num_healthy == 2
    results = mgr.foreach(lambda a: a.work.remote())
    assert len(results) == 2


def test_ppo_learns_cartpole(local_cluster):
    from ray_tpu.rl import PPOConfig

    algo = PPOConfig(
        num_env_runners=2, num_envs_per_runner=8,
        rollout_fragment_length=64, lr=1e-3, entropy_coeff=0.0,
        minibatch_size=256, num_epochs=6, seed=3).build()
    first = None
    best = 0.0
    for i in range(25):
        result = algo.train()
        ret = result["episode_return_mean"]
        if first is None and ret > 0:
            first = ret
        best = max(best, ret)
        if best >= 80.0 and i >= 4:
            break
    algo.stop()
    assert first is not None, "no episodes completed"
    assert best >= 80.0, f"PPO failed to learn: first={first} best={best}"
    assert best > 2 * min(first, 40.0)


def test_ppo_checkpoint_roundtrip(local_cluster, tmp_path):
    from ray_tpu.rl import PPOConfig

    algo = PPOConfig(num_env_runners=1, num_envs_per_runner=4,
                     rollout_fragment_length=16, seed=0).build()
    algo.train()
    path = algo.save_to_path(str(tmp_path / "ck"))
    it = algo._iteration
    algo.stop()

    algo2 = PPOConfig(num_env_runners=1, num_envs_per_runner=4,
                      rollout_fragment_length=16, seed=0).build()
    algo2.restore_from_path(path)
    assert algo2._iteration == it
    w1 = algo2._weights["pi"]["w"]
    result = algo2.train()
    assert result["training_iteration"] == it + 1
    algo2.stop()


def test_vtrace_on_policy_reduces_to_returns():
    """With target == behavior policy and rho/c clips inactive, vs equals
    the discounted TD(lambda=1)-style corrected values; sanity-check the
    recursion against a tiny hand-rolled rollout."""
    import jax.numpy as jnp

    from ray_tpu.rl.vtrace import vtrace

    T, B = 4, 1
    logp = np.log(np.full((T, B), 0.5, np.float32))
    rewards = np.ones((T, B), np.float32)
    values = np.zeros((T, B), np.float32)
    boot = np.zeros((B,), np.float32)
    dones = np.zeros((T, B), np.float32)
    vs, pg_adv = vtrace(jnp.asarray(logp), jnp.asarray(logp),
                        jnp.asarray(rewards), jnp.asarray(values),
                        jnp.asarray(boot), jnp.asarray(dones),
                        jnp.zeros((T, B), jnp.float32), gamma=1.0)
    # on-policy, V=0, gamma=1: vs_t = sum of future rewards
    np.testing.assert_allclose(np.asarray(vs)[:, 0], [4, 3, 2, 1], atol=1e-5)
    np.testing.assert_allclose(np.asarray(pg_adv)[:, 0], [4, 3, 2, 1],
                               atol=1e-5)


def test_vtrace_done_cuts_bootstrap():
    import jax.numpy as jnp

    from ray_tpu.rl.vtrace import vtrace

    T, B = 3, 1
    logp = np.zeros((T, B), np.float32)
    rewards = np.ones((T, B), np.float32)
    values = np.zeros((T, B), np.float32)
    dones = np.array([[0.0], [1.0], [0.0]], np.float32)
    vs, _ = vtrace(jnp.asarray(logp), jnp.asarray(logp),
                   jnp.asarray(rewards), jnp.asarray(values),
                   jnp.asarray(np.full((B,), 100.0, np.float32)),
                   jnp.asarray(dones), jnp.zeros((T, B), jnp.float32),
                   gamma=1.0)
    # episode ends at t=1: vs[0] = r0 + r1 = 2 (no leak across the cut);
    # vs[2] bootstraps into the final value
    np.testing.assert_allclose(np.asarray(vs)[:, 0], [2.0, 1.0, 101.0],
                               atol=1e-5)


def test_impala_learns_cartpole(local_cluster):
    """Learning-curve gate (ref: rllib tuned_examples --as-test): IMPALA
    must reach a mean return well above the random baseline (~20).

    Doubles as the compiled-DAG plane + throughput gate: the loop must
    ride the channel DAG (Podracer Sebulba shape — no per-call
    fallback) and sustain committed env-steps/s + learner-updates/s
    floors across the learning run (measured ~1270 steps/s / ~1.2
    updates/s on a loaded 1-core CI box; floors sit ~5x below)."""
    import time

    from ray_tpu.dag.channel_exec import ChannelCompiledDAG
    from ray_tpu.rl import IMPALA, IMPALAConfig

    algo = IMPALAConfig(
        env="CartPole-v1", num_env_runners=2, num_envs_per_runner=8,
        rollout_fragment_length=64, train_batch_size=512, vf_coeff=0.25,
        lr=1e-3, entropy_coeff=0.01, seed=1).build()
    best = 0.0
    try:
        assert isinstance(algo._dag.dag, ChannelCompiledDAG), \
            "IMPALA fell back off the compiled-DAG plane"
        assert algo._dag.channel_kinds["shm"] > 0
        # device edges are ON by default (ISSUE 12): agg→learner
        # batches, learner→driver weights, and the weight-broadcast
        # input edges all ride the raw-shard-bytes framing
        assert algo._dag.channel_kinds["device"] > 0, \
            algo._dag.channel_kinds
        algo.train()                      # warmup (jit compile)
        s0 = algo._total_steps
        t0 = time.perf_counter()
        updates = 0
        for _ in range(40):
            result = algo.train()
            updates += result["num_learner_updates"]
            best = max(best, result["episode_return_mean"])
            if best >= 100.0:
                break
        dt = time.perf_counter() - t0
        assert best >= 100.0, f"IMPALA failed to learn: best={best}"
        steps_per_s = (algo._total_steps - s0) / dt
        assert steps_per_s >= 250.0, \
            f"IMPALA-on-DAG env throughput regressed: {steps_per_s:.0f}/s"
        assert updates / dt >= 0.25, \
            f"IMPALA-on-DAG update rate regressed: {updates / dt:.2f}/s"
        # zero-host-pickle acceptance: the steady-state tick path
        # actually shipped weight arrays through the device framing —
        # the driver-side input wrappers counted packed jax leaves
        # (learning happened, so broadcasts happened), and
        # pack_device_tree leaves no jax.Array for pickle to see
        # (tests/test_dag_device.py asserts the pack coverage itself)
        import jax

        import ray_tpu as rt
        from ray_tpu.dag.device_channel import pack_device_tree

        dev_inputs = algo._dag.dag._device_input_channels
        assert dev_inputs, "weight-broadcast edges are not device-kind"
        assert sum(ch.device_arrays for ch in dev_inputs) > 0, \
            "no weight arrays rode the device framing"
        w = rt.get(algo._learner.get_weights.remote(), timeout=60)
        packed, n = pack_device_tree(
            jax.tree.map(jax.numpy.asarray, w))
        assert n == len(jax.tree.leaves(w))    # full pack coverage
    finally:
        algo.stop()


def test_replay_buffer_ring_and_sampling():
    from ray_tpu.rl.replay import ReplayBuffer

    buf = ReplayBuffer(capacity=10, seed=0)
    buf.add({"x": np.arange(6, dtype=np.float32),
             "a": np.arange(6, dtype=np.int32)})
    assert buf.size() == 6
    buf.add({"x": np.arange(6, 14, dtype=np.float32),
             "a": np.arange(6, 14, dtype=np.int32)})
    assert buf.size() == 10  # capacity-capped ring
    s = buf.sample(4)
    assert s["x"].shape == (4,) and s["a"].shape == (4,)
    np.testing.assert_array_equal(s["x"].astype(np.int32), s["a"])
    # the oldest entries (0..3) were overwritten by the wrap
    many = buf.sample(10)
    assert many["x"].min() >= 4.0
    assert buf.sample(11) is None


def test_dqn_learns_cartpole(local_cluster):
    """Learning gate (ref: rllib tuned_examples --as-test thresholds)."""
    from ray_tpu.rl.dqn import DQNConfig

    algo = DQNConfig(
        env="CartPole-v1", num_env_runners=2, num_envs_per_runner=8,
        rollout_fragment_length=32, learning_starts=500,
        train_batch_size=128, updates_per_iteration=48,
        target_update_freq=50, epsilon_decay_steps=4000,
        lr=1e-3, seed=0).build()
    first, best = None, -1.0
    try:
        for i in range(70):
            result = algo.train()
            ret = result["episode_return_mean"]
            if ret is not None:
                if first is None:
                    first = ret
                best = max(best, ret)
            if best >= 120.0:
                break
    finally:
        algo.stop()
    assert first is not None, "no episodes completed"
    assert best >= 120.0, f"DQN failed to learn: first={first} best={best}"


# ----------------------------------------------------- image RL (round 4)
def test_catch_env_mechanics():
    from ray_tpu.rl.env import CatchVectorEnv

    env = CatchVectorEnv(num_envs=4, seed=0)
    obs = env.reset(0)
    assert obs.shape == (4, 10, 10, 1)
    assert obs.sum(axis=(1, 2, 3)).max() <= 2.0  # fruit + paddle pixels
    total_reward = np.zeros(4)
    dones = 0
    for _ in range(30):
        obs, r, term, trunc, _ = env.step(np.ones(4, np.int64))  # stay
        total_reward += r
        dones += int(term.sum())
    assert dones >= 4  # fruit lands within GRID steps, episodes recycle
    assert np.all(np.abs(total_reward) >= 1.0)  # every env saw an outcome


def test_cnn_module_forward_and_grad():
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.rl import module as rlm

    cfg = rlm.CNNModuleConfig(obs_shape=(10, 10, 1), num_actions=3)
    params = rlm.init_params(cfg, jax.random.PRNGKey(0))
    obs = jnp.zeros((5, 10, 10, 1), jnp.float32)
    logits, value = rlm.forward(params, obs)
    assert logits.shape == (5, 3) and value.shape == (5,)

    # optimizer round-trip: conv stride metadata must be invisible to
    # gradients/updates (static pytree node)
    opt = optax.adam(1e-3)
    state = opt.init(params)

    def loss(p):
        lg, v = rlm.forward(p, obs)
        return (lg ** 2).mean() + (v ** 2).mean()

    grads = jax.grad(loss)(params)
    updates, state = opt.update(grads, state, params)
    new_params = optax.apply_updates(params, updates)
    assert new_params["conv"][0]["meta"].stride == 2

    # sampling path used by env runners
    a, logp, v = rlm.sample_actions(params, np.zeros((3, 10, 10, 1),
                                                     np.float32),
                                    jax.random.PRNGKey(1))
    assert a.shape == (3,) and logp.shape == (3,)


def test_connector_pipeline():
    from ray_tpu.rl.connectors import (ConnectorPipeline, FlattenObs,
                                       NormalizeImage)

    pipe = ConnectorPipeline([NormalizeImage(), FlattenObs()])
    obs = np.full((2, 4, 4, 1), 255, np.uint8)
    out = pipe(obs)
    assert out.shape == (2, 16)
    assert out.dtype == np.float32 and float(out.max()) == 1.0


def test_impala_learns_catch_with_cnn(local_cluster):
    """Config #4 shape at CI scale: image observations stream from the
    runner fleet into a CNN V-trace learner; mean return must clear a
    committed threshold well above the random policy (~-0.8)."""
    from ray_tpu.rl.impala import IMPALAConfig
    from ray_tpu.rl.module import CNNModuleConfig

    algo = IMPALAConfig(
        env="Catch-v0", num_env_runners=2, num_envs_per_runner=16,
        rollout_fragment_length=32, train_batch_size=1024,
        # fine iteration granularity: the break-on-threshold check below
        # runs every 2 updates, so the CNN learner does little work past
        # the committed bar (keeps the test inside its CI budget)
        min_updates_per_iteration=2,
        lr=3e-3, entropy_coeff=0.01, seed=0).build()
    assert isinstance(algo.module_cfg, CNNModuleConfig)
    try:
        first = None
        best = -1.0
        for _ in range(60):
            result = algo.train()
            if first is None and result["episode_return_mean"] != 0.0:
                first = result["episode_return_mean"]
            best = max(best, result["episode_return_mean"])
            if best >= -0.2:
                break
        # random policy sits at ~-0.8; the threshold is a clear learning
        # signal within the test budget
        assert best >= -0.2, \
            f"CNN IMPALA failed to learn Catch: best={best} first={first}"
    finally:
        algo.stop()


def test_appo_learns(local_cluster):
    """APPO (ref: algorithms/appo): IMPALA's async pipeline with the
    clipped-surrogate objective learns CartPole."""
    from ray_tpu.rl import APPOConfig

    algo = APPOConfig(
        env="CartPole-v1", num_env_runners=2, num_envs_per_runner=4,
        rollout_fragment_length=32, train_batch_size=512,
        call_timeout_s=600.0, seed=0).build()
    try:
        first = algo.train()
        last = first
        # 5 more iterations at min_updates_per_iteration=4 ≈ 24 learner
        # updates on the compiled-DAG plane — the curve moves decisively
        # (measured ~22 → ~33-42 mean return) where the old per-call
        # loop barely budged in 9 iterations
        for _ in range(5):
            last = algo.train()
        assert last["episode_return_mean"] > first["episode_return_mean"]
        assert last["num_env_steps_sampled"] > 0
    finally:
        algo.stop()
