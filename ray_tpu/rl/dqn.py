"""DQN — value-based off-policy algorithm (ref analogs:
rllib/algorithms/dqn/dqn.py + dqn_rainbow_learner.py: replay-buffer
training loop, target network, double-Q; the learner math is an
independent jitted JAX implementation).

Dataflow: DQNRunner actors step envs with epsilon-greedy over Q =
module logits -> transitions into a ReplayBuffer actor -> driver samples
minibatches -> jitted double-DQN Huber TD update -> periodic hard target
sync -> weights broadcast to runners (same weight-sync pattern as PPO).
"""

from __future__ import annotations

import dataclasses
import time

import cloudpickle
import numpy as np

import ray_tpu as rt
from ray_tpu.rl.actor_manager import FaultTolerantActorManager
from ray_tpu.rl.env import make_vector_env, require_discrete
from ray_tpu.rl.module import MLPModuleConfig
from ray_tpu.rl.replay import ReplayBuffer, ReplayRolloutMixin


class DQNRunner(ReplayRolloutMixin):
    """Epsilon-greedy rollout actor producing replay transitions."""

    def __init__(self, env_name: str, num_envs: int, seed: int,
                 module_cfg_blob: bytes):
        self.env = make_vector_env(env_name, num_envs, seed)
        self.module_cfg = cloudpickle.loads(module_cfg_blob)
        self._rng = np.random.default_rng(seed)
        self._obs = self.env.reset(seed)
        self._params = None
        self._ep_return = np.zeros(num_envs, np.float32)
        self._completed: list[float] = []

    def set_weights(self, params) -> bool:
        self._params = params
        return True

    def sample(self, num_steps: int, epsilon: float) -> dict:
        """[T*N] flat transition arrays + completed episode returns."""
        import jax.numpy as jnp

        from ray_tpu.rl import module as rlm

        assert self._params is not None, "set_weights first"
        N = self.env.num_envs

        def select(obs):
            q, _ = rlm.forward(self._params, jnp.asarray(obs))
            greedy = np.asarray(jnp.argmax(q, axis=-1))
            explore = self._rng.random(N) < epsilon
            return np.where(
                explore,
                self._rng.integers(0, self.module_cfg.num_actions, N),
                greedy).astype(np.int32)

        return self._rollout(num_steps, select)

    def ping(self) -> bool:
        return True


@dataclasses.dataclass
class DQNConfig:
    env: str = "CartPole-v1"
    num_env_runners: int = 2
    num_envs_per_runner: int = 8
    rollout_fragment_length: int = 32
    hidden: tuple = (64, 64)
    lr: float = 1e-3
    gamma: float = 0.99
    buffer_capacity: int = 50_000
    learning_starts: int = 1_000
    train_batch_size: int = 128
    updates_per_iteration: int = 16
    target_update_freq: int = 100       # updates between hard target syncs
    epsilon_initial: float = 1.0
    epsilon_final: float = 0.05
    epsilon_decay_steps: int = 10_000
    double_q: bool = True
    seed: int = 0

    def build(self) -> "DQN":
        return DQN(self)


class DQN:
    def __init__(self, config: DQNConfig):
        import jax
        import jax.numpy as jnp
        import optax

        self.config = config
        probe = make_vector_env(config.env, 1, config.seed)
        require_discrete(probe, "DQN")
        self.module_cfg = MLPModuleConfig(
            observation_size=probe.observation_size,
            num_actions=probe.num_actions, hidden=config.hidden)
        from ray_tpu.rl import module as rlm

        self.params = rlm.init_params(
            self.module_cfg, jax.random.PRNGKey(config.seed))
        self.target_params = jax.tree.map(lambda x: x, self.params)
        self._opt = optax.adam(config.lr)
        self._opt_state = self._opt.init(self.params)
        gamma, double_q = config.gamma, config.double_q

        def td_loss(params, target_params, batch):
            q, _ = rlm.forward(params, batch["obs"])
            q_sa = q[jnp.arange(q.shape[0]), batch["actions"]]
            q_next_target, _ = rlm.forward(target_params, batch["next_obs"])
            if double_q:
                q_next_online, _ = rlm.forward(params, batch["next_obs"])
                next_a = jnp.argmax(q_next_online, axis=-1)
            else:
                next_a = jnp.argmax(q_next_target, axis=-1)
            q_next = q_next_target[jnp.arange(q.shape[0]), next_a]
            target = batch["rewards"] + gamma * q_next * (
                1.0 - batch["dones"].astype(jnp.float32))
            target = jax.lax.stop_gradient(target)
            return optax.huber_loss(q_sa, target).mean()

        def update(params, target_params, opt_state, batch):
            loss, grads = jax.value_and_grad(td_loss)(
                params, target_params, batch)
            updates, opt_state = self._opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        self._update = jax.jit(update)

        blob = cloudpickle.dumps(self.module_cfg)
        runner_cls = rt.remote(num_cpus=1)(DQNRunner)
        self._runners = FaultTolerantActorManager([
            runner_cls.remote(config.env, config.num_envs_per_runner,
                              config.seed + 1 + i, blob)
            for i in range(config.num_env_runners)])
        self._buffer = rt.remote(num_cpus=0)(ReplayBuffer).remote(
            config.buffer_capacity, config.seed)
        self._broadcast_weights()
        self._iteration = 0
        self._env_steps = 0
        self._updates = 0
        self._last_returns: list[float] = []

    # ------------------------------------------------------------------ api
    def _broadcast_weights(self):
        ref = rt.put(self.params)
        self._runners.foreach(lambda a: a.set_weights.remote(ref))

    def _epsilon(self) -> float:
        c = self.config
        frac = min(1.0, self._env_steps / max(1, c.epsilon_decay_steps))
        return c.epsilon_initial + frac * (c.epsilon_final
                                           - c.epsilon_initial)

    def train(self) -> dict:
        import jax.numpy as jnp

        c = self.config
        t0 = time.monotonic()
        eps = self._epsilon()
        samples = self._runners.foreach(
            lambda a: a.sample.remote(c.rollout_fragment_length, eps))
        returns = []
        for s in samples:
            self._env_steps += s["steps"]
            returns.extend(s["episode_returns"])
            rt.get(self._buffer.add.remote(s["transitions"]), timeout=60)
        losses = []
        if self._env_steps >= c.learning_starts:
            for _ in range(c.updates_per_iteration):
                batch = rt.get(
                    self._buffer.sample.remote(c.train_batch_size),
                    timeout=60)
                if batch is None:
                    break
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                self.params, self._opt_state, loss = self._update(
                    self.params, self.target_params, self._opt_state, batch)
                losses.append(float(loss))
                self._updates += 1
                if self._updates % c.target_update_freq == 0:
                    import jax

                    self.target_params = jax.tree.map(
                        lambda x: x, self.params)
            self._broadcast_weights()
        self._iteration += 1
        self._last_returns = (self._last_returns + returns)[-100:]
        mean_ret = (float(np.mean(self._last_returns))
                    if self._last_returns else None)
        return {
            "training_iteration": self._iteration,
            "env_steps": self._env_steps,
            "num_updates": self._updates,
            "epsilon": eps,
            "episode_return_mean": mean_ret,
            "loss": float(np.mean(losses)) if losses else None,
            "time_s": time.monotonic() - t0,
        }

    def stop(self):
        for a, _kill in [(self._buffer, None)] + [
                (r, None) for r in self._runners._actors]:
            try:
                rt.kill(a)
            except Exception:
                pass
