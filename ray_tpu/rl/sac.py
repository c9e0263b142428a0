"""SAC — soft actor-critic for continuous control (ref analogs:
rllib/algorithms/sac/sac.py + sac_learner.py: twin Q critics, squashed
Gaussian actor, automatic entropy-temperature tuning; the learner math is
an independent jitted JAX implementation, Haarnoja et al. 2018).

Dataflow mirrors DQN's off-policy loop: SACRunner actors step continuous
envs sampling from the tanh-Gaussian policy -> transitions into a
ReplayBuffer actor -> driver samples minibatches -> one jitted update
does critic + actor + alpha steps and the polyak target move -> weights
broadcast back to runners.
"""

from __future__ import annotations

import dataclasses
import time

import cloudpickle
import numpy as np

import ray_tpu as rt
from ray_tpu.rl.actor_manager import FaultTolerantActorManager
from ray_tpu.rl.env import make_vector_env
from ray_tpu.rl.module import ContinuousModuleConfig
from ray_tpu.rl.replay import ReplayBuffer, ReplayRolloutMixin


class SACRunner(ReplayRolloutMixin):
    """Rollout actor sampling from the squashed-Gaussian policy."""

    def __init__(self, env_name: str, num_envs: int, seed: int,
                 module_cfg_blob: bytes):
        import jax

        self.env = make_vector_env(env_name, num_envs, seed)
        self.module_cfg = cloudpickle.loads(module_cfg_blob)
        self._key = jax.random.PRNGKey(seed)
        self._obs = self.env.reset(seed)
        self._actor = None
        self._ep_return = np.zeros(num_envs, np.float32)
        self._completed: list[float] = []

    def set_weights(self, actor_params) -> bool:
        self._actor = actor_params
        return True

    def sample(self, num_steps: int, random_actions: bool = False) -> dict:
        """[T*N] flat transition arrays + completed episode returns.

        random_actions drives uniform exploration before learning starts
        (the reference's `num_steps_sampled_before_learning_starts`)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.rl import module as rlm

        cfg = self.module_cfg
        N, A, high = self.env.num_envs, cfg.action_size, cfg.action_high

        def select(obs):
            self._key, k = jax.random.split(self._key)
            if random_actions or self._actor is None:
                return np.asarray(jax.random.uniform(
                    k, (N, A), minval=-high, maxval=high), np.float32)
            mean, log_std = rlm.actor_forward(self._actor, jnp.asarray(obs))
            a, _ = rlm.sample_squashed(mean, log_std, k, high)
            return np.asarray(a, np.float32)

        return self._rollout(num_steps, select)

    def ping(self) -> bool:
        return True


@dataclasses.dataclass
class SACConfig:
    env: str = "Pendulum-v1"
    num_env_runners: int = 2
    num_envs_per_runner: int = 8
    rollout_fragment_length: int = 32
    hidden: tuple = (64, 64)
    actor_lr: float = 3e-4
    critic_lr: float = 3e-4
    alpha_lr: float = 3e-4
    gamma: float = 0.99
    tau: float = 0.005                  # polyak rate for target critics
    initial_alpha: float = 1.0
    target_entropy: float | None = None  # default: -action_size
    buffer_capacity: int = 100_000
    learning_starts: int = 1_000
    train_batch_size: int = 128
    updates_per_iteration: int = 16
    seed: int = 0

    def build(self) -> "SAC":
        return SAC(self)


class SAC:
    def __init__(self, config: SACConfig):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.rl import module as rlm

        self.config = config
        probe = make_vector_env(config.env, 1, config.seed)
        if not probe.continuous:
            raise ValueError(
                f"SAC needs a continuous-action env; {config.env!r} is "
                "discrete (use DQN/PPO, or give the env `continuous=True` "
                "with `action_size`/`action_high`)")
        self.module_cfg = ContinuousModuleConfig(
            observation_size=probe.observation_size,
            action_size=probe.action_size,
            action_high=float(probe.action_high), hidden=config.hidden)
        self.params = rlm.init_continuous_params(
            self.module_cfg, jax.random.PRNGKey(config.seed))
        self.target_q = jax.tree.map(
            lambda x: x, {"q1": self.params["q1"], "q2": self.params["q2"]})
        self.log_alpha = jnp.asarray(
            np.log(config.initial_alpha), jnp.float32)
        target_entropy = (config.target_entropy
                          if config.target_entropy is not None
                          else -float(self.module_cfg.action_size))

        self._actor_opt = optax.adam(config.actor_lr)
        self._critic_opt = optax.adam(config.critic_lr)
        self._alpha_opt = optax.adam(config.alpha_lr)
        self._opt_state = {
            "actor": self._actor_opt.init(self.params["actor"]),
            "critic": self._critic_opt.init(
                {"q1": self.params["q1"], "q2": self.params["q2"]}),
            "alpha": self._alpha_opt.init(self.log_alpha),
        }
        gamma, tau = config.gamma, config.tau
        high = self.module_cfg.action_high

        def critic_loss(q_params, params, target_q, log_alpha, batch, key):
            mean, log_std = rlm.actor_forward(params["actor"],
                                              batch["next_obs"])
            next_a, next_logp = rlm.sample_squashed(mean, log_std, key, high)
            tq1 = rlm.q_forward(target_q["q1"], batch["next_obs"], next_a)
            tq2 = rlm.q_forward(target_q["q2"], batch["next_obs"], next_a)
            alpha = jnp.exp(log_alpha)
            soft_q = jnp.minimum(tq1, tq2) - alpha * next_logp
            target = batch["rewards"] + gamma * soft_q * (
                1.0 - batch["dones"].astype(jnp.float32))
            target = jax.lax.stop_gradient(target)
            q1 = rlm.q_forward(q_params["q1"], batch["obs"], batch["actions"])
            q2 = rlm.q_forward(q_params["q2"], batch["obs"], batch["actions"])
            return (((q1 - target) ** 2).mean()
                    + ((q2 - target) ** 2).mean())

        def actor_loss(actor_params, params, log_alpha, batch, key):
            mean, log_std = rlm.actor_forward(actor_params, batch["obs"])
            a, logp = rlm.sample_squashed(mean, log_std, key, high)
            q1 = rlm.q_forward(params["q1"], batch["obs"], a)
            q2 = rlm.q_forward(params["q2"], batch["obs"], a)
            alpha = jax.lax.stop_gradient(jnp.exp(log_alpha))
            return (alpha * logp - jnp.minimum(q1, q2)).mean(), logp

        def update(params, target_q, log_alpha, opt_state, batch, key):
            kc, ka = jax.random.split(key)
            q_params = {"q1": params["q1"], "q2": params["q2"]}
            closs, cgrads = jax.value_and_grad(critic_loss)(
                q_params, params, target_q, log_alpha, batch, kc)
            cupd, opt_c = self._critic_opt.update(
                cgrads, opt_state["critic"], q_params)
            q_params = optax.apply_updates(q_params, cupd)
            params = {**params, **q_params}

            (aloss, logp), agrads = jax.value_and_grad(
                actor_loss, has_aux=True)(
                params["actor"], params, log_alpha, batch, ka)
            aupd, opt_a = self._actor_opt.update(
                agrads, opt_state["actor"], params["actor"])
            params = {**params,
                      "actor": optax.apply_updates(params["actor"], aupd)}

            # alpha step: loss(log_alpha) = E[-log_alpha*(logp + H_target)]
            # so grad = -(logp + H_target).mean(); entropy below target
            # (logp + H_target > 0) pushes log_alpha UP -> more exploration
            entropy_gap = jax.lax.stop_gradient(logp) + target_entropy
            alpha_grad = -entropy_gap.mean()
            alupd, opt_al = self._alpha_opt.update(
                alpha_grad, opt_state["alpha"], log_alpha)
            log_alpha = optax.apply_updates(log_alpha, alupd)

            target_q = jax.tree.map(
                lambda t, s: (1.0 - tau) * t + tau * s, target_q, q_params)
            opt_state = {"actor": opt_a, "critic": opt_c, "alpha": opt_al}
            stats = {"critic_loss": closs, "actor_loss": aloss,
                     "alpha": jnp.exp(log_alpha), "entropy": -logp.mean()}
            return params, target_q, log_alpha, opt_state, stats

        self._update = jax.jit(update)
        self._key = jax.random.PRNGKey(config.seed + 1)

        blob = cloudpickle.dumps(self.module_cfg)
        runner_cls = rt.remote(num_cpus=1)(SACRunner)
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
        ref = rt.put(self.params["actor"])
        self._runners.foreach(lambda a: a.set_weights.remote(ref))

    def train(self) -> dict:
        import jax
        import jax.numpy as jnp

        c = self.config
        t0 = time.monotonic()
        warmup = self._env_steps < c.learning_starts
        samples = self._runners.foreach(
            lambda a: a.sample.remote(c.rollout_fragment_length, warmup))
        returns = []
        for s in samples:
            self._env_steps += s["steps"]
            returns.extend(s["episode_returns"])
            rt.get(self._buffer.add.remote(s["transitions"]), timeout=60)
        stats = None
        if self._env_steps >= c.learning_starts:
            for _ in range(c.updates_per_iteration):
                batch = rt.get(
                    self._buffer.sample.remote(c.train_batch_size),
                    timeout=60)
                if batch is None:
                    break
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                self._key, k = jax.random.split(self._key)
                (self.params, self.target_q, self.log_alpha,
                 self._opt_state, stats) = self._update(
                    self.params, self.target_q, self.log_alpha,
                    self._opt_state, batch, k)
                self._updates += 1
            self._broadcast_weights()
        self._iteration += 1
        self._last_returns = (self._last_returns + returns)[-100:]
        mean_ret = (float(np.mean(self._last_returns))
                    if self._last_returns else None)
        out = {
            "training_iteration": self._iteration,
            "env_steps": self._env_steps,
            "num_updates": self._updates,
            "episode_return_mean": mean_ret,
            "time_s": time.monotonic() - t0,
        }
        if stats is not None:
            out.update({k: float(v) for k, v in stats.items()})
        return out

    def policy_mean(self, obs: np.ndarray) -> np.ndarray:
        """Deterministic (mean) action for evaluation."""
        import jax.numpy as jnp

        from ray_tpu.rl import module as rlm

        mean, _ = rlm.actor_forward(self.params["actor"], jnp.asarray(obs))
        return np.asarray(jnp.tanh(mean) * self.module_cfg.action_high)

    def stop(self):
        for a in [self._buffer] + list(self._runners._actors):
            try:
                rt.kill(a)
            except Exception:
                pass
