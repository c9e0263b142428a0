"""JaxLearner + LearnerGroup (ref analogs: rllib/core/learner/learner.py:109
`compute_losses/compute_gradients`, learner_group.py:80, DDP wrapping in
torch_learner.py:409).

TPU-first: the whole PPO update (GAE, minibatch epochs, clipped losses,
optimizer) is one jitted function on the learner's devices; multi-learner
data parallelism averages gradients over the host-plane collective group
(cross-host path — in-slice DP is a mesh axis inside the jit)."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np


@dataclasses.dataclass
class PPOLearnerConfig:
    lr: float = 3e-4
    gamma: float = 0.99
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    vf_coeff: float = 0.5
    entropy_coeff: float = 0.01
    num_epochs: int = 4
    minibatch_size: int = 256
    max_grad_norm: float = 0.5


def compute_gae(rewards, values, dones, last_value, gamma, lam,
                trunc_values=None):
    """[T, N] arrays -> (advantages, returns), numpy (host side).

    `trunc_values[t, i]` is V(final_obs) where env i was *truncated*
    (time-limit cut, not a true terminal) at step t, 0 elsewhere: the GAE
    recursion still cuts at those steps, but the bootstrap target is the
    critic's value of the final state instead of 0.
    """
    T = rewards.shape[0]
    adv = np.zeros_like(rewards)
    gae = np.zeros(rewards.shape[1], rewards.dtype)
    next_value = last_value
    for t in range(T - 1, -1, -1):
        nonterminal = 1.0 - dones[t].astype(rewards.dtype)
        boot = next_value * nonterminal
        if trunc_values is not None:
            boot = boot + trunc_values[t]
        delta = rewards[t] + gamma * boot - values[t]
        gae = delta + gamma * lam * nonterminal * gae
        adv[t] = gae
        next_value = values[t]
    return adv, adv + values


def build_ppo_batch(samples: list, gamma: float, lam: float):
    """Fold sampled [T, N] trajectories into one flat PPO batch:
    GAE per trajectory, flatten, concat. Shared by the single-agent and
    multi-agent drivers (per-policy streams are the same shape).
    Returns (batch, episode_returns, env_steps)."""
    obs, acts, logps, advs, rets = [], [], [], [], []
    ep_returns: list[float] = []
    steps = 0
    for s in samples:
        adv, ret = compute_gae(
            s["rewards"], s["values"], s["dones"], s["last_value"],
            gamma, lam, s.get("trunc_values"))
        T, N = s["rewards"].shape
        steps += T * N
        obs.append(s["obs"].reshape((T * N,) + s["obs"].shape[2:]))
        acts.append(s["actions"].reshape(T * N))
        logps.append(s["logp"].reshape(T * N))
        advs.append(adv.reshape(T * N))
        rets.append(ret.reshape(T * N))
        ep_returns.extend(s["episode_returns"])
    batch = {
        "obs": np.concatenate(obs),
        "actions": np.concatenate(acts),
        "logp_old": np.concatenate(logps),
        "advantages": np.concatenate(advs).astype(np.float32),
        "returns": np.concatenate(rets).astype(np.float32),
    }
    return batch, ep_returns, steps


class JaxLearner:
    """One learner process; jit-compiled minibatch PPO update."""

    def __init__(self, module_cfg_blob: bytes, learner_cfg_blob: bytes,
                 seed: int = 0, group_name: Optional[str] = None,
                 world_size: int = 1, rank: int = 0):
        import cloudpickle
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.rl import module as rlm

        self.cfg: PPOLearnerConfig = cloudpickle.loads(learner_cfg_blob)
        self.module_cfg = cloudpickle.loads(module_cfg_blob)
        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        if group_name is not None and world_size > 1:
            from ray_tpu.util import collective

            collective.init_collective_group(world_size, rank,
                                             group_name=group_name)
        self.params = rlm.init_params(self.module_cfg,
                                      jax.random.PRNGKey(seed))
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(self.cfg.max_grad_norm),
            optax.adam(self.cfg.lr))
        self.opt_state = self.optimizer.init(self.params)
        cfg = self.cfg

        def loss_fn(params, batch):
            logits, value = rlm.forward(params, batch["obs"])
            logp_all = jax.nn.log_softmax(logits)
            logp = jnp.take_along_axis(
                logp_all, batch["actions"][:, None], axis=1)[:, 0]
            ratio = jnp.exp(logp - batch["logp_old"])
            adv = batch["advantages"]
            pg = -jnp.minimum(
                ratio * adv,
                jnp.clip(ratio, 1 - cfg.clip_eps, 1 + cfg.clip_eps) * adv)
            vf = 0.5 * (value - batch["returns"]) ** 2
            entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
            loss = (pg.mean() + cfg.vf_coeff * vf.mean()
                    - cfg.entropy_coeff * entropy.mean())
            return loss, {"loss": loss, "pg_loss": pg.mean(),
                          "vf_loss": vf.mean(), "entropy": entropy.mean()}

        def grad_step(params, opt_state, batch):
            (_, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, aux

        self._grad_step = jax.jit(grad_step)

        def apply(params, opt_state, grads):
            updates, new_opt = self.optimizer.update(grads, opt_state,
                                                     params)
            import optax as _optax

            return _optax.apply_updates(params, updates), new_opt

        self._apply = jax.jit(apply)

    # ---------------------------------------------------------------- update
    def update(self, batch: dict) -> dict:
        """batch: flat [B, ...] numpy arrays (obs, actions, logp_old,
        advantages, returns). Runs epochs x minibatches."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        B = batch["obs"].shape[0]
        adv = batch["advantages"]
        batch = dict(batch)
        batch["advantages"] = (adv - adv.mean()) / (adv.std() + 1e-8)
        rng = np.random.RandomState(0)
        mb = min(cfg.minibatch_size, B)
        n_mb = max(1, B // mb)
        aux_last: dict = {}
        for _ in range(cfg.num_epochs):
            perm = rng.permutation(B)[:n_mb * mb].reshape(n_mb, mb)
            for idx in perm:
                mb_batch = {k: jnp.asarray(v[idx]) for k, v in batch.items()}
                grads, aux = self._grad_step(self.params, self.opt_state,
                                             mb_batch)
                grads = self._sync_grads(grads)
                self.params, self.opt_state = self._apply(
                    self.params, self.opt_state, grads)
                aux_last = aux
        return {k: float(v) for k, v in aux_last.items()}

    def _sync_grads(self, grads):
        if self.group_name is None or self.world_size <= 1:
            return grads
        import jax
        import jax.numpy as jnp

        from ray_tpu.util import collective

        flat, tree = jax.tree.flatten(grads)
        host = [np.asarray(g) for g in flat]
        summed = [collective.allreduce(g, group_name=self.group_name)
                  for g in host]
        return jax.tree.unflatten(
            tree, [jnp.asarray(g / self.world_size) for g in summed])

    def get_weights(self):
        import jax

        return jax.tree.map(lambda x: np.asarray(x), self.params)

    def set_weights(self, params) -> bool:
        import jax
        import jax.numpy as jnp

        self.params = jax.tree.map(jnp.asarray, params)
        return True

    def save_state(self) -> dict:
        import jax

        return {"params": jax.tree.map(lambda x: np.asarray(x), self.params)}

    def load_state(self, state: dict) -> bool:
        import jax.numpy as jnp
        import jax

        self.params = jax.tree.map(jnp.asarray, state["params"])
        return True
