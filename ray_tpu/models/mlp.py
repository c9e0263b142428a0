"""Small MLP (MNIST-class).

Used by the JaxTrainer DDP path and tests; trivially shardable on the
``data`` axis (pure DP: params replicated, batch sharded).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.cross_entropy import softmax_cross_entropy


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    in_dim: int = 784
    hidden: tuple = (512, 512)
    n_classes: int = 10
    dtype: object = jnp.float32


def mlp_init(cfg: MLPConfig, key: jax.Array) -> list[dict]:
    dims = (cfg.in_dim,) + tuple(cfg.hidden) + (cfg.n_classes,)
    keys = jax.random.split(key, len(dims) - 1)
    return [
        {"w": (jax.random.normal(k, (a, b)) / math.sqrt(a)).astype(cfg.dtype),
         "b": jnp.zeros((b,), cfg.dtype)}
        for k, a, b in zip(keys, dims[:-1], dims[1:])
    ]


def mlp_forward(params: list[dict], x: jax.Array) -> jax.Array:
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def mlp_loss(params: list[dict], batch: dict):
    logits = mlp_forward(params, batch["x"])
    loss, n = softmax_cross_entropy(logits, batch["y"])
    acc = jnp.mean(jnp.argmax(logits, -1) == batch["y"])
    return loss, {"loss": loss, "accuracy": acc}
