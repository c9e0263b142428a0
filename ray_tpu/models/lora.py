"""LoRA adapters for the Llama family, TPU-first.

The reference has no first-class LoRA: fine-tuning arrives via
torch/DeepSpeed examples (ref: doc/source/train/examples/deepspeed/,
release/air_examples/dolly_v2_lightning_fsdp_finetuning/). Here LoRA is a
native model-layer feature because the adapter shardings, the frozen-base
gradient cut, and the remat policy must be co-designed with GSPMD.

Design:

* Adapters live in their OWN subtree ``{"layers": {"wq_a": [L, d, r],
  "wq_b": [L, r, out], ...}}`` — per-layer A/B stacked on the leading
  "layers" axis exactly like the base weights, so they ride the same
  ``lax.scan`` over blocks with zero extra traces.
* The forward applies the low-rank path ``x @ A @ B * (alpha / r)`` next
  to the frozen matmul — the [d, out] delta is NEVER materialized (a 7B
  delta would be ~6.5 GB bf16; the low-rank path is ~2*r/d of the base
  matmul FLOPs).
* Training differentiates ONLY w.r.t. the adapter subtree
  (``build_train_step(..., trainable_keys=("lora",))``): the backward
  never computes frozen-weight gradients, and optimizer moments exist
  only for adapters — the actual LoRA memory/FLOP win, not an
  optax-masked imitation of it.
* ``merge_lora`` folds adapters into base weights for serving.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig

# target name -> (base param key, A logical in-axis, B logical out-axis)
_TARGET_AXES = {
    "wq": ("embed", "heads"),
    "wk": ("embed", "kv_heads"),
    "wv": ("embed", "kv_heads"),
    "wo": ("heads", "embed"),
    "w_gate": ("embed", "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
}

DEFAULT_TARGETS = ("wq", "wk", "wv", "wo")


@dataclasses.dataclass(frozen=True)
class LoraConfig:
    rank: int = 8
    alpha: float = 16.0
    targets: tuple = DEFAULT_TARGETS

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def _target_dims(cfg: LlamaConfig, name: str) -> tuple[int, int]:
    d, h = cfg.dim, cfg.hidden_dim
    dims = {
        "wq": (d, cfg.n_heads * cfg.head_dim),
        "wk": (d, cfg.n_kv_heads * cfg.head_dim),
        "wv": (d, cfg.n_kv_heads * cfg.head_dim),
        "wo": (cfg.n_heads * cfg.head_dim, d),
        "w_gate": (d, h),
        "w_up": (d, h),
        "w_down": (h, d),
    }
    return dims[name]


def init_lora_params(cfg: LlamaConfig, lora: LoraConfig,
                     key: jax.Array) -> dict:
    """A ~ N(0, 1/r) (Kaiming-style), B = 0 — the adapter starts as an
    exact no-op so step 0 matches the frozen base model bit-for-bit."""
    if lora.alpha != cfg.lora_alpha:
        # the forward pass and merge_lora read cfg.lora_alpha; a LoraConfig
        # with a different alpha would silently train at the wrong scale
        raise ValueError(
            f"LoraConfig.alpha={lora.alpha} != LlamaConfig.lora_alpha="
            f"{cfg.lora_alpha}; set them consistently (e.g. "
            f"config_for(name, lora_alpha=...))")
    L, r = cfg.n_layers, lora.rank
    pd = cfg.param_dtype
    layers: dict = {}
    keys = jax.random.split(key, len(lora.targets))
    for k, name in zip(keys, lora.targets):
        if name not in _TARGET_AXES:
            raise ValueError(f"unknown LoRA target {name!r}; "
                             f"have {sorted(_TARGET_AXES)}")
        d_in, d_out = _target_dims(cfg, name)
        layers[name + "_a"] = (
            jax.random.normal(k, (L, d_in, r), jnp.float32)
            * (1.0 / math.sqrt(r))).astype(pd)
        layers[name + "_b"] = jnp.zeros((L, r, d_out), pd)
    return {"layers": layers}


def lora_logical_axes(cfg: LlamaConfig, lora: LoraConfig) -> dict:
    """Adapter sharding mirrors the base weight it augments: A shards its
    input dim like the base in-axis (fsdp), B shards its output dim like
    the base out-axis (tensor) — so TP keeps the low-rank contraction
    local and only the tiny rank dim is replicated."""
    layers: dict = {}
    for name in lora.targets:
        in_ax, out_ax = _TARGET_AXES[name]
        layers[name + "_a"] = ("layers", in_ax, None)
        layers[name + "_b"] = ("layers", None, out_ax)
    return {"layers": layers}


def merge_lora(params: dict, cfg: LlamaConfig) -> dict:
    """Fold adapters into the base weights (for serving/decode paths that
    don't know about LoRA). Returns a NEW params dict without "lora".

    The scale comes from ``cfg.lora_alpha`` — the SAME source the forward
    pass uses — so merged weights always match the trained model. Targets
    are inferred from the adapter keys themselves.
    """
    if "lora" not in params:
        return params
    base_layers = dict(params["layers"])
    lora_layers = params["lora"]["layers"]
    targets = sorted({k[:-2] for k in lora_layers if k.endswith("_a")})
    for name in targets:
        a = lora_layers[name + "_a"].astype(jnp.float32)
        b = lora_layers[name + "_b"].astype(jnp.float32)
        scale = cfg.lora_alpha / a.shape[-1]
        delta = jnp.einsum("lir,lro->lio", a, b) * scale
        base_layers[name] = (base_layers[name].astype(jnp.float32)
                             + delta).astype(base_layers[name].dtype)
    out = {k: v for k, v in params.items() if k != "lora"}
    out["layers"] = base_layers
    return out
