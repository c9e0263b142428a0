"""What the benchmark needs from a configuration file of the hybrid
(Mamba-2 + attention) family: the program's GraniteHybridConfig, the
weights from a seed in one jitted program, and the hyper-parameters the
plain reference reads. The engine takes the config as data, so nothing
is registered anywhere (benchmarks/model.py does that for llama, whose
engine took a preset's name when it was written).
"""

from __future__ import annotations

from benchmarks import model


def program_config(config: dict, role: str, **overrides):
    """The program's config from the published keys; `held_as[role]`
    gives the dtypes of parameters, compute and recurrent state."""
    import jax.numpy as jnp

    from ray_tpu.models import granite_hybrid

    held = config["held_as"][role]
    return granite_hybrid.from_published(
        config, param_dtype=jnp.dtype(held["param_dtype"]),
        dtype=jnp.dtype(held["compute_dtype"]),
        state_dtype=jnp.dtype(held.get("state_dtype", "float32")),
        **overrides)


def reference_hp(config: dict) -> dict:
    return {"layer_types": tuple(config["layer_types"]),
            "n_heads": int(config["num_attention_heads"]),
            "n_kv_heads": int(config["num_key_value_heads"]),
            "mamba_n_heads": int(config["mamba_n_heads"]),
            "mamba_d_state": int(config["mamba_d_state"]),
            "norm_eps": float(config["rms_norm_eps"]),
            "embedding": float(config["embedding_multiplier"]),
            "residual": float(config["residual_multiplier"]),
            "attention": float(config["attention_multiplier"]),
            "logits_scaling": float(config["logits_scaling"])}


def jitted_init(cfg, seed: int):
    """The model's own `init_params` (the configuration file's
    `departures` describe it) as one program on the device, not leaf by
    leaf."""
    import jax

    from ray_tpu.models import granite_hybrid

    return jax.jit(lambda key: granite_hybrid.init_params(cfg, key))(
        jax.random.PRNGKey(model.fold_seed(seed)))
