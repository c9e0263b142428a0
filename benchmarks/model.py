"""What the benchmark needs from a configuration file: the keys the
program's LlamaConfig takes, the weights from a seed in one jitted
program, and the hyper-parameters the plain reference reads.

The engine takes a preset *name* only (LLMEngine -> llama.config_for),
so `register_preset` puts the configuration under its name into
llama.PRESETS of the process that will build the engine or the train
step. That a configuration cannot be handed over as data is listed in
PERF.md for a later PR.
"""

from __future__ import annotations

import math


def program_keys(config: dict, role: str) -> dict:
    """Published key -> the program's LlamaConfig key. `role` is "serve"
    or "train": the file's `held_as` gives each its parameter dtype."""
    import jax.numpy as jnp

    return {
        "param_dtype": jnp.dtype(config["held_as"][role]["param_dtype"]),
        "dtype": jnp.dtype(config["held_as"][role]["compute_dtype"]),
        "vocab_size": int(config["vocab_size"]),
        "dim": int(config["hidden_size"]),
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "hidden_dim": int(config["intermediate_size"]),
        "max_seq_len": int(config["max_position_embeddings"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "tie_embeddings": bool(config.get("tie_word_embeddings", False)),
    }


def reference_hp(config: dict, lora_alpha: float | None = None) -> dict:
    hp = {"n_heads": int(config["num_attention_heads"]),
          "n_kv_heads": int(config["num_key_value_heads"]),
          "rope_theta": float(config["rope_theta"]),
          "norm_eps": float(config["rms_norm_eps"])}
    if lora_alpha is not None:
        hp["lora_alpha"] = float(lora_alpha)
    return hp


def register_preset(config: dict, role: str) -> str:
    from ray_tpu.models import llama

    llama.PRESETS[config["name"]] = program_keys(config, role)
    return config["name"]


def fold_seed(seed: int) -> int:
    """--seed may be a little over 2**31; a PRNGKey takes 32 signed bits."""
    return int(seed) % (2 ** 31 - 1)


def jitted_init(cfg, seed: int, shardings=None):
    """The base weights in one program: N(0, 1/fan_in) in the parameter
    dtype the program serves and trains in, norms at one. Same tree as
    llama.init_params, made on the device (or on the mesh, where
    `shardings` is given) and not leaf by leaf."""
    import jax
    import jax.numpy as jnp

    pd = cfg.param_dtype
    d, f, L = cfg.dim, cfg.hidden_dim, cfg.n_layers
    hd, nh, nkv, V = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.vocab_size
    dense = {("layers", "wq"): ((L, d, nh * hd), d),
             ("layers", "wk"): ((L, d, nkv * hd), d),
             ("layers", "wv"): ((L, d, nkv * hd), d),
             ("layers", "wo"): ((L, nh * hd, d), nh * hd),
             ("layers", "w_gate"): ((L, d, f), d),
             ("layers", "w_up"): ((L, d, f), d),
             ("layers", "w_down"): ((L, f, d), f),
             ("embed",): ((V, d), d),
             ("lm_head",): ((d, V), d)}

    def init(key):
        params: dict = {"layers": {"attn_norm": jnp.ones((L, d), pd),
                                   "mlp_norm": jnp.ones((L, d), pd)},
                        "final_norm": jnp.ones((d,), pd)}
        keys = jax.random.split(key, len(dense))
        for k, (path, (shape, fan_in)) in zip(keys, dense.items()):
            w = (jax.random.normal(k, shape, jnp.float32)
                 * (1.0 / math.sqrt(fan_in))).astype(pd)
            node = params
            for p in path[:-1]:
                node = node[p]
            node[path[-1]] = w
        return params

    fn = jax.jit(init, out_shardings=shardings)
    return fn(jax.random.PRNGKey(fold_seed(seed)))


def memory_peak_bytes() -> int:
    """Peak on the fullest device. On this runtime a program's
    temporaries are `reserved`, not `in use` (PERF.md section 6, PR 21),
    so the two peaks are added."""
    import jax

    peaks = []
    for dev in jax.local_devices():
        m = dev.memory_stats() or {}
        peaks.append(int(m.get("peak_bytes_in_use", 0))
                     + int(m.get("peak_bytes_reserved", 0)))
    return max(peaks)


def device_report() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
