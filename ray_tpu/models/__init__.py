"""Model zoo: TPU-first implementations (the reference delegates models to
torch; here the model layer is co-designed with sharding, see
models/llama.py docstring), and the contract between a model module and
the engine that serves it (`module_for`)."""

import types

from ray_tpu.models import llama, lora  # noqa: F401
from ray_tpu.models.lora import (LoraConfig, init_lora_params,  # noqa: F401
                                 lora_logical_axes, merge_lora)
from ray_tpu.models.mlp import MLPConfig, mlp_forward, mlp_init, mlp_loss  # noqa: F401

# What serve/llm.py's engine reads of the module that serves its config;
# `module_for` says what each name means. A module lacking a REQUIRED
# name is refused; one lacking an OPTIONAL name is given its default.
REQUIRED = ("init_params", "param_logical_axes", "forward", "init_cache",
            "cache_logical_axes", "CACHE_LEN_AXIS", "decode_step",
            "decode_read_block", "TENSOR_PARALLEL")


def _counts_nothing(*args) -> dict:
    return {}


OPTIONAL = {"decode_counters": _counts_nothing,
            "prefill_counters": _counts_nothing,
            "CACHE_KIND": types.MappingProxyType({}),
            "STEP_AUX": types.MappingProxyType({}),
            "mixed_step": None}


def registry() -> tuple:
    """The table `module_for` walks: (config type, module), one row a
    model file. The five beside llama are imported when the table is
    first asked for: they bring `jax.experimental.pallas` with them,
    which a process that imports this package for llama alone (a train
    worker, a driver) need not pay for."""
    from ray_tpu.models import (dots3_note, evabyte, granite_hybrid, kimi_k2,
                                laguna)

    return ((llama.LlamaConfig, llama),
            (granite_hybrid.GraniteHybridConfig, granite_hybrid),
            (dots3_note.Dots3NoteConfig, dots3_note),
            (evabyte.EvaByteConfig, evabyte),
            (kimi_k2.KimiK2Config, kimi_k2),
            (laguna.LagunaConfig, laguna))


def module_for(cfg):
    """The model module that serves a config, by the config's type
    (`registry`), held to the contract: it has every name of REQUIRED
    (TypeError otherwise) and, on return, every name of OPTIONAL.

    REQUIRED. `init_params(cfg, key)` and `param_logical_axes(cfg)`: the
    parameter tree and its logical axes. `forward(params, tokens, cfg)`:
    logits of a whole sequence, no cache. `init_cache(cfg, batch,
    max_len=)` and `cache_logical_axes(cfg)`: the cache tree and its
    axes; beside the bookkeeping leaves `length`, `start` and `aux`
    every leaf has a "batch" axis. `CACHE_LEN_AXIS`: the leaves with a
    position axis as deep as the cache, and that axis; every other leaf
    with a batch axis is grafted whole (a recurrent state, a window's
    ring: of latent rows in `dots3_note`, of K and V in
    `laguna`). `decode_step(params, cache, tokens, cfg)`: append tokens
    [b, s], return the last position's logits and the cache, same tree,
    shapes and dtypes. `decode_read_block(cfg, mesh)`: the positions in a
    block of a decode step's cache reads, or None where it reads a layer
    whole. `TENSOR_PARALLEL`: whether the parameters shard over a
    `tensor` axis.

    OPTIONAL. `decode_counters(cfg, spans, rows)`: what a decode step
    counts of its live rows' ranges and of all the rows it has, by
    counter of `stats()` (default: nothing). `prefill_counters(cfg,
    start, pos, chunk, bucket)`: the same of a prefill call's attention,
    from where its row starts and where the call's tokens lie (nothing).
    `CACHE_KIND`: the kind `stats()["cache_bytes"]` files a leaf under,
    beside `kv` and `state`; a function of the config and the leaf where
    two kinds share one leaf's position axis (`{}`). `STEP_AUX`: the
    counters the step decides on the device and returns in
    `cache["aux"]`, field of the emit span -> counter of `stats()`
    (`{}`). `mixed_step(params, small, chunk_tokens, cache, tokens,
    cfg)`: a prefill chunk [1, s] appended to one request's prefill
    cache `small` AND one token a row [b, 1] appended to the slots'
    `cache`, as two calls of `decode_step` append them, in one pass over
    the weights; returns (the chunk's last position's logits, small, the
    rows' logits, cache), and names its operations' phases itself
    (`prefill`, `decode`). With it, the engine's round with a chunk due
    is that one program; without it (None), a chunk's `decode_step` and
    then the rows'."""
    for config_type, module in registry():
        if isinstance(cfg, config_type):
            break
    else:
        raise TypeError(f"no model module serves a {type(cfg).__name__}")
    missing = [name for name in REQUIRED if not hasattr(module, name)]
    if missing:
        raise TypeError(f"{module.__name__} is no model module: it lacks "
                        f"{', '.join(missing)} (ray_tpu.models.REQUIRED)")
    for name, default in OPTIONAL.items():
        if not hasattr(module, name):
            setattr(module, name, default)
    return module
