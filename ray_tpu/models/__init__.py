"""Model zoo: TPU-first implementations (the reference delegates models to
torch; here the model layer is co-designed with sharding, see
models/llama.py docstring)."""

from ray_tpu.models import llama, lora  # noqa: F401
from ray_tpu.models.lora import (LoraConfig, init_lora_params,  # noqa: F401
                                 lora_logical_axes, merge_lora)
from ray_tpu.models.mlp import MLPConfig, mlp_forward, mlp_init, mlp_loss  # noqa: F401


def module_for(cfg):
    """The model module that serves a config, by the config's type. Each
    has the function set serve/llm.py's engine calls: init_params,
    param_logical_axes, forward, init_cache, cache_logical_axes,
    CACHE_LEN_AXIS (the cache leaves with a position axis as deep as the
    cache; every other leaf with a batch axis is grafted whole: a
    recurrent state, a window's ring), decode_step, decode_read_block
    (the positions in a block of a decode step's cache reads, or None),
    TENSOR_PARALLEL. What the engine asks only where a module has it:
    decode_counters (what a decode step counts of its live rows and of
    all the rows it has),
    prefill_counters (what a prefill call's attention visits and sees),
    CACHE_KIND (the kind `stats()["cache_bytes"]` files a leaf under,
    beside `kv` and `state`; a function of the config and the leaf where
    two kinds share one leaf's position axis) and STEP_AUX (counters the step decides on
    the device and returns in cache["aux"])."""
    from ray_tpu.models import dots3_note, evabyte, granite_hybrid, kimi_k2

    for module, config_type in ((llama, llama.LlamaConfig),
                                (granite_hybrid,
                                 granite_hybrid.GraniteHybridConfig),
                                (dots3_note, dots3_note.Dots3NoteConfig),
                                (evabyte, evabyte.EvaByteConfig),
                                (kimi_k2, kimi_k2.KimiK2Config)):
        if isinstance(cfg, config_type):
            return module
    raise TypeError(f"no model module serves a {type(cfg).__name__}")
