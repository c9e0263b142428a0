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
    CACHE_LEN_AXIS, decode_step, TENSOR_PARALLEL."""
    from ray_tpu.models import granite_hybrid

    for module, config_type in ((llama, llama.LlamaConfig),
                                (granite_hybrid,
                                 granite_hybrid.GraniteHybridConfig)):
        if isinstance(cfg, config_type):
            return module
    raise TypeError(f"no model module serves a {type(cfg).__name__}")
