"""The bytes and operations that the three mechanisms of the
`dots3_note` decode step require, from shapes: the yardsticks of
`moe_experts`', `index_score`'s and `sparse_attn`'s roofline shares,
kept beside peaks.py so that no PR that claims a gain can change what
100% means. Each is the LEAST a correct step must move or compute, not
what the program happens to: an expert that no token chose need not be
read, a cached row's padding to whole lanes need not be either.
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def _item(config: dict, role: str = "serve") -> int:
    return _ITEMSIZE[config["held_as"][role]["param_dtype"]]


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down of a SwiGLU."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_bytes(config: dict, experts_hit: int) -> float:
    """Every expert that at least one token chose is read once a layer
    a step; `experts_hit` is already summed over layers and steps."""
    return float(expert_params(config) * _item(config) * experts_hit)


def expert_flops(config: dict, expert_rows: int) -> float:
    """Two operations a weight for each token-expert pair computed."""
    return 2.0 * expert_params(config) * expert_rows


def index_score_bytes(config: dict, positions: int) -> float:
    """One index key for each live position a full layer a step;
    `positions` is already summed over the full layers."""
    return float(config["index_head_dim"] * _item(config) * positions)


def index_score_flops(config: dict, positions: int) -> float:
    """Each index head's dot product with the key, its relu, weight and
    sum."""
    return (config["index_n_heads"] * (2.0 * config["index_head_dim"] + 3)
            * positions)


def sparse_attn_bytes(config: dict, rows: int) -> float:
    """One cached row [c_kv | k_rope] for each attended position; `rows`
    is already summed over the full layers."""
    return float((config["kv_lora_rank"] + config["qk_rope_head_dim"])
                 * _item(config) * rows)


def sparse_attn_flops(config: dict, rows: int) -> float:
    """The absorbed form: each head's score against the row (latent and
    rope parts) and its value from the latent part."""
    return (config["num_attention_heads"] * 2.0
            * (2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * rows)
