"""The bytes and operations that the three mechanisms of the
`dots3_note` decode step require, from shapes: the yardsticks of
`moe_experts`', `index_score`'s and `sparse_attn`'s roofline shares,
and the decoder's operations a token for `serve_mfu` (named to the one
reader by sparse_moe_model.YARDSTICKS), kept beside peaks.py so that no
PR that claims a gain can change what 100% means. Each is the LEAST a
correct step must move or compute, not what the program happens to: an
expert that no token chose need not be read, a cached row's padding to
whole lanes need not be either.
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


def _side(config: dict, kind: str) -> dict:
    """The attention sizes of a layer of `kind`."""
    p = "" if kind == "full_attention" else "swa_"
    return {k: int(config[p + name]) for k, name in (
        ("heads", "num_attention_heads"), ("nope", "qk_nope_head_dim"),
        ("rope", "qk_rope_head_dim"), ("v", "v_head_dim"),
        ("q_rank", "q_lora_rank"), ("kv_rank", "kv_lora_rank"))}


def attention_params(config: dict, kind: str) -> int:
    """One layer's attention matrices: q_a, q_b, kv_a, the keys' and
    the values' up-projection, the head-wise gate, o; in a full layer
    the indexer's wq_b, its key and its heads' weights too."""
    z, d = _side(config, kind), config["hidden_size"]
    out = (d * z["q_rank"] + z["q_rank"] * z["heads"] * (z["nope"] + z["rope"])
           + d * (z["kv_rank"] + z["rope"])
           + z["kv_rank"] * z["heads"] * (z["nope"] + z["v"])
           + d * z["heads"] + z["heads"] * z["v"] * d)
    if kind == "full_attention":
        hi, di = config["index_n_heads"], config["index_head_dim"]
        out += z["q_rank"] * hi * di + d * di + d * hi
    return out


def held_pairs_per_token(config: dict) -> float:
    """Token-expert pairs a token gives this holder's experts in one
    expert layer, in expectation over a router that spreads evenly:
    `num_experts_per_tok` x held / routed (8 x 32 / 256 = 1)."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config.get("router_experts", config["n_routed_experts"]))


def matmul_params(config: dict) -> float:
    """Weights that take part in a matrix product for one token that
    passes the layers (the head is met only by a token whose logits are
    asked for; the embedding is a gather): every layer's attention, the
    dense layers' MLP, and in an expert layer the router, the shared
    experts and the expected share of one routed expert for each pair
    this holder computes."""
    d = config["hidden_size"]
    dense = config["first_k_dense_replace"]
    moe = config["num_hidden_layers"] - dense
    routed = config.get("router_experts", config["n_routed_experts"])
    return (sum(attention_params(config, kind)
                for kind in config["layer_types"])
            + dense * 3 * d * config["intermediate_size"]
            + moe * (d * routed + expert_params(config)
                     * (config["n_shared_experts"]
                        + held_pairs_per_token(config))))


def _first(n: int, most: int) -> int:
    """Keys attended to by `n` queries in a row of which each sees every
    position up to itself, and the newest `most` of them at the most."""
    k = min(n, most)
    return k * (k + 1) // 2 + max(n - most, 0) * most


def flops_per_token(config: dict, shapes: list) -> float:
    """Required operations a token served, over one cycle of the
    traffic's `shapes` [[prompt, output], ...]: every position of every
    request but its last output token (sampled and never fed back)
    passes the layers once; in a full layer it scores every position
    from its request's first to itself with the indexer
    (`index_score_flops`) and attends to the `index_topk` chosen (all,
    while there are fewer), in a sliding one to the newest
    `sliding_window_size`, each in the expanded form, the cheaper one
    (2 x (nope + rope + v) operations a head and key; the keys' and the
    values' up-projection is among the matrices, once a token); the head
    is met once for each output token."""
    kinds = list(config["layer_types"])
    full, sliding = (_side(config, k) for k in ("full_attention",
                                                "sliding_attention"))
    a_key = lambda z: 2.0 * z["heads"] * (z["nope"] + z["rope"] + z["v"])
    rows = [p + o - 1 for p, o in shapes]
    scored = sum(_first(n, n) for n in rows)
    chosen = sum(_first(n, config["index_topk"]) for n in rows)
    window = sum(_first(n, config["sliding_window_size"]) for n in rows)
    n_full = kinds.count("full_attention")
    attention = (n_full * (index_score_flops(config, scored)
                           + a_key(full) * chosen)
                 + (len(kinds) - n_full) * a_key(sliding) * window)
    return (2.0 * matmul_params(config) * sum(rows)
            + 2.0 * config["hidden_size"] * config["vocab_size"]
            * sum(o for _, o in shapes)
            + attention) / sum(p + o for p, o in shapes)
