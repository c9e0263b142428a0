"""The bytes and operations that a decoder of the `kimi_k2` family requires
(dense latent attention, routed experts of which this holder has a
share), from shapes: the Kimi cell's yardsticks of `serve_mfu` and of
`serve_decode_attn_` and `serve_prefill_attn_roofline_share` (named to
the one reader by latent_moe_model.YARDSTICKS), kept beside peaks.py so
that no PR that claims a gain can change what 100% means. Each is
written for the WORK, not for how the program does it: a decode query
must read the one cached row ``[c_kv | k_rope]`` of every position it
attends to, once, without the row's filling to whole lanes, and no
other; a query must score and weigh every key from its row's first to
itself, in whichever form of latent attention costs less (the expanded
one: 2 x (192 + 128) operations a head and key, the key's and the
value's up-projection counted once a token among the matrices, against 2
x (576 + 512) absorbed).
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def _item(config: dict, role: str = "serve") -> int:
    return _ITEMSIZE[config["held_as"][role]["param_dtype"]]


def attention_params(config: dict) -> int:
    """One layer's attention matrices: q_a, q_b, kv_a, kv_b (keys' and
    values' up-projection), o."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + v) + h * v * d)


def expert_params(config: dict) -> int:
    """One expert, routed or shared: gate, up and down of a SwiGLU."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_pairs_per_token(config: dict) -> float:
    """Token-expert pairs a token gives this holder's experts in one
    expert layer, in expectation over a router that spreads evenly:
    `num_experts_per_tok` x held / routed (8 x 12 / 384 = 0.25)."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config.get("router_experts", config["n_routed_experts"]))


def matmul_params(config: dict) -> float:
    """Weights that take part in a matrix product for one token that
    passes the layers (the head is `head_params`, met only by a token
    whose logits are asked for; the embedding is a gather): every
    layer's attention, the dense layers' MLP, and in an expert layer
    the router, the shared expert and the expected share of one routed
    expert for each pair this holder computes."""
    d = config["hidden_size"]
    dense = config["first_k_dense_replace"]
    moe = config["num_hidden_layers"] - dense
    routed = config.get("router_experts", config["n_routed_experts"])
    return (config["num_hidden_layers"] * attention_params(config)
            + dense * 3 * d * config["intermediate_size"]
            + moe * (d * routed + expert_params(config)
                     * (config["n_shared_experts"]
                        + held_pairs_per_token(config))))


def head_params(config: dict) -> int:
    return config["hidden_size"] * config["vocab_size"]


def decode_attn_bytes(config: dict, positions: int) -> float:
    """One cached row [c_kv | k_rope] for each position a decode query
    attends to; `positions` is already summed over rows and layers."""
    return float((config["kv_lora_rank"] + config["qk_rope_head_dim"])
                 * _item(config) * positions)


def decode_attn_flops(config: dict, positions: int) -> float:
    """The absorbed form, which a decode step needs (one query a row:
    expanding every key for it would cost more): each head's score
    against the row (latent and rope parts) and its value from the
    latent part."""
    return (config["num_attention_heads"] * 2.0
            * (2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * positions)


def decode_attn_work(config: dict, live: int) -> tuple:
    """(bytes, operations) of the decode rounds' attention over `live`
    positions a layer: every layer attends densely, so each counts once
    a layer."""
    positions = live * config["num_hidden_layers"]
    return (decode_attn_bytes(config, positions),
            decode_attn_flops(config, positions))


def attn_flops(config: dict, pairs: int) -> float:
    """The expanded form: each head's score against a key of nope +
    rope numbers and its weight into a value; `pairs` of query and key
    are already summed over layers."""
    return (config["num_attention_heads"] * 2.0
            * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
               + config["v_head_dim"]) * pairs)


def flops_per_token(config: dict, shapes: list) -> float:
    """Required operations a token served, over one cycle of the
    traffic's `shapes` [[prompt, output], ...]: every position of every
    request but its last output token (sampled and never fed back)
    passes the layers once and attends to every position from its
    request's first to itself; the head is met once for each output
    token."""
    tokens = sum(p + o for p, o in shapes)
    passed = sum(p + o - 1 for p, o in shapes)
    pairs = sum((p + o - 1) * (p + o) // 2 for p, o in shapes)
    return (2.0 * matmul_params(config) * passed
            + 2.0 * head_params(config) * sum(o for _, o in shapes)
            + attn_flops(config, config["num_hidden_layers"] * pairs)
            ) / tokens
