"""The bytes and operations that a decoder of the `laguna` family
requires (grouped-query attention of two kinds, each with its own count
of query heads: full depth and a window; routed experts of which this
holder has a share), from shapes: the Laguna cell's yardsticks of
`serve_mfu` and of `serve_decode_attn_` and
`serve_prefill_attn_roofline_share` (named to the one reader by
gqa_moe_model.YARDSTICKS), kept beside peaks.py so that no PR that
claims a gain can change what 100% means. Each is written for the
WORK, not for how the program does it: a decode query must read the key
and the value of every position it attends to, over all kv heads, once
(4,096 B a position and layer), and no other: not the rest of a block,
not a ring row that holds a position outside the window or before the
row's first; a query must score and weigh every visible key for each of
its layer's query heads (4 x 128 operations a head and key).
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
FULL, SLIDING = "full_attention", "sliding_attention"


def _item(config: dict, role: str = "serve") -> int:
    return _ITEMSIZE[config["held_as"][role]["param_dtype"]]


def heads(config: dict, kind: str) -> int:
    """Query heads of a layer of `kind` (one count a kind)."""
    return next(int(h) for t, h in zip(
        config["layer_types"], config["num_attention_heads_per_layer"])
        if t == kind)


def layers(config: dict, kind: str) -> int:
    return sum(t == kind for t in config["layer_types"])


def attention_params(config: dict, kind: str) -> int:
    """One layer's attention matrices: q, k, v, the per-head gate, o."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = heads(config, kind), config["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + d * h + h * hd * d


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down of a SwiGLU."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config[
        "shared_expert_intermediate_size"]


def held_pairs_per_token(config: dict) -> float:
    """Token-expert pairs a token gives this holder's experts in one
    expert layer, in expectation over a router that spreads evenly:
    `num_experts_per_tok` x held / routed (10 x 64 / 256 = 2.5)."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config.get("router_experts", config["num_experts"]))


def matmul_params(config: dict) -> float:
    """Weights that take part in a matrix product for one token that
    passes the layers (the head is `head_params`, met only by a token
    whose logits are asked for; the embedding is a gather): every
    layer's attention, the dense layers' MLP, and in an expert layer the
    router, the shared expert and the expected share of one routed
    expert for each pair this holder computes."""
    d = config["hidden_size"]
    dense = sum(t == "dense" for t in config["mlp_layer_types"])
    moe = config["num_hidden_layers"] - dense
    routed = config.get("router_experts", config["num_experts"])
    return (sum(layers(config, k) * attention_params(config, k)
                for k in (FULL, SLIDING))
            + dense * 3 * d * config["intermediate_size"]
            + moe * (d * routed + shared_expert_params(config)
                     + expert_params(config) * held_pairs_per_token(config)))


def head_params(config: dict) -> int:
    return config["hidden_size"] * config["vocab_size"]


def decode_attn_bytes(config: dict, positions: int) -> float:
    """K and V over all kv heads for each position a decode query
    attends to; `positions` is already summed over rows and layers of
    both kinds."""
    return float(2 * config["num_key_value_heads"] * config["head_dim"]
                 * _item(config) * positions)


def attn_flops(config: dict, full_pairs: int, window_pairs: int) -> float:
    """Each query head's score against a key and its weight into a
    value, 2 x 2 x head_dim; the pairs of query and key are already
    summed over the layers of their kind."""
    return 4.0 * config["head_dim"] * (
        heads(config, FULL) * full_pairs
        + heads(config, SLIDING) * window_pairs)


def decode_attn_work(config: dict, full: int, window: int) -> tuple:
    """(bytes, operations) of the decode rounds' attention over `full`
    positions of the full layers and `window` visible ring rows of the
    sliding ones (6 or 9 query heads a kv head: the bytes bound)."""
    return (decode_attn_bytes(config, full + window),
            attn_flops(config, full, window))


def flops_per_token(config: dict, shapes: list) -> float:
    """Required operations a token served, over one cycle of the
    traffic's `shapes` [[prompt, output], ...]: every position of every
    request but its last output token (sampled and never fed back)
    passes the layers once, attends in a full layer to every position
    from its request's first to itself and in a sliding one to the
    newest `sliding_window` of them; the head is met once for each
    output token."""
    w = config["sliding_window"]
    tokens = sum(p + o for p, o in shapes)
    passed = sum(p + o - 1 for p, o in shapes)
    full = sum((p + o - 1) * (p + o) // 2 for p, o in shapes)
    window = sum(min(n, w) * (min(n, w) + 1) // 2 + max(n - w, 0) * w
                 for n in (p + o - 1 for p, o in shapes))
    return (2.0 * matmul_params(config) * passed
            + 2.0 * head_params(config) * sum(o for _, o in shapes)
            + attn_flops(config, layers(config, FULL) * full,
                         layers(config, SLIDING) * window)) / tokens
