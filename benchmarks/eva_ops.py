"""The bytes and operations that EVA's attention requires (a window of
exact keys, one summary a chunk of everything before it), from shapes:
the byte cell's yardsticks of `serve_decode_attn_roofline_share`,
`serve_prefill_attn_roofline_share` and `serve_mfu` (named to the one
reader by eva_model.YARDSTICKS), kept beside
peaks.py so that no PR that claims a gain can change what 100% means.
Each is written for the WORK, not for how the program does it: a decode
query must read the key and the value of every window position and
every summary it attends to, once, and no other; a prefill query must
score and weigh every key it can see, and no other. Building the
summaries (16 positions folded into one, once) is under a hundredth of
either and is not counted.
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def _heads_dim(config: dict) -> int:
    """Numbers in one key (or value) over all heads: MHA, so the hidden
    size."""
    return int(config["hidden_size"])


def matmul_params(config: dict) -> int:
    """Weights that take part in a matrix multiplication, a byte served:
    the block's four projections and its SwiGLU, and all the output
    heads. The embedding is a gather."""
    d, f = config["hidden_size"], config["intermediate_size"]
    return (config["num_hidden_layers"] * (4 * d * d + 3 * d * f)
            + d * config["num_pred_heads"] * config["vocab_size"])


def decode_attn_bytes(config: dict, rows: int) -> float:
    """One key and one value over all heads for each window position or
    summary a decode query attends to; `rows` is already summed over
    queries and layers."""
    return (2.0 * _heads_dim(config)
            * _ITEMSIZE[config["held_as"]["serve"]["param_dtype"]] * rows)


def attn_flops(config: dict, pairs: int) -> float:
    """Each head's score against a key and its weight into the value:
    2 x 2 x head_dim operations a head; `pairs` of query and key (a
    window position or a summary) are already summed over layers."""
    return 4.0 * _heads_dim(config) * pairs


def decode_attn_work(config: dict, rows: int) -> tuple:
    """(bytes, operations) of the decode rounds' attention over `rows`
    window positions and summaries (2 operations a byte read: the bytes
    bound)."""
    return decode_attn_bytes(config, rows), attn_flops(config, rows)


def attended(config: dict, t):
    """What a query at position `t` of its request (an int or an array
    of them) attends to: its window's positions up to itself and one
    summary for each chunk of the windows before."""
    w, c = config["window_size"], config["chunk_size"]
    return t % w + 1 + (w // c) * (t // w)


def attn_flops_per_token(config: dict, shapes: list) -> float:
    """Attention operations a byte served, over one cycle of the
    traffic's `shapes` [[prompt, output], ...]: every position of every
    request is a query once (the last output byte is sampled and never
    fed back), in every layer."""
    import numpy as np

    pairs = sum(int(attended(config, np.arange(p + o - 1)).sum())
                for p, o in shapes)
    tokens = sum(p + o for p, o in shapes)
    return attn_flops(config, config["num_hidden_layers"] * pairs) / tokens


def flops_per_token(config: dict, shapes: list) -> float:
    """Required operations a byte served: two a matmul weight, and the
    attention of one cycle of the traffic's shapes."""
    return (2.0 * matmul_params(config)
            + attn_flops_per_token(config, shapes))
