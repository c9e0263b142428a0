"""The operations and bytes attention requires, from shapes: the
yardstick of the flash kernels' roofline share, and of `serve_mfu` for
the `llama` family's serve cells (named to the one reader by
serve_cell.YARDSTICKS), kept beside peaks.py so that no PR that claims a
gain can change what 100% means.

Causal attention needs half of the s x s score matrix, and that half is
counted once: the forward pass makes two matrix products over it (Q K^T
and P V), the backward pass four (dV, dP, dQ, dK). What the kernels do
beyond that is not required work and is not counted: the backward
kernels each build the scores again, and under remat the whole forward
kernel runs a second time.
"""

from __future__ import annotations

from benchmarks.traffic import cycle_lengths


def causal_attention_train_flops(config: dict, batch: int,
                                 seq_len: int) -> float:
    """Operations one training step's attention requires over `batch`
    sequences, all layers, forward and backward."""
    heads = config["num_attention_heads"]
    head_dim = config["hidden_size"] // heads
    per_product = 2.0 * (seq_len * seq_len / 2.0) * head_dim * heads
    return 6.0 * per_product * batch * config["num_hidden_layers"]


def causal_attention_train_bytes(config: dict, batch: int, seq_len: int,
                                 bytes_per_element: int = 2) -> float:
    """The least a step's attention has to move through device memory:
    the forward reads q, k, v and writes the output; the backward reads
    q, k, v, the output and its gradient and writes three gradients.
    Scores never leave the chip's fast memory."""
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    head_dim = config["hidden_size"] // heads
    q = batch * seq_len * heads * head_dim
    kv = batch * seq_len * kv_heads * head_dim
    forward = 2 * q + 2 * kv
    backward = 3 * q + 2 * kv + q + 2 * kv
    return float(bytes_per_element * (forward + backward)
                 * config["num_hidden_layers"])


def causal_pairs(n: int) -> int:
    """Pairs of query and visible key of `n` positions that each attend
    to every position from the first to itself."""
    return n * (n + 1) // 2


def cycle_sums(traffic: dict) -> dict:
    """Of one cycle of a closed loop whose file gives distributions and
    no `shapes`: the tokens served, the positions that pass the layers
    (every one but each request's last output token, sampled and never
    fed back), the output tokens, and the causal pairs of query and
    key, in expectation over the pairing of prompts and outputs, which
    each cycle shuffles anew."""
    prompts, outputs = cycle_lengths(traffic)
    n = len(prompts)
    tokens = sum(prompts) + sum(outputs)
    return {"tokens": tokens, "passed": tokens - n, "outputs": sum(outputs),
            "pairs": sum(causal_pairs(p + o - 1)
                         for p in prompts for o in outputs) / n}


def serve_flops_per_token(config: dict, traffic: dict) -> float:
    """Required operations a token served by a `llama` decoder, over one
    cycle of the traffic: two a weight of the block's projections and
    SwiGLU for every position that passes the layers, two a weight of
    the head for each output token (the embedding is a gather), and
    2 x 2 x head_dim a head for every pair of query and visible key in
    every layer. The LEAST a correct step must compute: a bucket's left
    padding, a chunk's masked tiles and the rest of a decode kernel's
    block are not counted."""
    d, nh = config["hidden_size"], config["num_attention_heads"]
    hd = d // nh
    block = (d * nh * hd + 2 * d * config["num_key_value_heads"] * hd
             + nh * hd * d + 3 * d * config["intermediate_size"])
    c = cycle_sums(traffic)
    return (2.0 * config["num_hidden_layers"] * block * c["passed"]
            + 2.0 * d * config["vocab_size"] * c["outputs"]
            + 4.0 * hd * nh * config["num_hidden_layers"] * c["pairs"]
            ) / c["tokens"]
