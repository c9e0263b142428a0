"""The operations and bytes attention requires, from shapes: the
yardstick of the flash kernels' roofline share, kept beside peaks.py so
that no PR that claims a gain can change what 100% means.

Causal attention needs half of the s x s score matrix, and that half is
counted once: the forward pass makes two matrix products over it (Q K^T
and P V), the backward pass four (dV, dP, dQ, dK). What the kernels do
beyond that is not required work and is not counted: the backward
kernels each build the scores again, and under remat the whole forward
kernel runs a second time.
"""

from __future__ import annotations


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
