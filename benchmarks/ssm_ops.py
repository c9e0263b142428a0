"""The bytes and operations the Mamba-2 state update requires, from
shapes: the yardstick of `ssm_update`'s roofline share, and the hybrid
decoder's operations a token for `serve_mfu` (named to the one reader by
hybrid_cell.YARDSTICKS), kept beside peaks.py so that no PR that claims
a gain can change what 100% means.

One decode round reads each live slot's recurrent state and convolution
tail once and writes them once, in every Mamba layer; nothing else of
the update is as large (its inputs x, B, C, dt are a few KB a slot). A
slot that holds no request has no state worth keeping: what the program
spends on it is not required work and is not counted.
"""

from __future__ import annotations

from benchmarks.attention_ops import cycle_sums

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def _mamba_layers(config: dict) -> int:
    return sum(1 for t in config["layer_types"] if t == "mamba")


def _state_elements(config: dict) -> int:
    return (config["mamba_n_heads"] * config["mamba_d_head"]
            * config["mamba_d_state"])


def ssm_update_bytes(config: dict, slots: int) -> float:
    """The least one decode round's state updates move through device
    memory for `slots` live requests: state and tail, in and out."""
    held = config["held_as"]["serve"]
    conv_dim = (config["mamba_n_heads"] * config["mamba_d_head"]
                + 2 * config["mamba_n_groups"] * config["mamba_d_state"])
    state = _state_elements(config) * _ITEMSIZE[held["state_dtype"]]
    tail = ((config["mamba_d_conv"] - 1) * conv_dim
            * _ITEMSIZE[held["compute_dtype"]])
    return 2.0 * (state + tail) * slots * _mamba_layers(config)


def ssm_update_flops(config: dict, slots: int) -> float:
    """Operations of the same round: for each element of the state, the
    decay's product, the outer product dt x (x) B and its addition, and
    the product and sum of the contraction with C."""
    return 5.0 * _state_elements(config) * slots * _mamba_layers(config)


def flops_per_token(config: dict, traffic: dict) -> float:
    """Required operations a token served by the hybrid decoder, over
    one cycle of the traffic (attention_ops.cycle_sums). Every position
    that passes the layers meets two operations a matrix weight (a
    Mamba layer's input projection [z | x B C | dt] and its output
    projection, an attention layer's q, k, v and o, and the SwiGLU MLP
    behind either), in every Mamba layer the recurrence as a step a
    token (`ssm_update_flops` of one slot: the fewest operations; a
    chunked scan spends more to use the matrix unit) and the
    convolution's d_conv products a channel, and in the attention
    layers 2 x 2 x head_dim a head for every key from its request's
    first to itself. The tied head is a matrix product all the same,
    met once for each output token."""
    d, f = config["hidden_size"], config["shared_intermediate_size"]
    nh = config["num_attention_heads"]
    hd = d // nh
    d_inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv_dim = d_inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    mamba = _mamba_layers(config)
    attention = config["num_hidden_layers"] - mamba
    matrices = (mamba * (d * (d_inner + conv_dim + config["mamba_n_heads"])
                         + d_inner * d)
                + attention * (d * nh * hd + nh * hd * d
                               + 2 * d * config["num_key_value_heads"] * hd)
                + config["num_hidden_layers"] * 3 * d * f)
    a_token = (2.0 * matrices + ssm_update_flops(config, 1)
               + 2.0 * config["mamba_d_conv"] * conv_dim * mamba)
    c = cycle_sums(traffic)
    return (a_token * c["passed"]
            + 2.0 * d * config["vocab_size"] * c["outputs"]
            + 4.0 * hd * nh * attention * c["pairs"]) / c["tokens"]
