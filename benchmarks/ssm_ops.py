"""The bytes and operations the Mamba-2 state update requires, from
shapes: the yardstick of `ssm_update`'s roofline share, kept beside
peaks.py so that no PR that claims a gain can change what 100% means.

One decode round reads each live slot's recurrent state and convolution
tail once and writes them once, in every Mamba layer; nothing else of
the update is as large (its inputs x, B, C, dt are a few KB a slot). A
slot that holds no request has no state worth keeping: what the program
spends on it is not required work and is not counted.
"""

from __future__ import annotations

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
