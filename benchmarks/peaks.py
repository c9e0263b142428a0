"""The yardstick's arithmetic: peaks of the chips, and the operations a
cell's work needs, computed from shapes. Kept with the benchmark so that
no PR that claims a gain can change what 100% means."""

from __future__ import annotations

# Published peaks per chip, keyed by jax's `device_kind`.
# Source: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to benchmarks/peaks.py "
                       f"with its source")
    return PEAKS[device_kind]


def matmul_params(config: dict) -> int:
    """Weights that take part in a matrix multiplication, per token: the
    block's projections and the head. The embedding is a gather."""
    d = config["hidden_size"]
    hd = d // config["num_attention_heads"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * config["intermediate_size"]
    return config["num_hidden_layers"] * per_layer + d * config["vocab_size"]


def lora_params(config: dict, rank: int, targets) -> int:
    d = config["hidden_size"]
    hd = d // config["num_attention_heads"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    f = config["intermediate_size"]
    dims = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return config["num_hidden_layers"] * sum(
        rank * (dims[t][0] + dims[t][1]) for t in targets)


def lora_train_flops_per_token(config: dict, seq_len: int, rank: int,
                               targets) -> float:
    """Operations one token of a LoRA step requires. Frozen base: the
    forward pass and the activation half of the backward (no weight
    gradients), 4 per weight. Adapters in full, 6 per weight. Causal
    attention: half of the s x s products; forward 2 matmuls, backward
    4. Recomputation under remat is not required work and not counted."""
    d_attn = config["num_attention_heads"] * (
        config["hidden_size"] // config["num_attention_heads"])
    attn_fwd = 2 * 2 * (seq_len / 2) * d_attn   # QK^T and PV, causal half
    return (4.0 * matmul_params(config)
            + 6.0 * lora_params(config, rank, targets)
            + 3.0 * attn_fwd * config["num_hidden_layers"])
