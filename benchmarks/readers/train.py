"""Readers of the recipe's own step waterfall (train/telemetry.py via
state_api.list_train_steps) and of the benchmark's step stamps."""

from __future__ import annotations

from benchmarks import peaks
from benchmarks.train_cell import train_tokens_per_s, window_steps


def host_share(obs: dict):
    """1 - sum(step_s) / sum(wall_s) over the window's steps: what the
    Trainer, telemetry, report and checkpoint cost around the step."""
    steps = window_steps(obs)
    wall = sum(s["wall_s"] for s in steps)
    if not wall:
        return None
    return 100.0 * (1.0 - sum(s["stages"]["step_s"] for s in steps) / wall)


def mfu(obs: dict):
    """Required operations per token (peaks.lora_train_flops_per_token)
    x tokens/s, over chips x the chip's published bf16 peak."""
    job = obs["job"]
    per_token = peaks.lora_train_flops_per_token(
        obs["config"], job["seq_len"], job["lora_rank"],
        job["lora_targets"])
    peak = peaks.peak(obs["device"]["kind"])["bf16_flops"]
    return 100.0 * per_token * train_tokens_per_s(obs) / (
        obs["chips"] * peak)
