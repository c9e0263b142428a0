"""Tokens of whole train steps per second, all chips together."""

from benchmarks.train_cell import train_tokens_per_s as read  # noqa: F401
