"""Prompt and generated tokens the replica got through per second."""

from benchmarks.serve_cell import serve_tokens_per_s as read  # noqa: F401
