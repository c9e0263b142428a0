"""90th percentile of the gaps between consecutive streamed tokens, at
the client, pooled over every request in the window."""

from benchmarks.serve_cell import itl_p90_ms as read  # noqa: F401
