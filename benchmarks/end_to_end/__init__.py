"""End-to-end metrics, one file each, found by the metric's name. Each
has `read(obs) -> float`, taken from the benchmark's own host-clock
stamps and never from the program."""
