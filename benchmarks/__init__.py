"""The benchmark: harness, traffic, plain references, trace reduction and
metric readers. BENCHMARK.json at the root of the repo names the cells."""
