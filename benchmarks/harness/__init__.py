"""The benchmark's own code: everything BENCHMARK.json's cells are measured
with. Nothing outside `benchmarks/` imports it but `tests/benchmark/`."""
