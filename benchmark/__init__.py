"""Benchmark harness: time to coordinates on the chip (see BENCHMARK.json)."""
