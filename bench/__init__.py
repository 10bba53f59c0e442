"""Benchmark harness: see BENCHMARK.json and PERF.md."""
