"""Chip benchmark of the served path: see BENCHMARK.json and PERF.md."""
