"""Chip benchmark of the Squeezy serving path (see BENCHMARK.json, PERF.md)."""
