"""Chip benchmark of the QLC serving stack (see BENCHMARK.json)."""
