"""Chip benchmark of the WAGEUBN training system (see BENCHMARK.json)."""
