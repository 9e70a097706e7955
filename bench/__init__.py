"""Chip benchmark of the semantic-join engine (see BENCHMARK.json)."""
