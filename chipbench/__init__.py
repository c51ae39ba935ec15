"""Chip benchmark of the MoSKA serving system (see BENCHMARK.json)."""
