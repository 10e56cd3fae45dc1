"""Chip benchmark: cells from BENCHMARK.json, run on a TPU (run.py)."""
