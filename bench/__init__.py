"""Benchmark of the serving path on the chip (see bench/run.py)."""
