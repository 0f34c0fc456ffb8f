"""Tests of the benchmark harness (run by hand: python3 -m pytest bench/tests)."""
