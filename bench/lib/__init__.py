"""The benchmark harness: spec, traffic, system, checks, reductions."""
