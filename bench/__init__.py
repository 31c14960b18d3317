"""Benchmark of the multi-task training step (see bench/run.py)."""
