"""Chip benchmark of the CPQx serving path: one cell per run, found by
name in the repository's ``BENCHMARK.json``.  See ``run.py``."""
