"""Chip benchmark of the library: see ``run.py`` and ``BENCHMARK.json``."""
