"""Chip benchmark of the Caesar round (see ``run.py`` and ``BENCHMARK.json``)."""
