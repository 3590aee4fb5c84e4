"""Chip benchmark: one general harness whose cells, traffic mixes, model
configurations, references and per-layer metric readers are files found by
the names in ``BENCHMARK.json``.  Run ``python3 bench/run.py --help``."""
