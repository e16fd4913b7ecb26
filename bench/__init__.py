"""Chip benchmark of the DPC engine; ``python3 bench/run.py --help``."""
