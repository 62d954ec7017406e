"""Chip benchmark of the triangle engine; ``python3 bench/run.py --help``."""
