"""Chip benchmark of the orchestrated serving path (see bench/run.py)."""
