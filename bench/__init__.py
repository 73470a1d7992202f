"""Chip benchmark of the scheduler: see ``bench/README.md``."""
