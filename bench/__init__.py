"""Chip benchmark of the batch resolve; ``bench/run.py`` is the entry."""
