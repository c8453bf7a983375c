"""Chip benchmark of the admission and power plane (see `bench/run.py`)."""
