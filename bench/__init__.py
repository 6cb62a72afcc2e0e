"""Chip benchmark of the cascade server.  Entry point: ``bench/run.py``."""
