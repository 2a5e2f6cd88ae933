"""Chip benchmark of the F2P serving stack; see ``bench/run.py``."""
