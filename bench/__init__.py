"""Chip benchmark of the planned 2-D FFT; see ``bench/run.py``."""
