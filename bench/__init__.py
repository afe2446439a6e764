"""On-chip benchmark of the served store (see run.py and PERF.md)."""
