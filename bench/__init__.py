"""The benchmark of the served what-if path (see run.py)."""
