"""The benchmark of this repository: see ``bench/harness.py``."""
