"""The benchmark of the MCGI serving path: see ``bench/run.py``."""
