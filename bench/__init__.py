"""On-chip benchmark of the NUMA advisor: ``python3 bench/run.py``."""
