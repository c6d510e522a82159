"""On-chip benchmark of the program under ``src/``; see ``run.py``."""
