"""The chip benchmark of the Data Calculator: ``python3 bench/run.py``."""
