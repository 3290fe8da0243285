"""The chip benchmark of the ASCII system: harness, configurations,
traffic, metric readers, peaks and the plain reference.  See ``run.py``."""
