"""Chip benchmark of the entangled serving path (see ``run.py``)."""
