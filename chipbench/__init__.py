"""Chip benchmark of the ColRel round: cells, traffic, metrics and the
plain reference that decides ``correct``.  Entry point: ``run.py``."""
