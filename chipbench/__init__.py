"""Chip benchmark of the Mirage reproduction: cells, traffic, metrics."""
