"""Chip benchmark of the sketching system: one cell per run, driven by data."""
