"""Chip benchmark of the MDGNN training and serving system (see PERF.md)."""
