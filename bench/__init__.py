"""Chip benchmark of the Taiji serving path (see PERF.md)."""
