"""Chip benchmark of the memory-managed training and serving paths."""
