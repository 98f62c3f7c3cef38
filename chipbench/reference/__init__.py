"""Plain float32 references of the benchmark's models.

They import nothing of the program under test and take nothing it made:
their weights come from ``chipbench/weights.py``, laid out as the program
lays out its own.
"""
