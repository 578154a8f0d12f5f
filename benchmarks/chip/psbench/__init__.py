"""Chip benchmark of the PS-DSF allocator: the harness behind
``benchmarks/chip/run_cell.py``."""
