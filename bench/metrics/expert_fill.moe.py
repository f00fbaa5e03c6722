"""Percent of the grouped matmuls' row slots that hold a routed row:
``routed`` over ``slots`` (the rows of the 128-row tiles the kernel
runs), summed over the window's ``moe_window`` counts
(bench/program_trace.py)."""
from bench import program_trace


def read(r):
    return program_trace.fill(r.trace, "moe_window", "routed", "slots")
