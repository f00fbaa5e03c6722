"""Percent of the window's device op time (loops and calls left out) in
ops whose name stack holds the program's ``moe`` scope (the expert
layer: router, dispatch, grouped matmuls, combine and shared experts), read
from each op's ``tf_op`` in the trace (bench/program_trace.py)."""
from bench.metrics._moe import scope_share


def read(r):
    return scope_share(r.trace, "moe")
