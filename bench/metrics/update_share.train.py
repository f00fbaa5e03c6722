"""Percent of the window's device op time (loops and calls left out) in
ops whose name stack holds the program's ``update`` scope, read from
each op's ``tf_op`` in the trace (bench/program_trace.py)."""
from bench import program_trace


def read(r):
    return program_trace.scope_share(r.trace, "update")
