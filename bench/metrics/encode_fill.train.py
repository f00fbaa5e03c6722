"""Percent of the encode set's rows that needed encoding: the program's
``encoded`` over ``encode_rows`` (steps x encode budget), summed over the
window's ``train_window`` counts (bench/program_trace.py)."""
from bench import program_trace


def read(r):
    return program_trace.fill(r.trace, "train_window", "encoded",
                              "encode_rows")
