"""Percent of the encode-set rows the encoder ran that needed encoding:
the program's ``encoded`` over ``encode_rows_run`` (the rows of the
chunks it ran), summed over the window's ``train_window`` counts
(bench/program_trace.py).  A program that counts no ``encode_rows_run``
reads nothing."""
from bench import program_trace


def read(r):
    return program_trace.fill(r.trace, "train_window", "encoded",
                              "encode_rows_run")
