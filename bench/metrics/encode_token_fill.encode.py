"""Percent of the token slots the encoder runs (chunk x K x S, the padded
tail included) that hold real tokens: ``tokens`` over ``token_slots``,
summed over the window's ``encode_window`` counts
(bench/program_trace.py)."""
from bench import program_trace


def read(r):
    return program_trace.fill(r.trace, "encode_window", "tokens",
                              "token_slots")
