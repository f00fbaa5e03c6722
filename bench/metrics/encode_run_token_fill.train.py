"""Percent of the token slots the encoder ran that hold real tokens: the
program's ``enc_tokens`` over ``enc_token_slots_run`` (the rows of the
chunks it ran x K x the segment length each chunk ran at), summed over
the window's ``train_window`` counts (bench/program_trace.py).  A program
that counts no ``enc_token_slots_run`` reads nothing."""
from bench import program_trace


def read(r):
    return program_trace.fill(r.trace, "train_window", "enc_tokens",
                              "enc_token_slots_run")
