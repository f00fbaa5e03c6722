"""Percent of the token slots the encoder runs for the rows that needed
encoding (rows x K x bucket) that hold real tokens: ``enc_tokens`` over
``enc_token_slots``, summed over the window's ``train_window`` counts
(bench/program_trace.py)."""
from bench import program_trace


def read(r):
    return program_trace.fill(r.trace, "train_window", "enc_tokens",
                              "enc_token_slots")
