"""Percent of the merged set's real news read from the embedding cache:
``cache_hits`` over ``merged_news``, summed over the window's
``train_window`` counts (bench/program_trace.py)."""
from bench import program_trace


def read(r):
    return program_trace.fill(r.trace, "train_window", "cache_hits",
                              "merged_news")
