"""Percent of the traced window in which the device is idle while the
host is inside the program's ``encode_fetch`` span (the wait for a
chunk's embeddings and their copy to the host), from ``r.trace`` alone."""
from bench import program_trace


def read(r):
    return program_trace.idle_inside(r.trace, "encode_fetch")
