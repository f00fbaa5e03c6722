"""Percent of the traced window in which no operation ran on the device."""
from bench import trace


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(r.trace) / r.trace.window_s)
