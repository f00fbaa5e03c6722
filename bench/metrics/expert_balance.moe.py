"""How evenly the held experts are loaded: the rows the least loaded held
expert took over the rows the most loaded one took, in percent, summed
over the window's steps (``rows_least`` over ``rows_most`` of the
``moe_window`` counts, bench/program_trace.py)."""
from bench import program_trace


def read(r):
    return program_trace.fill(r.trace, "moe_window", "rows_least",
                              "rows_most")
