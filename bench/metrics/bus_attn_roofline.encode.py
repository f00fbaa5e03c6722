"""Share of the bus-attention kernels' device time (forward and backward)
that the roofline needs, from the traced window (bench/flops.py counts)."""
from bench.metrics._bus import read  # noqa: F401
