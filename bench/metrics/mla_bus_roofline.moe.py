"""Share of the causal latent bus-attention kernels' device time (forward
and backward) that the roofline needs, from the traced window
(bench/flops_moe.py counts)."""
from bench.metrics._moe import bus_roofline as read  # noqa: F401
