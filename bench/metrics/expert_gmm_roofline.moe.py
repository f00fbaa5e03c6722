"""Share of the grouped-matmul kernels' device time (gmm and tgmm) that
the roofline needs, from the traced window (bench/flops_moe.py counts)."""
from bench.metrics._moe import gmm_roofline as read  # noqa: F401
