"""Model FLOPs of the window's work (bench/flops.py) over its host-clock
time, as a percent of the chip's bf16 peak."""


def read(r):
    if not r.stats.get("model_flops"):
        return None
    return 100.0 * r.stats["model_flops"] / r.window_s / r.peaks.bf16_flops
