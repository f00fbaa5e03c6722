"""Model FLOPs of the window's work over its host-clock time, as a percent
of the chip's bf16 peak.  The work is three times the forward (forward and
backward) of the rows that needed encoding (``encoded`` of the window's
``train_window`` counts) at the window's mean bucket length, of the rows
routed to the held experts (``routed`` of ``moe_window``: the held share
of the expert layers) and of each step's user model and loss
(bench/flops_moe.py, bench/flops.py)."""
from bench import flops_moe, program_trace


def read(r):
    pt = program_trace.load(r.trace)
    enc = program_trace.window_counts(pt, "train_window")
    moe = program_trace.window_counts(pt, "moe_window")
    steps = r.stats.get("bucket_steps")
    if not enc or not moe or not steps or "config" not in r.stats:
        return None
    c = r.stats["config"]
    n = sum(steps.values())
    seg = sum(int(b) * k for b, k in steps.items()) / n
    fwd = (enc["encoded"] * flops_moe.article_forward(c, round(seg))
           + moe["routed"] * flops_moe.routed_row_forward(c)
           + enc["steps"] * r.stats["user_and_loss_flops"])
    return 100.0 * 3.0 * fwd / r.window_s / r.peaks.bf16_flops
