"""What the expert encoder's readers share: the share of the window's
device op time in one program scope (``moe`` or ``mla``), and the
roofline shares of its two kernels (bench/flops_moe.py counts)."""
from bench import flops_moe, program_trace, trace
from bench.trace import _ITEMSIZE


def scope_share(tr, scope: str):
    """Percent of the window's device op time (loops and calls left out)
    in ops whose name stack holds ``scope``; None when no op does."""
    pt = program_trace.load(tr)
    if tr is None or pt is None:
        return None
    memo, inside, total = {}, 0.0, 0.0
    for op in tr.ops():
        if op.is_control_flow:
            continue
        hit = memo.get(op.text)
        if hit is None:
            hit = memo[op.text] = scope in program_trace.stack_names(
                pt.stacks.get(op.text))
        total += op.dur
        inside += op.dur if hit else 0.0
    if not inside:
        return None
    return 100.0 * inside / total


def gmm_roofline(r):
    """Roofline share of the grouped-matmul kernels (``gmm`` forward and
    input gradient, ``tgmm`` weight gradient).  The rows a call multiplies
    are not in its shapes (its row buffer is the dropless bound): each
    expert layer of a chunk makes three tgmm calls, one per weight, and
    every call of that layer multiplies the same routed rows, so a call's
    rows are the window's routed rows (``moe_window``) times 3 over the
    tgmm calls."""
    if r.trace is None:
        return None
    ops = trace.kernels(r.trace, "gmm")
    n_t = sum(1 for op in ops if op.name.startswith("tgmm"))
    counts = program_trace.window_counts(program_trace.load(r.trace),
                                         "moe_window")
    if not ops or not n_t or not counts or not counts.get("routed"):
        return None
    rows = 3.0 * counts["routed"] / n_t

    def cost(op):
        shapes = op.shapes()
        out = shapes[0][1]
        if op.name.startswith("tgmm"):            # -> [G, k, n]
            g, k, n = out
            return flops_moe.grouped_matmul(rows=rows, k=k, n=n, groups=g,
                                            transposed_out=True)
        w = next(dims for _, dims in shapes[1:] if len(dims) == 3)
        k = next(dims for _, dims in shapes[1:]
                 if len(dims) == 2 and dims[0] == out[0])[1]
        return flops_moe.grouped_matmul(rows=rows, k=k, n=out[1],
                                        groups=w[0])

    return trace.roofline_share(ops, cost, r.peaks)


def bus_roofline(r):
    """Roofline share of the causal latent bus-attention kernel, forward
    and backward, from each call's shapes."""
    if r.trace is None:
        return None

    def cost(op):
        shapes = op.shapes()
        # forward: o [M,K,H,S,Dv], then q, k [M,K,H,Sk,D], v, mask;
        # backward: (dq, dk, dv), then q, k, v, mask, do
        five = [dims for _, dims in shapes if len(dims) == 5]
        backward = "bwd" in op.name
        q, k, v = (five[3], five[4], five[5]) if backward else \
            (five[1], five[2], five[3])
        M, K, H, S, D = q
        return flops_moe.latent_bus_attention(
            M=M, K=K, S=S, H=H, D=D, Dv=v[4], backward=backward,
            itemsize=_ITEMSIZE[shapes[0][0]])

    return trace.roofline_share(trace.kernels(r.trace, "bus_attention"),
                                cost, r.peaks)
