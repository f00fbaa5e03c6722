"""Roofline of the fused bus-attention kernel (forward and backward), as
the bus_attn_roofline.* readers share it."""
from bench import flops, trace
from bench.trace import _ITEMSIZE


def cost(op):
    shapes = op.shapes()
    backward = "bwd" in op.name
    # forward: o [M,K,H,S,D], then q, k [M,K,H,Sk,D], v, mask;
    # backward: (dq, dk, dv), then q, k, v, mask, do
    dtype, (M, K, H, S, D) = shapes[0]
    Sk = max(dims[3] for _, dims in shapes if len(dims) == 5)
    assert Sk == S + K, (op.name, shapes)
    return flops.bus_attention(M=M, K=K, S=S, H=H, D=D, backward=backward,
                               itemsize=_ITEMSIZE[dtype])


def read(r):
    if r.trace is None:
        return None
    return trace.roofline_share(trace.kernels(r.trace, "bus_attention"),
                                cost, r.peaks)
