"""BusLM segment+bus attention — the paper's kernel (§4.1.3) as a Pallas
TPU kernel, forward AND backward.

Problem shape: M news x K segments x S tokens attend over the segment's own
S keys PLUS the K bus proxies ([CLS] of every segment of the same news) —
Sk = S + K. The paper's config is tiny per-segment (S=32, K=3): the entire
[S, Sk] score tile fits in VMEM, so the economic design is a *fully fused*
attention (scores + mask + softmax + PV in one kernel invocation) rather
than a streaming flash loop — probabilities never exist in HBM, and the
bus concat is materialized once by the wrapper instead of per-layer
(wrapper ops.bus_attention builds kv = [segment, bus]).

Backward is ONE fused kernel over the same grid: because the whole tile
is resident, it recomputes the softmax locally (same max-subtraction
arithmetic as the forward — bit-identical p even for fully-masked padded
segments, where reconstructing p from a stored logsumexp would collapse
under f32 cancellation; that is also why, unlike the flash kernel, the
forward emits no lse residual — it would be dead weight in the hot path)
and produces dq/dk/dv in a single pass, f32 accumulation. Gradients for
the bus *columns* of dk/dv flow back to the segment CLS rows through the
wrapper's concat by plain autodiff — the kernel's custom_vjp boundary is
(q, k, v, mask) -> o, see kernels.ops.bus_attention.

Layout (what Mosaic accepts): the public arrays are [M, K, S, H, D]; the
kernel runs on heads-major [M, K, H, S, D] copies with the segment and
head axes squeezed out of the block, so every block's last two dims are
the full (S, D) / (Sk, D) extents (Sk = 35 needs no padding then), and
the mask travels as int32 [M, K, 1, Sk] (valid 1, masked 0).

Grid: (M_blocks, K, H); block = one head of one segment for a block of
news. The ops wrapper pads M up to a block_m multiple (padded rows carry
an all-False mask and are sliced off) instead of degrading block_m.

Causal encoders (``causal=True``): a query at position i of its segment
sees the segment's keys 0..i (and the mask's bus columns, which the
caller sets per segment), so the key mask alone cannot express it; the
kernel adds the in-segment triangle from iotas, in both passes.  Queries
and keys may have a head dim D other than the values' Dv (latent
attention: 192 against 128); the scale is D^-1/2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _visible(mask, S: int, causal: bool):
    """Which keys each query of a [bm, S, Sk] tile sees: the key mask, and
    under ``causal`` the in-segment triangle (bus columns j >= S stay as
    the mask sets them)."""
    ok = mask != 0                                       # [bm, 1, Sk]
    if not causal:
        return ok
    Sk = mask.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (S, Sk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (S, Sk), 1)
    return ok & ((col >= S) | (col <= row))[None]


def _tile_softmax(q, k, mask, scale, causal=False):
    """Masked scores + stable softmax for one [bm, S, Sk] tile; returns
    (p, l, m) with the exact arithmetic the forward uses."""
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_visible(mask, q.shape[1], causal), s, NEG_INF)  # [bm,S,Sk]
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return p, l, m


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, scale: float,
                causal: bool = False):
    # blocks: q [bm, S, D]; k [bm, Sk, D]; v [bm, Sk, Dv]; mask [bm, 1, Sk]
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    p, l, _ = _tile_softmax(q, k, mask_ref[...], scale, causal)
    o = jax.lax.dot_general(p / l, v, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, dq_ref, dk_ref,
                dv_ref, *, scale: float, causal: bool = False):
    q = q_ref[...].astype(jnp.float32)                   # [bm, S, D]
    k = k_ref[...].astype(jnp.float32)                   # [bm, Sk, D]
    v = v_ref[...].astype(jnp.float32)
    mask = mask_ref[...]
    do = do_ref[...].astype(jnp.float32)                 # [bm, S, Dv]
    p, l, _ = _tile_softmax(q, k, mask, scale, causal)
    p = p / l                                            # [bm, S, Sk]
    dv = jax.lax.dot_general(p, do, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    delta = (p * dp).sum(axis=-1, keepdims=True)         # [bm, S, 1]
    # masked keys' scores came through jnp.where -> their ds is exactly 0
    ds = jnp.where(_visible(mask, q.shape[1], causal), p * (dp - delta),
                   0.0) * scale
    dq = jax.lax.dot_general(ds, k, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(ds, q, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _spec(T, D, block_m):
    """One head of one segment for a block of news: [bm, T, D]."""
    return pl.BlockSpec((block_m, None, None, T, D),
                        lambda m, kk, h: (m, kk, h, 0, 0))


def _specs(S, Sk, D, block_m):
    mask_spec = pl.BlockSpec((block_m, None, 1, Sk),
                             lambda m, kk, h: (m, kk, 0, 0))
    return _spec(S, D, block_m), _spec(Sk, D, block_m), mask_spec


def _heads_major(x):
    """[M, K, T, H, D] <-> [M, K, H, T, D]."""
    return x.transpose(0, 1, 3, 2, 4)


def _prepare(q, k, v, kv_mask, block_m):
    """Heads-major kernel operands and the int32 [M, K, 1, Sk] mask."""
    M, K, S, H, D = q.shape
    Sk = k.shape[2]
    block_m = min(block_m, M)
    assert M % block_m == 0, "pad M to a block_m multiple (ops.bus_attention)"
    ops = (_heads_major(q), _heads_major(k), _heads_major(v),
           kv_mask.astype(jnp.int32)[:, :, None, :])
    return ops, (M, K, S, H, D, Sk, block_m)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "interpret", "causal"))
def bus_attention(q, k, v, kv_mask, *, block_m: int = 8, interpret: bool,
                  causal: bool = False):
    """q: [M, K, S, H, D]; k: [M, K, Sk, H, D]; v: [M, K, Sk, H, Dv];
    kv_mask: [M, K, Sk] -> [M, K, S, H, Dv]. Sk = S + K (bus columns
    appended by the wrapper); masked (padded) keys contribute nothing, and
    under ``causal`` neither do later keys of the query's own segment.
    M % block_m == 0 (the ops wrapper pads odd merged-set sizes up and
    masks the tail).  ``interpret`` has no default: kernels.ops decides
    it per backend."""
    operands, (M, K, S, H, D, Sk, block_m) = _prepare(
        q, k, v, kv_mask, block_m)
    Dv = v.shape[-1]
    q_spec, k_spec, mask_spec = _specs(S, Sk, D, block_m)
    o_spec, v_spec = _spec(S, Dv, block_m), _spec(Sk, Dv, block_m)
    kernel = functools.partial(_fwd_kernel, scale=D ** -0.5, causal=causal)
    o = pl.pallas_call(
        kernel,
        grid=(M // block_m, K, H),
        in_specs=[q_spec, k_spec, v_spec, mask_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((M, K, H, S, Dv), q.dtype),
        interpret=interpret,
    )(*operands)
    return _heads_major(o)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "interpret", "causal"))
def bus_attention_bwd(q, k, v, kv_mask, do, *, block_m: int = 8,
                      interpret: bool, causal: bool = False):
    """(dq, dk, dv) for one fused tile pass; mask gets no cotangent."""
    operands, (M, K, S, H, D, Sk, block_m) = _prepare(
        q, k, v, kv_mask, block_m)
    Dv = v.shape[-1]
    q_spec, k_spec, mask_spec = _specs(S, Sk, D, block_m)
    o_spec, v_spec = _spec(S, Dv, block_m), _spec(Sk, Dv, block_m)
    kernel = functools.partial(_bwd_kernel, scale=D ** -0.5, causal=causal)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(M // block_m, K, H),
        in_specs=[q_spec, k_spec, v_spec, mask_spec, o_spec],
        out_specs=[q_spec, k_spec, v_spec],
        out_shape=[
            jax.ShapeDtypeStruct((M, K, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((M, K, H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((M, K, H, Sk, Dv), v.dtype),
        ],
        interpret=interpret,
    )(*operands, _heads_major(do))
    return _heads_major(dq), _heads_major(dk), _heads_major(dv)
