"""Flash attention (causal, GQA) — Pallas TPU kernels, forward AND backward.

Forward: streaming online-softmax over K/V blocks. For each (batch, q-head,
q-block) the kernel iterates k-blocks in the last grid dimension, keeping
the running max / normalizer / accumulator in VMEM scratch, so the [Sq, Sk]
probability matrix never exists in HBM (the memory-roofline fix for the S^2
attention traffic measured in the dry-run baseline — EXPERIMENTS.md §Perf).
Alongside the output it emits the per-row logsumexp ``lse = m + log(l)``
([B, Hq, Sq] f32) — the only softmax statistic the backward pass needs.

Backward: recompute-based, FlashAttention-2 style, TWO kernels so each
gradient is produced by exactly one streaming accumulation:
  * dQ   — grid (B, Hq, n_q, n_k), k innermost: p = exp(s - lse) is
           rebuilt per tile, ds = p * (dp - delta), dq += ds @ K * scale.
  * dK/dV — grid (B, Hq, n_k, n_q), q innermost: dv += p^T @ dO,
           dk += ds^T @ Q * scale; per-q-head partials are reduced over
           GQA groups by the wrapper (dk[b, h//G] = sum over the group).
``delta = rowsum(dO * O)`` is plain elementwise jnp (O(S*D), no tile).
All accumulation is f32 regardless of input dtype (bf16 in -> bf16 grads
out, f32 math inside); residuals are q/k/v/o/lse — O(S*D) per head, never
the [Sq, Sk] probabilities.

Causal skipping happens at two levels (this replaces the old dead
``isinstance(needed, bool)`` early-out, which passed a traced predicate
through an identity expression and never pruned anything at the grid
level): the K/V (resp. Q) BlockSpec index maps clamp the streamed block
index to the last (resp. first) tile that intersects the diagonal, so
fully-masked tiles re-present the previously fetched block and Mosaic
skips the copy; ``pl.when`` then skips the FLOPs. Sq != Sk is supported
via the explicit ``q_off = Sk - Sq`` row offset (query row i sits at
absolute key position i + q_off — the same convention as ref.py), rather
than being inferred from grid extents.

Layout: [B, H, S, D] blocks; BlockSpecs map the GQA group h -> h // G on
K/V so grouped heads stream the same KV tiles. MXU alignment: block_q /
block_k default 128; D is the head dim.

On this container every call runs in interpret mode (real Pallas
semantics, Python/XLA execution); on TPU the same calls compile to
Mosaic. The differentiable entry point is ``kernels.ops.flash_attention``
(jax.custom_vjp over the _fwd/_bwd pair here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _causal_mask(q_start, k_start, block_q, block_k):
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return rows >= cols


def _kv_index_map(G, causal, block_q, block_k, q_off, n_k):
    """K/V block map for k-innermost grids: above-diagonal tiles clamp to
    the last needed block, so the revisit carries no fresh copy."""
    def index_map(b, h, qi, ki):
        if causal:
            hi = jnp.maximum(qi * block_q + q_off + block_q - 1, 0)
            ki = jnp.minimum(ki, jnp.clip(hi // block_k, 0, n_k - 1))
        return (b, h // G, ki, 0)
    return index_map


def _q_index_map(causal, block_q, block_k, q_off, n_q, rank3=False):
    """Q-side block map for q-innermost grids (dK/dV): below-diagonal
    tiles clamp to the first q block that reaches this k block."""
    def index_map(b, h, ki, qi):
        if causal:
            lo = ki * block_k - q_off - (block_q - 1)
            lo = jnp.where(lo > 0, lo // block_q, 0)   # floor, nonneg domain
            qi = jnp.maximum(qi, jnp.minimum(lo, n_q - 1))
        return (b, h, qi) if rank3 else (b, h, qi, 0)
    return index_map


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                n_k: int, q_off: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + q_off
    k_start = ki * block_k
    needed = True if not causal else k_start <= q_start + block_q - 1

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            s = jnp.where(_causal_mask(q_start, k_start, block_q, block_k),
                          s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, 0]


def _shapes(q, k, block_q, block_k, causal):
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    # causal with Sq > Sk would leave rows with no visible key at all:
    # the streaming kernel emits 0 there while the finite-NEG_INF oracle
    # emits uniform attention — both meaningless, so reject the shape
    assert not causal or Sq <= Sk, \
        "causal flash attention requires Sq <= Sk (rows need >= 1 key)"
    return (B, Sq, Sk, Hq, Hkv, D, Hq // Hkv, block_q, block_k,
            Sq // block_q, Sk // block_k, Sk - Sq)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool):
    """q: [B, Sq, Hq, D]; k/v: [B, Sk, Hkv, D] ->
    (out [B, Sq, Hq, D], lse [B, Hq, Sq] f32)."""
    (B, Sq, Sk, Hq, Hkv, D, G, block_q, block_k, n_q, n_k,
     q_off) = _shapes(q, k, block_q, block_k, causal)
    kv_map = _kv_index_map(G, causal, block_q, block_k, q_off, n_k)
    kernel = functools.partial(_fwd_kernel, scale=D ** -0.5, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k,
                               q_off=q_off)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return o.transpose(0, 2, 1, 3), lse


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool):
    """q: [B, Sq, Hq, D]; k/v: [B, Sk, Hkv, D] -> [B, Sq, Hq, D]."""
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)[0]


# ---------------------------------------------------------------------------
# backward (recompute p from q/k + lse; never materialize [Sq, Sk] in HBM)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale: float, causal: bool, block_q: int,
                   block_k: int, n_k: int, q_off: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + q_off
    k_start = ki * block_k
    needed = True if not causal else k_start <= q_start + block_q - 1

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                            # [bq]
        delta = delta_ref[0, 0]                        # [bq]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            mask = _causal_mask(q_start, k_start, block_q, block_k)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                  # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None])
        if causal:
            ds = jnp.where(mask, ds, 0.0)
        acc_scr[...] += jax.lax.dot(ds, k) * scale

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, block_q: int, block_k: int, n_q: int,
                    q_off: int):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q + q_off
    k_start = ki * block_k
    needed = True if not causal else q_start + block_q - 1 >= k_start

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            mask = _causal_mask(q_start, k_start, block_q, block_k)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                  # [bq, bk]
        dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None])
        if causal:
            ds = jnp.where(mask, ds, 0.0)
        dk_scr[...] += jax.lax.dot_general(ds, q,
                                           (((0,), (0,)), ((), ()))) * scale

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool):
    """(dq, dk, dv) from saved residuals + upstream cotangent ``do``."""
    (B, Sq, Sk, Hq, Hkv, D, G, block_q, block_k, n_q, n_k,
     q_off) = _shapes(q, k, block_q, block_k, causal)
    scale = D ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
        .sum(-1).transpose(0, 2, 1)                    # [B, Hq, Sq]

    kv_map = _kv_index_map(G, causal, block_q, block_k, q_off, n_k)
    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                  block_q=block_q, block_k=block_k, n_k=n_k,
                                  q_off=q_off)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    q_map = _q_index_map(causal, block_q, block_k, q_off, n_q)
    q_map3 = _q_index_map(causal, block_q, block_k, q_off, n_q, rank3=True)
    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   block_k=block_k, n_q=n_q, q_off=q_off)
    dk_h, dv_h = pl.pallas_call(
        dkv_kernel,
        grid=(B, Hq, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_q), q_map3),
            pl.BlockSpec((1, 1, block_q), q_map3),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Sk, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    # reduce per-q-head partials over the GQA group (q head h reads kv head
    # h // G, so its dk/dv contribution lands on that kv head)
    dk = dk_h.reshape(B, Hkv, G, Sk, D).sum(2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, G, Sk, D).sum(2).astype(v.dtype)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))
