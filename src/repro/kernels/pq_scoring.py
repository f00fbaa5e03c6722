"""PQ asymmetric-distance LUT scoring — Pallas TPU kernel.

The ANN retrieval hot path (serving §5.1.4): after product quantization the
corpus is [N, M] uint8 codes; a query is turned into a lookup table
LUT[m, k] = <q_m, codebook[m, k]> and the score of candidate n is
sum_m LUT[m, codes[n, m]] — a gather + segment accumulate per candidate.

Two block-scoring variants, selected by ``variant``:

  "onehot"  TPU-native: the per-code gather is hostile to the VPU (random
            lane indexing), so for each sub-quantizer m the kernel builds
            the one-hot [K, block_n] of its codes with a sublane-iota
            compare (pure VPU) and accumulates LUT row m [1, K] times it
            on the MXU, at HIGHEST precision so the f32 LUT entries come
            through whole.  K*M extra FLOPs per candidate, zero irregular
            memory traffic — the MXU is idle during a scan anyway.
  "gather"  direct LUT gather (codes offset into the flattened [M*K]
            table) + sum over M.  In interpret mode the one-hot
            materialization is real host memory traffic, so the gather is
            ~an order of magnitude cheaper there; Mosaic cannot lower it.
  "auto"    gather when interpreting (CPU) — measured strictly faster at
            every M*K in BENCH_retrieval.json — else the MXU contraction.

Layouts:
  lut    [B, M, K]  f32   one table per query
  codes  [Bc, N, M] uint8 (or int32) — Bc == B (per-query candidate
                          lists, IVF path) or Bc == 1 (one shared corpus
                          scan, flat-PQ path — the block index_map
                          broadcasts without copying)
  valid  [Bv, N]    bool  optional slot validity (padded-CSR gathers carry
                          unwritten tail slots; invalid scores come back
                          -inf so a downstream top-k never selects them)
  out    [B, N]     f32

Inside, the kernel sees what Mosaic tiles: codes transposed to
[Bc, M, N] (candidates on the 128 lanes), validity and scores as
[B, 1, N] rows, and block_n a whole number of lane tiles unless one
block spans all of N.

Grid: (B, N / block_n); the LUT block stays resident across the inner
dimension while candidate blocks stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _row_scores_onehot(lut_ref, codes_ref, *, n_codes: int):
    """[1, bn] scores of one sub-quantizer: the one-hot [K, bn] of its
    codes (a VPU compare against a sublane iota) times its LUT row [1, K]
    on the MXU."""
    bn = codes_ref.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_codes, bn), 0)
    onehot = (iota == codes_ref[...].astype(jnp.int32)).astype(jnp.float32)
    return jax.lax.dot_general(              # f32 LUT entries kept whole
        lut_ref[...].astype(jnp.float32), onehot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _row_scores_gather(lut_ref, codes_ref, *, n_codes: int):
    """[1, bn] scores of one sub-quantizer by a direct LUT-row gather."""
    del n_codes
    return jnp.take(lut_ref[0].astype(jnp.float32),
                    codes_ref[...].astype(jnp.int32), axis=0)


_SCORES = {"onehot": _row_scores_onehot, "gather": _row_scores_gather}


def _kernel(lut_ref, codes_ref, *rest, n_codes: int, variant: str):
    """Grid step (b, n, m): add sub-quantizer m's scores into the output
    block, which stays resident while m (the innermost axis) advances;
    with a validity operand, mask the finished block at the last m."""
    *valid_ref, o_ref = rest
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _SCORES[variant](lut_ref, codes_ref, n_codes=n_codes)
    if valid_ref:
        @pl.when(m == pl.num_programs(2) - 1)
        def _mask():
            o_ref[...] = jnp.where(valid_ref[0][...] != 0, o_ref[...],
                                   -jnp.inf)


def _resolve_variant(variant: str, interpret: bool) -> str:
    if variant == "auto":
        # interpret mode executes the kernel body as real host ops, where
        # the one-hot materialization dominates; compiled Mosaic keeps the
        # MXU contraction (the gather is not a Mosaic-lowerable op)
        return "gather" if interpret else "onehot"
    if variant not in _SCORES:
        raise ValueError(f"unknown pq scan variant: {variant!r}")
    return variant


@functools.partial(jax.jit, static_argnames=("block_n", "interpret",
                                             "variant"))
def pq_lut_scores(lut, codes, valid=None, *, block_n: int = 128,
                  interpret: bool, variant: str = "auto"):
    """lut: [B, M, K] f32; codes: [Bc, N, M] uint8/int32 with Bc in {1, B}.

    Returns [B, N] f32: out[b, n] = sum_m lut[b, m, codes[min(b,Bc-1), n, m]].
    With valid [Bv, N] (Bv in {1, B}), out[b, n] = -inf where not
    valid[min(b,Bv-1), n] — the padded-CSR gather path scores fixed-width
    candidate blocks whose tail slots hold no entry.  ``variant`` picks
    the block-scoring strategy (see module docstring); ``interpret`` has
    no default — kernels.ops decides it per backend.
    """
    variant = _resolve_variant(variant, interpret)
    B, M, K = lut.shape
    Bc, N, Mc = codes.shape
    assert Mc == M and Bc in (1, B), (codes.shape, lut.shape)
    block_n = min(block_n, N)
    if block_n < N:         # a partial block must be whole lane tiles
        block_n = min(-(-block_n // LANE) * LANE, N)
    pad = (-N) % block_n
    codes = jnp.pad(codes, ((0, 0), (0, pad), (0, 0)))
    codes = codes.transpose(0, 2, 1)[:, :, None, :]          # [Bc, M, 1, Np]
    Np = N + pad

    def _bcast(b_shared):
        """Block index_map over (b, n, m), broadcasting b when shared."""
        if b_shared:
            return lambda b, n, m: (0, m, 0, n)
        return lambda b, n, m: (b, m, 0, n)

    in_specs = [
        pl.BlockSpec((None, None, 1, K), lambda b, n, m: (b, m, 0, 0)),
        pl.BlockSpec((None, None, 1, block_n), _bcast(Bc == 1)),
    ]
    operands = [lut[:, :, None, :], codes]
    if valid is not None:
        Bv, Nv = valid.shape
        assert Nv == N and Bv in (1, B), (valid.shape, lut.shape)
        valid = jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, pad)))
        in_specs.append(pl.BlockSpec(
            (None, 1, block_n),
            (lambda b, n, m: (0, 0, n)) if Bv == 1
            else (lambda b, n, m: (b, 0, n))))
        operands.append(valid[:, None, :])
    out = pl.pallas_call(
        functools.partial(_kernel, n_codes=K, variant=variant),
        grid=(B, Np // block_n, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, 1, block_n),
                               lambda b, n, m: (b, 0, n)),
        out_shape=jax.ShapeDtypeStruct((B, 1, Np), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[:, 0, :N]
