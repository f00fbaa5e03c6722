"""Fused EmbeddingBag (gather + weighted segment reduce) — Pallas TPU kernel.

The recsys hot path: table [V, d] lives in HBM; per (batch row, field) the
kernel accumulates nnz weighted rows. TPU-native design: the flattened
index matrix is a *scalar-prefetch* operand, and the table BlockSpec's
index_map selects the table row for each grid step from the prefetched
indices — the canonical TPU embedding-gather pattern (rows stream HBM->VMEM
without a materialized [B, F, nnz, d] intermediate).

Grid: (B, F, nnz); the output block [1, 1, d] accumulates in place across
the nnz steps (Pallas keeps the same output block resident in VMEM while
only the last grid dimension advances).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, w_ref, table_ref, o_ref):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[0, 0, 0]
    o_ref[...] += (table_ref[...].astype(jnp.float32)
                   * w.astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(table, idx, weights=None, *, interpret: bool):
    """table: [V, d]; idx: [B, F, nnz] int32; weights: [B, F, nnz] or None.

    Returns [B, F, d] = sum_n weights[b,f,n] * table[idx[b,f,n]].
    """
    B, F, nnz = idx.shape
    V, d = table.shape
    if weights is None:
        weights = jnp.ones((B, F, nnz), jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, F, nnz),
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda b, f, n, idx_p: (b, f, n)),
            pl.BlockSpec((1, d), lambda b, f, n, idx_p: (idx_p[b, f, n], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, f, n, idx_p: (b, f, 0)),
    )
    kernel = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, F, d), table.dtype),
        interpret=interpret,
    )
    return kernel(idx, weights, table)
