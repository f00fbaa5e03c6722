"""Public, differentiable entry points for the Pallas kernels.

The attention kernels are TRAINABLE: ``flash_attention`` and
``bus_attention`` are ``jax.custom_vjp`` pairs over the forward kernels
and recompute-based backward kernels (kernels/flash_attention.py —
whose forward emits the logsumexp residual the streaming backward needs —
and kernels/bus_attention.py, whose single-tile backward re-derives the
masked softmax locally), so ``jax.grad``
through ``repro.nn.attention(..., impl="pallas")`` and
``core.buslm_encode(..., impl="pallas")`` runs fused Pallas in BOTH
directions — the [S, Sk] probability matrix exists in neither pass.
Residuals are q/k/v (+ o/lse for flash): O(S*D) per head, which is why
the kernels compose with ``jax.checkpoint``/``cfg.remat`` without a
second recompute of anything quadratic. Inputs may be bf16; every kernel
accumulates in f32 and returns gradients in the primal dtypes.

Backend selection happens here and nowhere else: the raw kernels take
``interpret`` as a required keyword, and ``_interpret()`` sets it from
the backend — interpret mode on CPU (the kernel body executes with real
Pallas semantics, the correctness-validation path), Mosaic on TPU.
``resolve_attn_impl`` maps the configs' default ``"auto"`` to "pallas"
exactly when the backend compiles it for real (TPU), so the training hot
path picks the fused kernels up automatically on device while CPU test
runs keep the fast XLA reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import bus_attention as _bus
from . import embedding_bag as _ebag
from . import flash_attention as _flash
from . import pq_scoring as _pq


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_attn_impl() -> str:
    """Pallas when the backend compiles it natively, else the XLA path
    (interpret mode stays available behind an explicit impl="pallas")."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_attn_impl(impl: str | None) -> str:
    if impl in (None, "auto"):
        return default_attn_impl()
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown attn impl: {impl!r}")
    return impl


FLASH_BLOCK = 128      # default block_q/block_k of flash_attention


def flash_attention_supported(seq_len: int) -> bool:
    """Whether the default-block flash kernel accepts this (self-attention)
    sequence length: S must divide into the clamped block and stay
    sublane-aligned.  Callers use this to fall back to XLA instead of
    tripping the kernel's divisibility assert inside jit."""
    return seq_len % 8 == 0 and seq_len % min(FLASH_BLOCK, seq_len) == 0


# ---------------------------------------------------------------------------
# flash attention (custom VJP: fwd emits lse; bwd = dQ pass + dK/dV pass)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(q, k, v, causal, block_q, block_k, interpret):
    return _flash.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash.flash_attention_fwd(q, k, v, causal=causal,
                                        block_q=block_q, block_k=block_k,
                                        interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _flash.flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return _flash_vjp(q, k, v, causal, block_q, block_k, _interpret())


# ---------------------------------------------------------------------------
# bus attention (custom VJP: one fused tile pass per direction; odd merged
# set sizes pad M up to the block instead of degrading block_m to 1)
# ---------------------------------------------------------------------------

def _pad_rows(x, m_pad: int):
    return jnp.pad(x, ((0, m_pad),) + ((0, 0),) * (x.ndim - 1))


def _data_mesh():
    """The ambient mesh when it has a ``data`` axis, else None.  XLA cannot
    partition a Mosaic call, so under a mesh the kernel runs per device
    inside a shard_map over the news axis (the encode set is data
    parallel); ``Trainer`` makes its mesh ambient while it traces."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "data" not in mesh.axis_names:
        return None
    return mesh


def _per_data_shard(fn, mesh, n_out: int):
    """``fn`` over row blocks of every operand on the mesh's data axis."""
    if mesh is None:
        return fn
    spec = P("data")
    return jax.shard_map(fn, mesh=mesh, in_specs=spec,
                         out_specs=spec if n_out == 1 else (spec,) * n_out,
                         check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _bus_vjp(q, k, v, kv_mask, block_m, interpret, causal):
    return _bus_vjp_fwd(q, k, v, kv_mask, block_m, interpret, causal)[0]


def _bus_vjp_fwd(q, k, v, kv_mask, block_m, interpret, causal):
    M = q.shape[0]
    mesh = _data_mesh()
    shards = mesh.shape["data"] if mesh is not None else 1
    m_pad = -M % (min(block_m, M) * shards)
    if m_pad:      # padded rows: all-False mask, sliced off below
        q, k, v = (_pad_rows(t, m_pad) for t in (q, k, v))
        kv_mask = _pad_rows(kv_mask, m_pad)
    fwd = functools.partial(_bus.bus_attention, block_m=block_m,
                            interpret=interpret, causal=causal)
    o = _per_data_shard(fwd, mesh, 1)(q, k, v, kv_mask)
    return o[:M], (q, k, v, kv_mask)


def _bus_vjp_bwd(block_m, interpret, causal, res, do):
    q, k, v, kv_mask = res          # already padded to the block multiple
    m_pad = q.shape[0] - do.shape[0]
    if m_pad:
        do = _pad_rows(do, m_pad)
    bwd = functools.partial(_bus.bus_attention_bwd, block_m=block_m,
                            interpret=interpret, causal=causal)
    dq, dk, dv = _per_data_shard(bwd, _data_mesh(), 3)(q, k, v, kv_mask, do)
    M = q.shape[0] - m_pad
    return dq[:M], dk[:M], dv[:M], None


_bus_vjp.defvjp(_bus_vjp_fwd, _bus_vjp_bwd)


def bus_attention(q, k, v, kv_mask, *, block_m: int = 8,
                  causal: bool = False):
    return _bus_vjp(q, k, v, kv_mask, block_m, _interpret(), causal)


# ---------------------------------------------------------------------------
# grouped matmul (megablox gmm forward, gmm + tgmm backward): one bf16 MXU
# pass on every product, float32 accumulation and float32 results, the
# backend's DEFAULT precision of a float32 matmul
# ---------------------------------------------------------------------------

def _gmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn) of one gmm or tgmm call, as megablox asks it for the
    call's own dims: 128 rows; a contracted or output dim whole when it is
    no multiple of 512 (the expert width 1,408), else in 512s -- every
    block within the default scoped VMEM of a v5e."""
    def tile(x):
        return 512 if x % 512 == 0 else x
    return 128, tile(k), tile(n)


def _megablox():
    """The megablox kernels' module (its package exports ``gmm`` as a
    function of the same name)."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_vjp(lhs, rhs, group_sizes, interpret):
    return _gmm_vjp_fwd(lhs, rhs, group_sizes, interpret)[0]


def _gmm_vjp_fwd(lhs, rhs, group_sizes, interpret):
    lb, rb = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    out = _megablox().gmm(lb, rb, group_sizes, jnp.float32, _gmm_tiling,
                          interpret=interpret)
    return out, (lb, rb, group_sizes)


def _gmm_vjp_bwd(interpret, res, g):
    lb, rb, group_sizes = res
    mb, gb = _megablox(), g.astype(jnp.bfloat16)
    d_lhs = mb.gmm(gb, rb, group_sizes, jnp.float32, _gmm_tiling,
                   transpose_rhs=True, interpret=interpret)
    d_rhs = mb.tgmm(lb.swapaxes(0, 1), gb, group_sizes, jnp.float32,
                    _gmm_tiling, interpret=interpret)
    return d_lhs, d_rhs, None


_gmm_vjp.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """Rows ``lhs[offs[g]:offs[g+1]] @ rhs[g]`` for each group g, where
    ``offs`` are the running sums of ``group_sizes``: lhs [m, k], rhs
    [G, k, n] float32 -> [m, n] float32, gradients float32.  Rows past
    ``sum(group_sizes)`` are not computed and hold garbage (NaN in
    interpret mode), as do their rows of the lhs gradient; a caller masks
    both with a select.  ``m`` is a multiple of 128."""
    return _gmm_vjp(lhs, rhs, group_sizes, _interpret())


# ---------------------------------------------------------------------------
# forward-only kernels
# ---------------------------------------------------------------------------

def embedding_bag(table, idx, weights=None):
    return _ebag.embedding_bag(table, idx, weights, interpret=_interpret())


def pq_lut_scores(lut, codes, valid=None, *, block_n: int = 128,
                  variant: str = "auto"):
    return _pq.pq_lut_scores(lut, codes, valid, block_n=block_n,
                             interpret=_interpret(), variant=variant)
