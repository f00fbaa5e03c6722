"""Attention substrate: GQA/MHA, causal/bidirectional, qk-norm, chunked-local
(Llama-4-style iRoPE locals), and single-token decode against a KV cache.

The jnp path here is the XLA reference implementation used for dry-runs and
smoke tests; the Pallas flash kernels in ``repro.kernels`` are drop-in
*trainable* replacements for the hot inner product (custom-VJP forward and
backward kernels, selected via ``impl='pallas'`` or the default
``impl='auto'``, which picks them up on TPU).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .core import init_dense, dense, init_rmsnorm, rmsnorm
from .rope import rope_cos_sin, apply_rope

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qkv_bias: bool = False
    out_bias: bool = False
    qk_norm: bool = False
    rope_fraction: float = 1.0       # 0.0 disables rope (NoPE layers)
    rope_theta: float = 10000.0
    causal: bool = True
    chunk_size: Optional[int] = None  # chunked-local attention window
    block_q: Optional[int] = None     # query-blocked attention (flash-like)
    dtype: str = "float32"


def init_attention(key, cfg: AttnConfig, param_dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    d, hq, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    p = {
        "q": init_dense(ks[0], d, hq * hd, use_bias=cfg.qkv_bias, stddev=0.02,
                        dtype=param_dtype),
        "k": init_dense(ks[1], d, hk * hd, use_bias=cfg.qkv_bias, stddev=0.02,
                        dtype=param_dtype),
        "v": init_dense(ks[2], d, hk * hd, use_bias=cfg.qkv_bias, stddev=0.02,
                        dtype=param_dtype),
        "o": init_dense(ks[3], hq * hd, d, use_bias=cfg.out_bias, stddev=0.02,
                        dtype=param_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(ks[4], hd, param_dtype)
        p["k_norm"] = init_rmsnorm(ks[5], hd, param_dtype)
    return p


# ---------------------------------------------------------------------------
# core scaled-dot-product with GQA grouping
# ---------------------------------------------------------------------------

def sdpa(q, k, v, *, causal: bool, mask=None, q_offset: int | None = None):
    """q/k: [B,Sq|Sk,Hq|Hkv,D], v: [B,Sk,Hkv,Dv] -> [B,Sq,Hq,Dv];
    Hq % Hkv == 0.

    mask: optional [B, Sk] (key validity) or [B, Sq, Sk] additive-compatible
    boolean mask. ``q_offset``: starting absolute position of q for causal
    masking when Sq != Sk (e.g. chunked prefill / decode).
    """
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    scale = D ** -0.5
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    if causal:
        off = q_offset if q_offset is not None else Sk - Sq
        qpos = jnp.arange(Sq)[:, None] + off
        kpos = jnp.arange(Sk)[None, :]
        cmask = qpos >= kpos                                # [Sq, Sk]
        logits = jnp.where(cmask[None, None, None], logits, NEG_INF)
    if mask is not None:
        if mask.ndim == 2:        # [B, Sk]
            m = mask[:, None, None, None, :]
        else:                     # [B, Sq, Sk]
            m = mask[:, None, None, :, :]
        logits = jnp.where(m, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(B, Sq, Hq, v.shape[-1])


def blocked_sdpa(q, k, v, *, causal: bool, mask=None, block_q: int = 512):
    """Query-blocked attention (XLA flash analogue, §Perf/H3).

    Computes attention one query block at a time under jax.checkpoint, so
    only a [B, block_q, H, Sk] logit tile is ever live (fwd AND bwd) instead
    of the full [B, Sq, H, Sk] matrix — the memory-roofline fix for long
    sequences when the Pallas kernel isn't available to the backend.
    Exact softmax per block (full keys visible); numerics match sdpa.
    """
    B, S, Hq, D = q.shape
    n = S // block_q
    qb = q.reshape(B, n, block_q, Hq, D).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        i, qc = args
        return sdpa(qc, k, v, causal=causal, mask=mask,
                    q_offset=i * block_q)

    outs = jax.lax.map(one, (jnp.arange(n), qb))
    return outs.swapaxes(0, 1).reshape(B, S, Hq, D)


def chunked_sdpa(q, k, v, *, chunk: int, mask=None):
    """Causal attention restricted to hard chunks of size ``chunk``.

    Sub-quadratic: cost O(S * chunk). Requires S % chunk == 0.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    n = S // chunk
    qc = q.reshape(B * n, chunk, Hq, D)

    def split(t):
        return t.reshape(B, n, chunk, Hkv, D).reshape(B * n, chunk, Hkv, D)

    mc = None
    if mask is not None:
        mc = mask.reshape(B * n, chunk)
    out = sdpa(qc, split(k), split(v), causal=True, mask=mc)
    return out.reshape(B, S, Hq, D)


# ---------------------------------------------------------------------------
# full attention layer (projections + rope + sdpa)
# ---------------------------------------------------------------------------

def attention(params, x, cfg: AttnConfig, *, positions=None, mask=None,
              impl: str = "auto"):
    """Self-attention over x: [B, S, d_model].

    ``impl="auto"`` resolves to the trainable Pallas flash kernel wherever
    the backend compiles it natively (see kernels.ops.resolve_attn_impl);
    gradients flow through its custom VJP. The flash kernel carries no
    key-validity mask and no local window, and requires S to divide into
    its blocks — masked calls, chunked-local layers, and odd sequence
    lengths fall back to the XLA path.
    """
    from repro.kernels.ops import flash_attention_supported, resolve_attn_impl
    impl = resolve_attn_impl(impl)
    B, S, _ = x.shape
    chunked_local = (cfg.chunk_size is not None and cfg.causal
                     and S > cfg.chunk_size and S % cfg.chunk_size == 0)
    if impl == "pallas" and (mask is not None or chunked_local
                             or not flash_attention_supported(S)):
        impl = "xla"
    hq, hk, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = dense(params["q"], x).reshape(B, S, hq, hd)
    k = dense(params["k"], x).reshape(B, S, hk, hd)
    v = dense(params["v"], x).reshape(B, S, hk, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if cfg.rope_fraction > 0.0:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        d_rot = int(hd * cfg.rope_fraction)
        d_rot -= d_rot % 2
        cos, sin = rope_cos_sin(positions, d_rot, theta=cfg.rope_theta)
        q = apply_rope(q, cos, sin, fraction=cfg.rope_fraction)
        k = apply_rope(k, cos, sin, fraction=cfg.rope_fraction)
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=cfg.causal)
    elif chunked_local:
        out = chunked_sdpa(q, k, v, chunk=cfg.chunk_size, mask=mask)
    elif (cfg.block_q is not None and S > cfg.block_q
          and S % cfg.block_q == 0):
        out = blocked_sdpa(q, k, v, causal=cfg.causal, mask=mask,
                           block_q=cfg.block_q)
    else:
        out = sdpa(q, k, v, causal=cfg.causal, mask=mask)
    return dense(params["o"], out.reshape(B, S, hq * hd))


# ---------------------------------------------------------------------------
# decode: one new token against a KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, cfg: AttnConfig, dtype=jnp.bfloat16):
    hk, hd = cfg.n_kv, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, hk, hd), dtype),
        "v": jnp.zeros((batch, max_len, hk, hd), dtype),
    }


# ---------------------------------------------------------------------------
# int8-quantized KV cache (decode is KV-bandwidth-bound: §Roofline — this
# halves the dominant memory term; per-token-per-head absmax scales)
# ---------------------------------------------------------------------------

def init_kv_cache_q8(batch: int, max_len: int, cfg: AttnConfig):
    hk, hd = cfg.n_kv, cfg.head_dim
    return {
        "k_q": jnp.zeros((batch, max_len, hk, hd), jnp.int8),
        "k_s": jnp.zeros((batch, max_len, hk), jnp.float32),
        "v_q": jnp.zeros((batch, max_len, hk, hd), jnp.int8),
        "v_s": jnp.zeros((batch, max_len, hk), jnp.float32),
    }


def _q8(x):
    """x: [B, 1, H, D] -> (int8 values, [B, 1, H] scales)."""
    s = jnp.maximum(jnp.abs(x).max(axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def _dq8(q, s, dtype):
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


def decode_attention(params, x, cache, cache_index, cfg: AttnConfig):
    """x: [B, 1, d]; cache: dict(k,v) [B, S_max, Hkv, D]; cache_index: scalar
    int32 — number of valid tokens already in the cache. Returns (out, cache').

    Global layers attend over the whole (masked) cache; chunked-local layers
    attend only over the trailing ``chunk_size`` window (sub-quadratic decode).
    """
    B = x.shape[0]
    hq, hk, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = dense(params["q"], x).reshape(B, 1, hq, hd)
    k = dense(params["k"], x).reshape(B, 1, hk, hd)
    v = dense(params["v"], x).reshape(B, 1, hk, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if cfg.rope_fraction > 0.0:
        pos = jnp.full((B, 1), cache_index, dtype=jnp.int32)
        d_rot = int(hd * cfg.rope_fraction)
        d_rot -= d_rot % 2
        cos, sin = rope_cos_sin(pos, d_rot, theta=cfg.rope_theta)
        q = apply_rope(q, cos, sin, fraction=cfg.rope_fraction)
        k = apply_rope(k, cos, sin, fraction=cfg.rope_fraction)
    # write new kv (plain or int8-quantized layout)
    quant = "k_q" in cache
    if quant:
        kq, ks = _q8(k)
        vq, vs = _q8(v)
        new_cache = {
            "k_q": jax.lax.dynamic_update_slice(cache["k_q"], kq,
                                                (0, cache_index, 0, 0)),
            "k_s": jax.lax.dynamic_update_slice(cache["k_s"], ks,
                                                (0, cache_index, 0)),
            "v_q": jax.lax.dynamic_update_slice(cache["v_q"], vq,
                                                (0, cache_index, 0, 0)),
            "v_s": jax.lax.dynamic_update_slice(cache["v_s"], vs,
                                                (0, cache_index, 0)),
        }
        S_max = new_cache["k_q"].shape[1]

        def read(start, w):
            kw = jax.lax.dynamic_slice(new_cache["k_q"], (0, start, 0, 0),
                                       (B, w, hk, hd))
            ksw = jax.lax.dynamic_slice(new_cache["k_s"], (0, start, 0),
                                        (B, w, hk))
            vw = jax.lax.dynamic_slice(new_cache["v_q"], (0, start, 0, 0),
                                       (B, w, hk, hd))
            vsw = jax.lax.dynamic_slice(new_cache["v_s"], (0, start, 0),
                                        (B, w, hk))
            return _dq8(kw, ksw, q.dtype), _dq8(vw, vsw, q.dtype)
    else:
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype),
                (0, cache_index, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype),
                (0, cache_index, 0, 0)),
        }
        S_max = new_cache["k"].shape[1]

        def read(start, w):
            kw = jax.lax.dynamic_slice(new_cache["k"], (0, start, 0, 0),
                                       (B, w, hk, hd))
            vw = jax.lax.dynamic_slice(new_cache["v"], (0, start, 0, 0),
                                       (B, w, hk, hd))
            return kw.astype(q.dtype), vw.astype(q.dtype)

    if cfg.chunk_size is not None and cfg.chunk_size < S_max:
        # local window: trailing chunk_size entries ending at cache_index
        w = cfg.chunk_size
        start = jnp.clip(cache_index + 1 - w, 0, S_max - w)
        kw, vw = read(start, w)
        valid = (jnp.arange(w)[None, :] + start[None]) <= cache_index
        valid = jnp.broadcast_to(valid, (B, w))
        out = sdpa(q, kw, vw, causal=False, mask=valid)
    else:
        kw, vw = read(0, S_max)
        valid = jnp.arange(S_max)[None, :] <= cache_index
        valid = jnp.broadcast_to(valid, (B, S_max))
        out = sdpa(q, kw, vw, causal=False, mask=valid)
    out = dense(params["o"], out.reshape(B, 1, hq * hd))
    return out, new_cache
