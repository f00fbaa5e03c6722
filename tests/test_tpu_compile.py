"""Compile the main-path Pallas kernels for a described (not attached) TPU
v5e at real widths.

Interpret mode runs a kernel body with Pallas semantics but never asks
Mosaic, so a block layout the chip's compiler refuses passes every other
test in this suite.  These tests lower and compile for one chip of a
described ``v5e:2x2`` topology and check that the kernel is really there
(``tpu_custom_call``): the BusLM attention forward and its gradient
through the ``kernels.ops`` custom VJP at the PROD encoder's shapes, and
the masked one-hot LUT scan of IVF-PQ serving at a served shape.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bus_attention as bus_raw
from repro.kernels import ops
from repro.kernels import pq_scoring

# PROD encoder (configs/speedyfeed_arch.py): K=3 segments of S=32 tokens,
# 12 heads of 64; Sk = S + K bus columns.  M is one block row's worth of
# merged news per grid step times a few steps.
M, K, S, H, D = 64, 3, 32, 12, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _n_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bus_attention_forward_compiles_for_v5e(one_chip, dtype):
    q = _sds(one_chip, (M, K, S, H, D), dtype)
    kv = _sds(one_chip, (M, K, S + K, H, D), dtype)
    mask = _sds(one_chip, (M, K, S + K), jnp.bool_)
    compiled = jax.jit(lambda q, k, v, m: bus_raw.bus_attention(
        q, k, v, m, block_m=8, interpret=False)).lower(
        q, kv, kv, mask).compile()
    assert _n_kernels(compiled) >= 1


def test_bus_attention_grad_through_ops_compiles_for_v5e(one_chip,
                                                         monkeypatch):
    """jax.grad through ops.bus_attention: the custom VJP's forward and
    backward kernels both reach Mosaic (the odd M also exercises the
    wrapper's block padding)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    m_odd = M - 3
    q = _sds(one_chip, (m_odd, K, S, H, D))
    kv = _sds(one_chip, (m_odd, K, S + K, H, D))
    mask = _sds(one_chip, (m_odd, K, S + K), jnp.bool_)

    def loss(q, k, v, mask):
        return (ops.bus_attention(q, k, v, mask) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, mask).compile()
    assert _n_kernels(compiled) >= 2


@pytest.mark.parametrize("dim", [32, 768])
def test_masked_onehot_lut_scan_compiles_for_v5e(one_chip, dim):
    """The served IVF-PQ scan: a pow2 batch bucket, N = nprobe * cap
    gathered uint8 codes with their slot-validity mask, PQ sized as the
    Recommender sizes it (768 = PROD's news_dim: 96 x 256 codes)."""
    from repro.serving import pq_config_for
    pq = pq_config_for(dim)
    n_subvec, n_codes = pq.n_subvec, pq.n_codes
    B, nprobe, cap = 16, 32, 1024
    N = nprobe * cap
    lut = _sds(one_chip, (B, n_subvec, n_codes))
    codes = _sds(one_chip, (B, N, n_subvec), jnp.uint8)
    valid = _sds(one_chip, (B, N), jnp.bool_)
    compiled = jax.jit(lambda l, c, v: pq_scoring.pq_lut_scores(
        l, c, v, block_n=4096, interpret=False, variant="onehot")).lower(
        lut, codes, valid).compile()
    assert _n_kernels(compiled) >= 1


def test_causal_latent_bus_attention_grad_compiles_for_v5e(one_chip,
                                                           monkeypatch):
    """Moonlight-16B-A3B's latent attention through ops.bus_attention: 16
    heads, query/key head dim 192 against value head dim 128, the causal
    in-segment mask; forward and backward kernels both reach Mosaic."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    Mm, Hm, Dq, Dv = 32, 16, 192, 128
    q = _sds(one_chip, (Mm, K, S, Hm, Dq))
    k = _sds(one_chip, (Mm, K, S + K, Hm, Dq))
    v = _sds(one_chip, (Mm, K, S + K, Hm, Dv))
    mask = _sds(one_chip, (Mm, K, S + K), jnp.bool_)

    def loss(q, k, v, mask):
        return (ops.bus_attention(q, k, v, mask, causal=True) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v, mask).compile()
    assert _n_kernels(compiled) >= 2


def test_expert_grouped_matmul_grad_compiles_for_v5e(one_chip, monkeypatch):
    """The held experts' grouped matmul at published widths (hidden 2,048,
    expert 1,408, 8 held, a chunk's 3,072 tokens x top-6 rows): gmm
    forward, and gmm and tgmm backward, all reach Mosaic."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    rows, d, f, g = 3072 * 6, 2048, 1408, 8
    x = _sds(one_chip, (rows, d))
    w = _sds(one_chip, (g, d, f))
    sizes = _sds(one_chip, (g,), jnp.int32)

    def loss(x, w, sizes):
        return (ops.grouped_matmul(x, w, sizes) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, w, sizes).compile()
    assert _n_kernels(compiled) >= 3
