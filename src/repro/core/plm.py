"""PLM news encoder substrate: the encoder layer as its config describes
it.

Two layer kinds.  The paper's (``decoder=None``): a UniLM-like post-LN
bidirectional transformer (UniLMv2-base is 12L x 768d x 12H; here the
architecture at configurable scale with random init), whose OBoW
*frequency embedding* (paper §4.2.1) is an input embedding alongside
token / position / segment embeddings.  A DeepSeek-V3 decoder
(``decoder=DecoderConfig(...)``, e.g. Moonlight-16B-A3B): pre-norm
RMSNorm, multi-head latent attention with decoupled RoPE, leading dense
SwiGLU layers and then expert layers, token embeddings only
(core/buslm.py has the layer equations).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.nn import (AttnConfig, attention, dense, init_attention,
                      init_dense, init_embedding, init_layernorm, layernorm,
                      embed, normal_init)
from repro.nn.moe import RoutedMoEConfig, init_routed_moe


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """A DeepSeek-V3 decoder stack as the news encoder: multi-head latent
    attention (no query compression, ``q_lora_rank`` null), the first
    ``n_dense`` layers with a dense SwiGLU FFN of the PLM's ``d_ff``, the
    rest with the expert layer ``moe``."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50_000.0
    rms_eps: float = 1e-5
    n_dense: int = 1                  # first_k_dense_replace
    moe: RoutedMoEConfig | None = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class PLMConfig:
    vocab: int = 30522
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    max_len: int = 512           # positions
    n_segments: int = 3          # BusLM K (title/abstract/body); 1 = no split
    seg_len: int = 32            # tokens per segment
    max_freq: int = 32           # OBoW frequency embedding vocab
    use_freq_embedding: bool = True
    news_dim: int = 64           # final news embedding dim (paper uses d_model;
                                 # production uses a projection — configurable)
    use_bus: bool = True
    dtype: str = "float32"
    remat: bool = False
    attn_impl: str = "auto"      # auto (pallas on TPU, xla elsewhere) |
    #                              xla | pallas — resolved per call by
    #                              kernels.ops.resolve_attn_impl
    decoder: DecoderConfig | None = None   # None: the paper's BusLM layer

    @property
    def attn(self) -> AttnConfig:
        return AttnConfig(d_model=self.d_model, n_heads=self.n_heads,
                          n_kv=self.n_heads, head_dim=self.d_model // self.n_heads,
                          qkv_bias=True, out_bias=True, qk_norm=False,
                          rope_fraction=0.0, causal=False)


def init_plm(key, cfg: PLMConfig, param_dtype=jnp.float32):
    if cfg.decoder is not None:
        return _init_decoder(key, cfg, param_dtype)
    ks = jax.random.split(key, 8 + cfg.n_layers)
    p = {
        "tok_emb": init_embedding(ks[0], cfg.vocab, cfg.d_model, dtype=param_dtype),
        "pos_emb": init_embedding(ks[1], cfg.max_len, cfg.d_model, dtype=param_dtype),
        "seg_emb": init_embedding(ks[2], max(cfg.n_segments, 2), cfg.d_model,
                                  dtype=param_dtype),
        "emb_ln": init_layernorm(ks[3], cfg.d_model, param_dtype),
        # two-level attention pooling (paper Appendix Eq. 9-14)
        "pool_tok": _init_addattn(ks[4], cfg.d_model, param_dtype),
        "pool_seg": _init_addattn(ks[5], cfg.d_model, param_dtype),
        "out_proj": init_dense(ks[6], cfg.d_model, cfg.news_dim, use_bias=True,
                               dtype=param_dtype),
    }
    if cfg.use_freq_embedding:
        p["freq_emb"] = init_embedding(ks[7], cfg.max_freq, cfg.d_model,
                                       dtype=param_dtype)
    layer_keys = ks[8:]
    p["layers"] = jax.vmap(lambda k: _init_layer(k, cfg, param_dtype))(
        jnp.stack(layer_keys))
    return p


def _init_addattn(key, dim, param_dtype):
    k1, k2 = jax.random.split(key)
    return {"proj": init_dense(k1, dim, dim, use_bias=True, dtype=param_dtype),
            "query": (jax.random.normal(k2, (dim,)) * 0.02).astype(param_dtype)}


def _init_layer(key, cfg: PLMConfig, param_dtype):
    ks = jax.random.split(key, 5)
    return {
        "attn": init_attention(ks[0], cfg.attn, param_dtype),
        "ln1": init_layernorm(ks[1], cfg.d_model, param_dtype),
        "ffn_up": init_dense(ks[2], cfg.d_model, cfg.d_ff, use_bias=True,
                             stddev=0.02, dtype=param_dtype),
        "ffn_down": init_dense(ks[3], cfg.d_ff, cfg.d_model, use_bias=True,
                               stddev=0.02, dtype=param_dtype),
        "ln2": init_layernorm(ks[4], cfg.d_model, param_dtype),
    }


def _init_decoder(key, cfg: PLMConfig, param_dtype):
    """Token embedding, ``n_dense`` dense layers, the expert layers, the
    final RMSNorm and the paper's pooling head (``layers`` stacks the
    expert layers, ``dense_layers`` the dense ones, for two scans)."""
    dc, d = cfg.decoder, cfg.d_model
    ks = jax.random.split(key, 6)

    def layer(k, moe: bool):
        k1, k2 = jax.random.split(k)
        out = {"attn_norm": {"scale": jnp.ones((d,), param_dtype)},
               "mla": _init_mla(k1, cfg, param_dtype),
               "ffn_norm": {"scale": jnp.ones((d,), param_dtype)}}
        if moe:
            out["moe"] = init_routed_moe(k2, dc.moe, param_dtype)
        else:
            k3, k4, k5 = jax.random.split(k2, 3)
            out["ffn"] = {
                "w1": normal_init(k3, (d, cfg.d_ff), 0.02, param_dtype),
                "w3": normal_init(k4, (d, cfg.d_ff), 0.02, param_dtype),
                "w2": normal_init(k5, (cfg.d_ff, d), 0.02, param_dtype)}
        return out

    n_moe = cfg.n_layers - dc.n_dense
    return {
        "tok_emb": init_embedding(ks[0], cfg.vocab, d, dtype=param_dtype),
        "dense_layers": jax.vmap(lambda k: layer(k, False))(
            jax.random.split(ks[1], dc.n_dense)),
        "layers": jax.vmap(lambda k: layer(k, True))(
            jax.random.split(ks[2], n_moe)),
        "final_norm": {"scale": jnp.ones((d,), param_dtype)},
        "pool_tok": _init_addattn(ks[3], d, param_dtype),
        "pool_seg": _init_addattn(ks[4], d, param_dtype),
        "out_proj": init_dense(ks[5], d, cfg.news_dim, use_bias=True,
                               dtype=param_dtype),
    }


def _init_mla(key, cfg: PLMConfig, param_dtype):
    dc, d, H = cfg.decoder, cfg.d_model, cfg.n_heads
    r, dr = dc.kv_lora_rank, dc.qk_rope_head_dim
    ks = jax.random.split(key, 4)
    return {"wq": normal_init(ks[0], (d, H * dc.qk_head_dim), 0.02,
                              param_dtype),
            "wkva": normal_init(ks[1], (d, r + dr), 0.02, param_dtype),
            "kv_norm": {"scale": jnp.ones((r,), param_dtype)},
            "wkvb": normal_init(ks[2], (r, H * (dc.qk_nope_head_dim
                                                + dc.v_head_dim)),
                                0.02, param_dtype),
            "wo": normal_init(ks[3], (H * dc.v_head_dim, d), 0.02,
                              param_dtype)}


def additive_attention(p, h, mask=None):
    """Eq. 9-11 / 12-14: softmax(q^T tanh(W h + b)) weighted sum over axis -2.

    h: [..., N, d]; mask: [..., N] bool. Returns [..., d].
    """
    a = jnp.einsum("...nd,d->...n",
                   jnp.tanh(dense(p["proj"], h).astype(jnp.float32)),
                   p["query"].astype(jnp.float32))
    if mask is not None:
        a = jnp.where(mask, a, -1e30)
    w = jax.nn.softmax(a, axis=-1).astype(h.dtype)
    return jnp.einsum("...n,...nd->...d", w, h)


def embed_inputs(p, cfg: PLMConfig, tokens, freq=None):
    """tokens: [B, K, S] -> [B, K, S, d] summed embeddings."""
    B, K, S = tokens.shape
    h = embed(p["tok_emb"], tokens)
    h = h + embed(p["pos_emb"], jnp.arange(S))[None, None]
    h = h + embed(p["seg_emb"], jnp.arange(K))[None, :, None]
    if cfg.use_freq_embedding and freq is not None:
        h = h + embed(p["freq_emb"], jnp.clip(freq, 0, cfg.max_freq - 1))
    return layernorm(p["emb_ln"], h)


def ffn(layer, x):
    h = jax.nn.gelu(dense(layer["ffn_up"], x))
    return dense(layer["ffn_down"], h)
