"""BusLM — the paper's economic news encoder (§4.1.3, Appendix A.1.1).

The news article is split into K segments [B, K, S]. Each transformer layer:
  Bus^i   = { H_j^i[0] }_{j=1..K}                      (Eq. 6: CLS proxies)
  H^{i+1} = Transformer^i([H_j^i, Bus^i])              (Eq. 7)
with Q from the segment only and K/V from [segment, bus] (Eq. 8), so
attention cost is O(K * S * (S + K)) = O(N^2/K + NK) instead of O(N^2).

The final embedding uses two-level additive attention pooling (Eq. 9-14).

TPU adaptation: all segments are encoded in one batched einsum
([B*K, S] x [B*K, S+K]) — MXU-aligned when S+K pads to a lane multiple; the
Pallas kernel in kernels/bus_attention.py fuses the concat into the flash
inner loop so the bus never materializes in HBM.

The same bus under a causal decoder (``PLMConfig.decoder``, a DeepSeek-V3
stack such as Moonlight-16B-A3B), layer by layer on h [M, K, S, d]:

  h <- h + MLA(RMSNorm(h));   h <- h + FFN(RMSNorm(h))

  MLA: q = W_q x, per head [q_nope 128, q_rope 64];  [c_kv 512, k_rope 64]
       = W_kva x, k_rope shared by every head;  [k_nope 128, v 128] =
       W_kvb RMSNorm(c_kv) per head.  RoPE (theta 50,000) rotates q_rope
       and k_rope at each token's position within its segment; q = [q_nope,
       q_rope], k = [k_nope, k_rope], scale 1/sqrt(192), then W_o.
  Bus: under a causal mask position 0 sees nothing, so each segment's
       proxy is its LAST real token: the bus entries' keys and values are
       those of each segment's last real token.  A query sees its own
       segment causally, plus the proxies of the other segments.
  FFN: the first ``n_dense`` layers SwiGLU of width d_ff; then the expert
       layer (nn/moe.py::routed_moe): s = sigmoid(W_r x); top-6 of
       s + bias; gates s over the 6, renormalised, times 2.446; plus the
       shared experts, one SwiGLU of width 2,816.

  Then the final RMSNorm, the paper's two-level additive pooling and the
  projection to ``news_dim``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.nn import (apply_rope, dense, layernorm, rmsnorm, rope_cos_sin,
                      sdpa)
from repro.nn.moe import routed_moe, swiglu
from .plm import PLMConfig, additive_attention, embed_inputs, ffn


def _bus_attention_layer(layer, h, mask, cfg: PLMConfig, impl: str):
    """One BusLM layer. h: [M, K, S, d]; mask: [M, K, S] bool."""
    M, K, S, d = h.shape
    nh = cfg.n_heads
    hd = d // nh
    ap = layer["attn"]

    use_bus = cfg.use_bus and K > 1
    if use_bus:
        bus = h[:, :, 0, :]                                   # [M, K, d]
        bus_b = jnp.broadcast_to(bus[:, None], (M, K, K, d))  # per-segment copy
        kv_in = jnp.concatenate([h, bus_b], axis=2)           # [M, K, S+K, d]
        seg_valid = mask.any(axis=-1)                         # [M, K]
        bus_mask = jnp.broadcast_to(seg_valid[:, None], (M, K, K))
        kv_mask = jnp.concatenate([mask, bus_mask], axis=2)   # [M, K, S+K]
    else:
        kv_in, kv_mask = h, mask

    Sk = kv_in.shape[2]
    q = dense(ap["q"], h).reshape(M * K, S, nh, hd)
    k = dense(ap["k"], kv_in).reshape(M * K, Sk, nh, hd)
    v = dense(ap["v"], kv_in).reshape(M * K, Sk, nh, hd)

    if impl == "pallas" and use_bus:
        from repro.kernels import ops as kops
        out = kops.bus_attention(
            q.reshape(M, K, S, nh, hd),
            k.reshape(M, K, Sk, nh, hd),
            v.reshape(M, K, Sk, nh, hd),
            kv_mask,
        ).reshape(M * K, S, nh, hd)
    else:
        out = sdpa(q, k, v, causal=False, mask=kv_mask.reshape(M * K, Sk))
    out = dense(ap["o"], out.reshape(M, K, S, d))

    h = layernorm(layer["ln1"], h + out)
    h = layernorm(layer["ln2"], h + ffn(layer, h))
    return h


def buslm_encode(params, cfg: PLMConfig, tokens, freq=None, mask=None,
                 impl: str | None = None):
    """Encode news articles. tokens: [M, K, S] -> [M, news_dim].  A
    config with a ``decoder`` runs that stack (``decoder_encode``).

    Valid (non-pad) tokens are ``tokens != 0``; pass ``mask`` to override.
    ``impl`` defaults to ``cfg.attn_impl`` ("auto" resolves to the fused
    Pallas kernels whenever the backend compiles them natively); gradients
    flow through the kernel's custom VJP, so this is the training path,
    not just an inference fast path.
    """
    if cfg.decoder is not None:
        return decoder_encode(params, cfg, tokens, impl=impl)[0]
    from repro.kernels.ops import resolve_attn_impl
    impl = resolve_attn_impl(impl if impl is not None else cfg.attn_impl)
    if mask is None:
        mask = tokens != 0
    h = embed_inputs(params, cfg, tokens, freq)               # [M, K, S, d]

    def layer_fn(h, layer):
        return _bus_attention_layer(layer, h, mask, cfg, impl), None

    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn)
    h, _ = jax.lax.scan(layer_fn, h, params["layers"])

    # two-level pooling: tokens -> segment vectors -> news embedding
    v_seg = additive_attention(params["pool_tok"], h, mask)   # [M, K, d]
    seg_valid = mask.any(axis=-1)                             # [M, K]
    e = additive_attention(params["pool_seg"], v_seg, seg_valid)  # [M, d]
    return dense(params["out_proj"], e)


def _mla(p, x, mask, last, rope, cfg: PLMConfig, impl: str):
    """Latent attention of x [M, K, S, d] (RMSNormed) over each segment
    causally and the other segments' last-token proxies."""
    dc, H = cfg.decoder, cfg.n_heads
    M, K, S, _ = x.shape
    dn, dr, dv, r = (dc.qk_nope_head_dim, dc.qk_rope_head_dim,
                     dc.v_head_dim, dc.kv_lora_rank)
    cos, sin = rope
    q = (x @ p["wq"]).reshape(M, K, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], cos, sin)], -1)
    kva = x @ p["wkva"]                                   # [M, K, S, r + dr]
    c = rmsnorm(p["kv_norm"], kva[..., :r], eps=dc.rms_eps)
    k_rope = apply_rope(kva[..., None, r:], cos, sin)     # [M, K, S, 1, dr]
    kv = (c @ p["wkvb"]).reshape(M, K, S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (M, K, S, H, dr))], -1)
    v = kv[..., dn:]

    def with_bus(t):        # [M, K, S, H, D] -> [M, K, S + K, H, D]
        proxy = jnp.take_along_axis(t, last[:, :, None, None, None],
                                    axis=2)[:, :, 0]      # [M, K, H, D]
        return jnp.concatenate(
            [t, jnp.broadcast_to(proxy[:, None], (M, K) + proxy.shape[1:])],
            axis=2)

    others = ~jnp.eye(K, dtype=bool)
    bus_mask = mask.any(-1)[:, None, :] & others[None]    # [M, K, K]
    kv_mask = jnp.concatenate([mask, bus_mask], axis=2)   # [M, K, S + K]
    k, v = with_bus(k), with_bus(v)
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.bus_attention(q, k, v, kv_mask, causal=True)
    else:
        Sk = S + K
        col = jnp.arange(Sk)[None, :]
        tri = (col >= S) | (col <= jnp.arange(S)[:, None])        # [S, Sk]
        full = (kv_mask[:, :, None, :] & tri).reshape(M * K, S, Sk)
        out = sdpa(q.reshape(M * K, S, H, dn + dr),
                   k.reshape(M * K, Sk, H, dn + dr),
                   v.reshape(M * K, Sk, H, dv), causal=False, mask=full)
    return out.reshape(M, K, S, H * dv) @ p["wo"]


def _decoder_parts(cfg: PLMConfig, tokens, impl: str):
    """The decoder stack's pieces for tokens [M, K, S]: the layers
    ``dense(h, layer) -> h`` and ``expert(h, layer) -> (h, counts)``, and
    ``head(params, h)``: the final RMSNorm, the pooling and the projection.
    Attention runs under the ``mla`` scope, the expert layer (router,
    dispatch, grouped matmuls, combine, shared experts) under ``moe``."""
    dc = cfg.decoder
    S = tokens.shape[2]
    mask = tokens != 0
    # each segment's last real token (S - 1 for an empty one, which no
    # query sees: its bus column is masked)
    last = (S - 1 - jnp.argmax(mask[..., ::-1], axis=-1)).astype(jnp.int32)
    rope = rope_cos_sin(jnp.arange(S), dc.qk_rope_head_dim,
                        theta=dc.rope_theta)

    def attend(layer, h):
        with jax.named_scope("mla"):
            x = rmsnorm(layer["attn_norm"], h, eps=dc.rms_eps)
            return h + _mla(layer["mla"], x, mask, last, rope, cfg, impl)

    def dense_layer(h, layer):
        h = attend(layer, h)
        x = rmsnorm(layer["ffn_norm"], h, eps=dc.rms_eps)
        f = layer["ffn"]
        return h + swiglu(f["w1"], f["w3"], f["w2"], x)

    def expert_layer(h, layer):
        h = attend(layer, h)
        x = rmsnorm(layer["ffn_norm"], h, eps=dc.rms_eps)
        with jax.named_scope("moe"):
            y, counts = routed_moe(layer["moe"], x.reshape(-1, x.shape[-1]),
                                   mask.reshape(-1), dc.moe, impl=impl)
        return h + y.reshape(h.shape), counts

    def head(p, h):
        h = rmsnorm(p["final_norm"], h, eps=dc.rms_eps)
        v_seg = additive_attention(p["pool_tok"], h, mask)    # [M, K, d]
        e = additive_attention(p["pool_seg"], v_seg, mask.any(-1))
        return dense(p["out_proj"], e)

    return dense_layer, expert_layer, head


_HEAD = ("final_norm", "pool_tok", "pool_seg", "out_proj")


def decoder_encode(params, cfg: PLMConfig, tokens, impl: str | None = None):
    """The decoder stack (module docstring) on tokens [M, K, S] ->
    ([M, news_dim], counts): the expert layers' ``routed`` rows, grouped
    matmul row ``slots`` and ``expert_rows`` [n_held], summed over the
    expert layers."""
    from repro.kernels.ops import resolve_attn_impl
    impl = resolve_attn_impl(impl if impl is not None else cfg.attn_impl)
    dense_layer, expert_layer, head = _decoder_parts(cfg, tokens, impl)

    def dense_step(h, layer):
        return dense_layer(h, layer), None

    if cfg.remat:
        dense_step, expert_layer = (jax.checkpoint(dense_step),
                                    jax.checkpoint(expert_layer))
    h = params["tok_emb"]["table"][tokens]                # [M, K, S, d]
    h, _ = jax.lax.scan(dense_step, h, params["dense_layers"])
    h, counts = jax.lax.scan(expert_layer, h, params["layers"])
    return head(params, h), jax.tree.map(lambda c: c.sum(0), counts)


def decoder_encode_set(params, cfg: PLMConfig, tokens, n_valid, rows: int):
    """The decoder stack on an encode set tokens [E, K, S] of which the
    first ``n_valid`` rows need encoding, in chunks of ``rows`` rows:
    ([E, news_dim], counts summed over the chunks run).

    A chunk that holds none of the first ``n_valid`` rows is not run (its
    embeddings are zeros), and inside a chunk that runs, the rows past
    them are blanked, so no expert work goes to embeddings that are thrown
    away.  The backward pass is written out (``_encode_set_bwd``): each
    chunk is run forward again, then back through its layers one at a
    time, and every layer's weight gradient is added into one float32
    accumulator in place.  Autodiff of the chunk loop instead keeps a
    whole second gradient per chunk beside the accumulator (2.1 GB for
    Moonlight-16B-A3B's share), which a 16 GB chip cannot hold with the
    Adam state and the embedding cache."""
    return _encode_set(params, tokens, n_valid, cfg, rows)


def _chunked(tokens, n_valid, rows: int):
    E = tokens.shape[0]
    live = (jnp.arange(E) < n_valid)[:, None, None]
    t = jnp.where(live, tokens, 0)
    return jnp.arange(E // rows), t.reshape(E // rows, rows, *t.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _encode_set(params, tokens, n_valid, cfg, rows):
    return _encode_set_fwd(params, tokens, n_valid, cfg, rows)[0]


def _encode_set_fwd(params, tokens, n_valid, cfg, rows):
    from repro.kernels.ops import resolve_attn_impl
    impl = resolve_attn_impl(cfg.attn_impl)
    idx, chunks = _chunked(tokens, n_valid, rows)

    def encode(t):
        return decoder_encode(params, cfg, t, impl)

    out = jax.eval_shape(encode, chunks[0])

    def skip(t):
        return jax.tree.map(lambda o: jnp.zeros(o.shape, o.dtype), out)

    def body(carry, xs):
        c, t = xs
        return carry, jax.lax.cond(c * rows < n_valid, encode, skip, t)

    _, (emb, counts) = jax.lax.scan(body, None, (idx, chunks))
    out = (emb.reshape(tokens.shape[0], -1),
           jax.tree.map(lambda x: x.sum(0), counts))
    return out, (params, tokens, n_valid)


def _encode_set_bwd(cfg, rows, res, ct):
    from repro.kernels.ops import resolve_attn_impl
    impl = resolve_attn_impl(cfg.attn_impl)
    params, tokens, n_valid = res
    idx, chunks = _chunked(tokens, n_valid, rows)
    d_emb = ct[0].reshape(idx.shape[0], rows, -1)

    def grads(acc, t, d_e):
        dense_layer, expert_layer, head = _decoder_parts(cfg, t, impl)
        h0 = params["tok_emb"]["table"][t]
        h1, h_dense = jax.lax.scan(lambda h, l: (dense_layer(h, l), h), h0,
                                   params["dense_layers"])
        h2, h_expert = jax.lax.scan(lambda h, l: (expert_layer(h, l)[0], h),
                                    h1, params["layers"])
        _, head_vjp = jax.vjp(head, {k: params[k] for k in _HEAD}, h2)
        g_head, d_h = head_vjp(d_e)
        acc = dict(acc, **jax.tree.map(jnp.add, {k: acc[k] for k in _HEAD},
                                       g_head))

        def back(layer_fn, key):
            def body(carry, xs):
                d_h, acc_l = carry
                i, h_in = xs
                layer = jax.tree.map(lambda x: x[i], params[key])
                _, vjp = jax.vjp(layer_fn, h_in, layer)
                d_in, g = vjp(d_h)
                acc_l = jax.tree.map(lambda a, gl: a.at[i].add(gl), acc_l, g)
                return (d_in, acc_l), None
            n = jax.tree.leaves(params[key])[0].shape[0]
            return lambda d_h, h_in: jax.lax.scan(
                body, (d_h, acc[key]), (jnp.arange(n), h_in), reverse=True)[0]

        d_h, acc["layers"] = back(lambda h, l: expert_layer(h, l)[0],
                                  "layers")(d_h, h_expert)
        d_h, acc["dense_layers"] = back(dense_layer, "dense_layers")(
            d_h, h_dense)
        table = acc["tok_emb"]["table"].at[t].add(d_h)
        return dict(acc, tok_emb={"table": table})

    def body(acc, xs):
        c, t, d_e = xs
        return jax.lax.cond(c * rows < n_valid, grads,
                            lambda acc, t, d_e: acc, acc, t, d_e), None

    with jax.named_scope("plm_encode"):
        acc, _ = jax.lax.scan(body, jax.tree.map(jnp.zeros_like, params),
                              (idx, chunks, d_emb))
    return acc, None, None


_encode_set.defvjp(_encode_set_fwd, _encode_set_bwd)


def plm_flops(cfg: PLMConfig, n_news: int) -> float:
    """Analytic encode FLOPs (fwd) for the roofline/napkin math."""
    K, S, d, f, L = (cfg.n_segments, cfg.seg_len, cfg.d_model, cfg.d_ff,
                     cfg.n_layers)
    Sk = S + (K if (cfg.use_bus and K > 1) else 0)
    per_layer = (
        4 * K * S * d * d * 2            # qkv+o projections (q on S; k,v on Sk~S)
        + 2 * K * S * Sk * d * 2         # logits + weighted sum
        + 2 * K * S * d * f * 2          # ffn
    )
    return n_news * L * per_layer
