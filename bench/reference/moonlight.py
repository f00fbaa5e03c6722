"""Plain reference of SpeedyFeed (arXiv 2102.09268) with Moonlight-16B-A3B
(huggingface.co/moonshotai/Moonlight-16B-A3B, config.json, a DeepSeek-V3
decoder) as the news encoder, independent of the program under test.

The encoder, layer by layer on h [M, K, S, d] (K segments of S tokens):

  h <- h + MLA(RMSNorm(h));   h <- h + FFN(RMSNorm(h))

- MLA: q = W_q x, per head [q_nope 128, q_rope 64];  [c_kv 512, k_rope 64]
  = W_kva x, k_rope shared by every head;  [k_nope 128, v 128] =
  W_kvb RMSNorm(c_kv) per head.  RoPE (rotate-half, theta 50,000) turns
  q_rope and k_rope by each token's position within its segment.  Scores
  q.k / sqrt(192) over the keys a query sees; the heads' outputs go
  through W_o.
- What a query sees: the tokens of its own segment up to itself (causal),
  and the bus -- each OTHER non-empty segment's proxy, the key and value
  of that segment's last real token (BusLM's bus under a causal mask,
  where a segment's first token sees nothing).
- FFN: the leading ``n_dense`` layers SwiGLU of width ``d_ff``; then the
  expert layer: s = sigmoid(W_r x) over all ``n_experts``; the ``top_k``
  experts of largest s + bias; gates s over them, renormalised, times
  ``routed_scale``.  Of the routed experts only the ``n_held`` held here
  (ids ``held_start ...``) are computed, every one densely on every token
  and weighted by its gate (0 where not chosen), as the chip's share of an
  expert-parallel deployment computes its part; padding tokens are not
  routed.  The shared experts are one SwiGLU of width n_shared * d_expert.
- Then RMSNorm, the paper's two-level additive pooling and the projection
  to ``news_dim``.

Departures from the published decoder, shared with the program: the bus
proxies; RoPE positions restart in each segment; token embeddings only
(no positions, segments or OBoW frequencies); the pooling head.

The encode set is encoded in blocks of rows, each recomputed in the
backward pass, so that a step at the cell's size fits on one chip.  The
rest of Algorithm 1 -- cache plan, causal user model, autoregressive
loss, clipping and Adam -- is the BusLM reference's
(``bench/reference/speedyfeed.py``), which imports nothing of the
program either.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import speedyfeed as sf

F32, BF16, Numerics = sf.F32, sf.BF16, sf.Numerics
NEVER, NEG = sf.NEVER, sf.NEG
seed_key = sf.seed_key


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The encoder's sizes as a configuration file states them."""
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    n_dense: int
    d_ff: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_eps: float
    n_experts: int
    top_k: int
    n_held: int
    d_expert: int
    n_shared: int
    routed_scale: float
    news_dim: int
    held_start: int = 0        # global id of the first expert held here
    block_rows: int = 32       # rows of the encode set per block


def sizes(config: dict) -> Sizes:
    """The Sizes of a configuration file (bench/configs/moonlight_*.json)."""
    c, p = config, config["plm"]
    return Sizes(
        vocab=p["vocab"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        n_dense=c["first_k_dense_replace"], d_ff=c["intermediate_size"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        n_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
        n_held=c["n_routed_experts"], d_expert=c["moe_intermediate_size"],
        n_shared=c["n_shared_experts"],
        routed_scale=float(c["routed_scaling_factor"]),
        news_dim=p["news_dim"])


# ---------------------------------------------------------------- weights

def _normal(key, shape, std=0.02):
    return std * jax.random.normal(key, shape, jnp.float32)


def _layer(key, z: Sizes, moe: bool):
    d, H = z.d_model, z.n_heads
    r, dr = z.kv_lora_rank, z.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    out = {"attn_norm": {"scale": jnp.ones((d,), jnp.float32)},
           "mla": {"wq": _normal(ks[0], (d, H * (z.qk_nope_head_dim + dr))),
                   "wkva": _normal(ks[1], (d, r + dr)),
                   "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
                   "wkvb": _normal(ks[2], (r, H * (z.qk_nope_head_dim
                                                   + z.v_head_dim))),
                   "wo": _normal(ks[3], (H * z.v_head_dim, d))},
           "ffn_norm": {"scale": jnp.ones((d,), jnp.float32)}}
    if not moe:
        k1, k2, k3 = jax.random.split(ks[4], 3)
        out["ffn"] = {"w1": _normal(k1, (d, z.d_ff)),
                      "w3": _normal(k2, (d, z.d_ff)),
                      "w2": _normal(k3, (z.d_ff, d))}
        return out
    g, f, fs = z.n_held, z.d_expert, z.n_shared * z.d_expert
    kk = jax.random.split(ks[5], 8)
    out["moe"] = {"router": _normal(kk[0], (d, z.n_experts)),
                  "router_bias": _normal(kk[1], (z.n_experts,)),
                  "w1": _normal(kk[2], (g, d, f)),
                  "w3": _normal(kk[3], (g, d, f)),
                  "w2": _normal(kk[4], (g, f, d)),
                  "shared": {"w1": _normal(kk[5], (d, fs)),
                             "w3": _normal(kk[6], (d, fs)),
                             "w2": _normal(kk[7], (fs, d))}}
    return out


@functools.partial(jax.jit, static_argnames=("z",))
def _init(key, z: Sizes):
    d, nd = z.d_model, z.news_dim
    ks = jax.random.split(key, 9)
    plm = {
        "tok_emb": {"table": _normal(ks[0], (z.vocab, d))},
        "dense_layers": jax.vmap(lambda k: _layer(k, z, False))(
            jax.random.split(ks[1], z.n_dense)),
        "layers": jax.vmap(lambda k: _layer(k, z, True))(
            jax.random.split(ks[2], z.n_layers - z.n_dense)),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "pool_tok": sf._addattn(ks[3], d),
        "pool_seg": sf._addattn(ks[4], d),
        "out_proj": sf._dense(ks[5], d, nd, xavier=True),
    }
    user = {"proj": sf._dense(ks[6], nd, nd, xavier=True),
            "query": _normal(ks[7], (nd,))}
    return {"plm": plm, "user": user}


@functools.partial(jax.jit, static_argnames=("z",))
def _routers(key, z: Sizes):
    def one(k):
        k0, k1 = jax.random.split(k)
        return {"router": _normal(k0, (z.d_model, z.n_experts)),
                "router_bias": _normal(k1, (z.n_experts,))}
    return jax.vmap(one)(jax.random.split(key, z.n_layers - z.n_dense))


def init_params(seed: int, z: Sizes, *, router_seed: int | None = None):
    """The whole parameter tree, float32, on the default device, from the
    seed in jitted calls (the program's layout).  With ``router_seed``
    the expert layers' router weights and correction biases are drawn
    from it in place of the seed: a cell draws them from its fixed
    ``shape_seed``, so that every run routes about as many rows to the
    experts held here, whatever its seed."""
    p = _init(seed_key(seed, 1), z)
    if router_seed is not None:
        p["plm"]["layers"]["moe"].update(
            _routers(seed_key(router_seed, 3), z))
    return p


# ---------------------------------------------------------------- encoder

def _mm(x, w, nx: Numerics):
    return jnp.einsum("...i,io->...o", x, w, precision=nx.prec)


def _rms(p, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, theta):
    """Rotate-half RoPE of x [..., S, heads, dr] at positions 0..S-1."""
    S, dr = x.shape[-3], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv        # [S, dr/2]
    c = jnp.cos(ang)[:, None, :].astype(x.dtype)
    s = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _mla(p, x, mask, z: Sizes, nx):
    M, K, S, _ = x.shape
    H, dn, dr, dv, r = (z.n_heads, z.qk_nope_head_dim, z.qk_rope_head_dim,
                        z.v_head_dim, z.kv_lora_rank)
    q = _mm(x, p["wq"], nx).reshape(M, K, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], z.rope_theta)], -1)
    kva = _mm(x, p["wkva"], nx)
    c = _rms(p["kv_norm"], kva[..., :r], z.rms_eps)
    k_rope = _rope(kva[..., None, r:], z.rope_theta)           # [M,K,S,1,dr]
    kv = _mm(c, p["wkvb"], nx).reshape(M, K, S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (M, K, S, H, dr))], -1)
    v = kv[..., dn:]
    # the bus: each segment's last real token
    n_real = mask.sum(-1)                                         # [M, K]
    last = jnp.maximum(n_real - 1, 0)
    kb = jnp.take_along_axis(k, last[:, :, None, None, None], 2)[:, :, 0]
    vb = jnp.take_along_axis(v, last[:, :, None, None, None], 2)[:, :, 0]
    s_own = jnp.einsum("mkshd,mkthd->mkhst", q, k, precision=nx.attn_prec)
    s_bus = jnp.einsum("mkshd,mjhd->mkhsj", q, kb, precision=nx.attn_prec)
    s = jnp.concatenate([s_own, s_bus], -1) / math.sqrt(dn + dr)
    pos = jnp.arange(S)
    own = (pos[None, :] <= pos[:, None])[None, None] & mask[:, :, None, :]
    bus = (n_real > 0)[:, None, :] & ~jnp.eye(K, dtype=bool)[None]
    seen = jnp.concatenate(
        [own, jnp.broadcast_to(bus[:, :, None, :], (M, K, S, K))], -1)
    s = jnp.where(seen[:, :, None], s, NEG)                   # [M,K,H,S,S+K]
    pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    o = (jnp.einsum("mkhst,mkthd->mkshd", pr[..., :S], v,
                    precision=nx.attn_prec)
         + jnp.einsum("mkhsj,mjhd->mkshd", pr[..., S:], vb,
                      precision=nx.attn_prec))
    return _mm(o.reshape(M, K, S, H * dv), p["wo"], nx)


def _swiglu(p, x, nx):
    return _mm(jax.nn.silu(_mm(x, p["w1"], nx)) * _mm(x, p["w3"], nx),
               p["w2"], nx)


def _moe(p, x, valid, z: Sizes, nx):
    """The expert layer on x [T, d] for real tokens ``valid`` [T]: the held
    experts' part and the shared experts, and the number of (token,
    expert) rows routed to the held experts."""
    s = jax.nn.sigmoid(_mm(x, p["router"], nx).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), z.top_k)
    gate = jnp.take_along_axis(s, idx, -1)
    gate = gate / gate.sum(-1, keepdims=True) * z.routed_scale      # [T, k]
    pick = jax.nn.one_hot(idx, z.n_experts, dtype=jnp.float32)      # [T,k,E]
    weight = jnp.einsum("tk,tke->te", gate, pick) * valid[:, None]  # [T, E]
    ours = slice(z.held_start, z.held_start + z.n_held)
    held = weight[:, ours]                                          # [T, g]
    routed = (pick.sum(1)[:, ours] * valid[:, None]).sum()
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, p["w1"],
                               precision=nx.prec)) \
        * jnp.einsum("td,edf->etf", x, p["w3"], precision=nx.prec)
    y = jnp.einsum("etf,efd->etd", h, p["w2"], precision=nx.prec)
    out = jnp.einsum("etd,te->td", y, held.astype(y.dtype),
                     precision=nx.prec)
    return out + _swiglu(p["shared"], x, nx), routed


def _encode_block(p, tokens, z: Sizes, nx):
    """Embeddings [M, news_dim] of tokens [M, K, S], and the rows routed to
    the held experts over the expert layers."""
    mask = tokens != 0
    h = p["tok_emb"]["table"][tokens]

    def attend(layer, h):
        return h + _mla(layer["mla"], _rms(layer["attn_norm"], h, z.rms_eps),
                        mask, z, nx)

    @jax.checkpoint
    def dense(h, layer):
        h = attend(layer, h)
        return h + _swiglu(layer["ffn"], _rms(layer["ffn_norm"], h,
                                              z.rms_eps), nx), None

    @jax.checkpoint
    def expert(h, layer):
        h = attend(layer, h)
        x = _rms(layer["ffn_norm"], h, z.rms_eps)
        y, routed = _moe(layer["moe"], x.reshape(-1, x.shape[-1]),
                         mask.reshape(-1), z, nx)
        return h + y.reshape(h.shape), routed

    h, _ = jax.lax.scan(dense, h, p["dense_layers"])
    h, routed = jax.lax.scan(expert, h, p["layers"])
    h = _rms(p["final_norm"], h, z.rms_eps)
    seg = sf._additive_pool(p["pool_tok"], h, mask, nx)
    e = sf._additive_pool(p["pool_seg"], seg, mask.any(-1), nx)
    return e, routed.sum()


def encode(plm_params, tokens, *, z: Sizes, nx: Numerics = F32):
    """tokens [M, K, S] int -> (news embeddings [M, news_dim], rows routed
    to the held experts).  In blocks of ``z.block_rows`` rows, each
    recomputed in the backward pass."""
    p = sf._cast(plm_params, nx)
    M = tokens.shape[0]
    B = z.block_rows if M % z.block_rows == 0 else M
    es, routed = jax.lax.map(
        jax.checkpoint(lambda t: _encode_block(p, t, z, nx)),
        tokens.reshape(M // B, B, *tokens.shape[1:]))
    e = es.reshape(M, -1)
    if nx.out is not None:
        out = jnp.dtype(nx.out)
        return sf._apply_dense(jax.tree.map(lambda x: x.astype(out),
                                            plm_params["out_proj"]),
                               e.astype(out), nx), routed.sum()
    return sf._apply_dense(p["out_proj"], e, nx), routed.sum()


# ------------------------------------------------------------ Algorithm 1

def step_loss(params, cache_emb, written, batch, tokens, step, rng,
              spec: sf.StepSpec, z: Sizes, nx: Numerics):
    """Loss of one step, what the cache refresh needs, and the rows routed
    to the held experts.  Rows of the encode set that need no encoding
    are blanked before the encoder, as the program blanks them."""
    rng_cache, rng_neg = jax.random.split(rng)
    ids = batch["news_ids"]
    enc_pos, enc_valid = sf.cache_plan(written, ids, step, rng_cache, spec)
    tok = jnp.where(enc_valid[:, None, None], tokens[enc_pos], 0)
    new, routed = encode(params["plm"], tok, z=z, nx=nx)
    cached = jax.lax.stop_gradient(cache_emb[ids]).astype(new.dtype)
    emb = cached.at[enc_pos].set(
        jnp.where(enc_valid[:, None], new, cached[enc_pos]))
    emb = emb * (ids != 0)[:, None]
    theta = emb[batch["hist_inv"]]
    mask = batch["hist_mask"]
    mu = sf.causal_user(sf._cast(params["user"], nx), theta, mask, nx)
    M = ids.shape[0]
    neg = jax.random.randint(rng_neg, mask[:, 1:].shape + (spec.n_neg,), 1, M)
    users = None
    if spec.half_batch:
        users = jnp.arange(mask.shape[0]) % 2 == 0
    loss = sf.ar_loss(mu, theta, mask, emb, ids, neg, batch["hist_inv"], nx,
                      users)
    return loss.astype(jnp.float32), (new, enc_pos, enc_valid, routed)


@functools.partial(jax.jit, static_argnames=("spec", "z", "nx"),
                   donate_argnames=("params", "m", "v", "cache_emb",
                                    "written"))
def train_step(params, m, v, count, cache_emb, written, batch, tokens, step,
               rng, *, spec: sf.StepSpec, z: Sizes, nx: Numerics):
    """One step of Algorithm 1 with its Adam update; returns the new
    state, the loss, the clipped gradient as the optimizer got it and the
    rows routed to the held experts."""
    (loss, (new, enc_pos, enc_valid, routed)), grads = jax.value_and_grad(
        step_loss, has_aux=True)(params, cache_emb, written, batch, tokens,
                                 step, rng, spec, z, nx)
    params, m, v, g = sf.adam(params, grads, m, v, count, spec, nx)
    tgt = jnp.where(enc_valid, batch["news_ids"][enc_pos], cache_emb.shape[0])
    cache_emb = cache_emb.at[tgt].set(
        jax.lax.stop_gradient(new).astype(cache_emb.dtype), mode="drop")
    written = written.at[tgt].set(step.astype(jnp.int32), mode="drop")
    return params, m, v, count + 1, cache_emb, written, loss, g, routed
