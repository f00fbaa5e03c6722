"""Plain reference of SpeedyFeed (arXiv 2102.09268), independent of the
program under test: BusLM (§4.1.3, App. A.1.1), the cache-accelerated
encoding of Algorithm 1/2 (§4.1.2), the causal attentive user model and
autoregressive loss (§4.1.4, Eq. 5), global-norm clipping and Adam with
the paper's two learning rates (§A.3), and two-stage retrieval scores.

Written in straightforward ``jax.numpy``; every matmul takes an explicit
precision, and the default ``Numerics`` is float32 at HIGHEST (on a TPU a
float32 matmul otherwise takes one bf16 pass).  ``Numerics(dtype=bf16)``
is the lower-precision control: the same arithmetic with parameters,
activations and the optimizer's parameter update in bfloat16.
``STATED`` keeps float32 storage with the backend's DEFAULT matmul
precision, the arithmetic the configurations state for inference.

The benchmark makes the weights (``init_params``, one jitted call from
the seed) and hands the same tree to the program and to this reference;
the tree has the program's layout so the program can take it as is.

Departures from the paper, shared with the program: GELU is the tanh
approximation; empty (fully masked) segments attend uniformly over their
masked keys, as a max-subtracted softmax over all-equal scores does.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

NEVER = -(2 ** 30)          # "never written" marker of a cache row
NEG = -1e30                 # masked score


@dataclasses.dataclass(frozen=True)
class Numerics:
    dtype: str = "float32"
    precision: str = "highest"
    out: str | None = None      # dtype of the encoder's last projection
    attn: str | None = None     # precision of the attention products

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def prec(self):
        return _PREC[self.precision]

    @property
    def attn_prec(self):
        return _PREC[self.attn or self.precision]


_PREC = {"highest": jax.lax.Precision.HIGHEST,
         "default": jax.lax.Precision.DEFAULT}


F32 = Numerics()
# float32 storage with the backend's DEFAULT matmul precision: the
# arithmetic the configurations state for the program's inference paths
STATED = Numerics(precision="default")
# the same, with the attention products (scores and their weighted sum)
# at full float32 precision
KERNEL = Numerics(precision="default", attn="highest")
BF16 = Numerics(dtype="bfloat16", precision="default")
# bfloat16 inside, float32 out: the lower precision hidden behind a last
# float32 projection, so the outputs' bits look like float32 ones
BF16_F32_OUT = Numerics(dtype="bfloat16", precision="default", out="float32")


# ---------------------------------------------------------------- weights

def _xavier(key, shape):
    lim = math.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def _normal(key, shape, std=0.02):
    return std * jax.random.normal(key, shape, jnp.float32)


def _dense(key, i, o, *, xavier=False):
    w = _xavier(key, (i, o)) if xavier else _normal(key, (i, o))
    return {"w": w, "b": jnp.zeros((o,), jnp.float32)}


def _ln(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def _layer(key, d, f):
    ks = jax.random.split(key, 6)
    return {"attn": {"q": _dense(ks[0], d, d), "k": _dense(ks[1], d, d),
                     "v": _dense(ks[2], d, d), "o": _dense(ks[3], d, d)},
            "ln1": _ln(d), "ffn_up": _dense(ks[4], d, f),
            "ffn_down": _dense(ks[5], f, d), "ln2": _ln(d)}


def _addattn(key, d):
    k1, k2 = jax.random.split(key)
    return {"proj": _dense(k1, d, d, xavier=True), "query": _normal(k2, (d,))}


@functools.partial(jax.jit, static_argnames=("sizes",))
def _init(key, sizes):
    s = dict(sizes)
    d, f, nd = s["d_model"], s["d_ff"], s["news_dim"]
    ks = jax.random.split(key, 10 + s["n_layers"])
    plm = {
        "tok_emb": {"table": _normal(ks[0], (s["vocab"], d))},
        "pos_emb": {"table": _normal(ks[1], (s["max_len"], d))},
        "seg_emb": {"table": _normal(ks[2], (max(s["n_segments"], 2), d))},
        "emb_ln": _ln(d),
        "pool_tok": _addattn(ks[3], d),
        "pool_seg": _addattn(ks[4], d),
        "out_proj": _dense(ks[5], d, nd, xavier=True),
        "freq_emb": {"table": _normal(ks[6], (s["max_freq"], d))},
        "layers": jax.vmap(lambda k: _layer(k, d, f))(
            jnp.stack(ks[10:])),
    }
    user = {"proj": _dense(ks[7], nd, nd, xavier=True),
            "query": _normal(ks[8], (nd,))}
    return {"plm": plm, "user": user}


def init_params(seed: int, plm: dict):
    """The whole parameter tree, float32, on the default device, from the
    seed in one jitted call.  ``plm``: the configuration's encoder sizes."""
    keys = ("vocab", "n_layers", "d_model", "n_heads", "d_ff", "n_segments",
            "news_dim", "max_len", "max_freq")
    sizes = tuple((k, int(plm[k])) for k in keys)
    return _init(seed_key(seed, 1), sizes)


def seed_key(seed: int, stream: int):
    """A PRNG key for one of the benchmark's random streams; seeds may be
    wider than 32 bits."""
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.random.fold_in(jax.random.fold_in(key, seed // (2 ** 31)),
                              stream)


# ---------------------------------------------------------------- encoder

def _mm(x, w, nx: Numerics):
    return jnp.einsum("...i,io->...o", x, w, precision=nx.prec)


def _apply_dense(p, x, nx):
    return _mm(x, p["w"], nx) + p["b"]


def _layernorm(p, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _additive_pool(p, h, mask, nx):
    a = jnp.einsum("...nd,d->...n", jnp.tanh(_apply_dense(p["proj"], h, nx)),
                   p["query"], precision=nx.prec)
    a = jnp.where(mask, a, NEG)
    w = jax.nn.softmax(a, axis=-1)
    return jnp.einsum("...n,...nd->...d", w, h, precision=nx.prec)


def _bus_layer(layer, h, mask, n_heads, nx):
    M, K, S, d = h.shape
    D = d // n_heads
    bus = jnp.broadcast_to(h[:, None, :, 0, :], (M, K, K, d))
    kv = jnp.concatenate([h, bus], axis=2)                     # [M,K,S+K,d]
    seg_valid = mask.any(-1)
    kv_mask = jnp.concatenate(
        [mask, jnp.broadcast_to(seg_valid[:, None, :], (M, K, K))], axis=2)
    a = layer["attn"]
    q = _apply_dense(a["q"], h, nx).reshape(M, K, S, n_heads, D)
    k = _apply_dense(a["k"], kv, nx).reshape(M, K, S + K, n_heads, D)
    v = _apply_dense(a["v"], kv, nx).reshape(M, K, S + K, n_heads, D)
    s = jnp.einsum("mkshd,mkthd->mkhst", q, k,
                   precision=nx.attn_prec) * D ** -0.5
    s = jnp.where(kv_mask[:, :, None, None, :], s, NEG)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("mkhst,mkthd->mkshd", pr, v, precision=nx.attn_prec)
    h = _layernorm(layer["ln1"], h + _apply_dense(a["o"], o.reshape(h.shape),
                                                  nx))
    ff = _apply_dense(layer["ffn_down"],
                      _gelu(_apply_dense(layer["ffn_up"], h, nx)), nx)
    return _layernorm(layer["ln2"], h + ff)


def encode(plm_params, tokens, freq, *, n_heads: int, max_freq: int,
           nx: Numerics = F32):
    """BusLM: tokens, freq [M, K, S] int -> news embeddings [M, news_dim].
    Layer by layer (a scan with each layer recomputed in the backward
    pass), so the encode set of a step fits."""
    p = _cast(plm_params, nx)
    M, K, S = tokens.shape
    mask = tokens != 0
    h = (p["tok_emb"]["table"][tokens]
         + p["pos_emb"]["table"][jnp.arange(S)][None, None]
         + p["seg_emb"]["table"][jnp.arange(K)][None, :, None]
         + p["freq_emb"]["table"][jnp.clip(freq, 0, max_freq - 1)])
    h = _layernorm(p["emb_ln"], h)

    @jax.checkpoint
    def body(h, layer):
        return _bus_layer(layer, h, mask, n_heads, nx), None

    h, _ = jax.lax.scan(body, h, p["layers"])
    seg = _additive_pool(p["pool_tok"], h, mask, nx)               # [M,K,d]
    e = _additive_pool(p["pool_seg"], seg, mask.any(-1), nx)       # [M,d]
    if nx.out is not None:
        out = jnp.dtype(nx.out)
        return _apply_dense(jax.tree.map(lambda x: x.astype(out),
                                         plm_params["out_proj"]),
                            e.astype(out), nx)
    return _apply_dense(p["out_proj"], e, nx)


def _cast(tree, nx: Numerics):
    return jax.tree.map(lambda x: x.astype(nx.jdtype), tree)


# ----------------------------------------------------- user model and loss

def user_scores(p, theta, nx):
    return jnp.einsum("...ld,d->...l", jnp.tanh(_apply_dense(p["proj"], theta,
                                                             nx)),
                      p["query"], precision=nx.prec)


def user_embedding(user_params, theta, mask, nx: Numerics = F32):
    """Attentive user embedding (serving): softmax-weighted mean of the
    history embeddings theta [B, L, d] under mask [B, L] -> [B, d]."""
    p = _cast(user_params, nx)
    theta = theta.astype(nx.jdtype)
    a = jnp.where(mask, user_scores(p, theta, nx), NEG)
    w = jax.nn.softmax(a, axis=-1)
    return jnp.einsum("bl,bld->bd", w, theta, precision=nx.prec)


def causal_user(p, theta, mask, nx):
    """mu_t from {theta_l, l <= t} by prefix sums -> [B, L, d]."""
    a = user_scores(p, theta, nx)
    a = a - jax.lax.stop_gradient(a.max(-1, keepdims=True))
    w = jnp.exp(a) * mask
    num = jnp.cumsum(w[..., None] * theta, axis=1)
    den = jnp.cumsum(w, axis=1)[..., None]
    return num / jnp.maximum(den, 1e-9)


def ar_loss(mu, theta, mask, emb_m, news_ids, neg_idx, hist_inv, nx,
            users=None):
    """Eq. 5 with in-batch negatives; ``users`` (a [B] bool) restricts
    the mean to those users."""
    mu_t, pos = mu[:, :-1], theta[:, 1:]
    valid = mask[:, 1:] & mask[:, :-1]
    if users is not None:
        valid = valid & users[:, None]
    pos_s = jnp.einsum("bld,bld->bl", mu_t, pos, precision=nx.prec)
    neg_s = jnp.einsum("bld,blnd->bln", mu_t, emb_m[neg_idx],
                       precision=nx.prec)
    neg_ids = news_ids[neg_idx]
    pos_ids = news_ids[hist_inv[:, 1:]]
    bad = (neg_ids == 0) | (neg_ids == pos_ids[..., None])
    neg_s = jnp.where(bad, NEG, neg_s)
    logits = jnp.concatenate([pos_s[..., None], neg_s], axis=-1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)[..., 0]
    return -(logp * valid).sum() / jnp.maximum(valid.sum(), 1)


# ------------------------------------------------------------ Algorithm 1

@dataclasses.dataclass(frozen=True)
class StepSpec:
    """What one Algorithm-1 step needs beyond the arrays."""
    n_heads: int
    max_freq: int
    gamma: int
    beta: float
    encode_budget: int
    n_neg: int
    lr: float
    plm_lr_scale: float
    grad_clip: float
    b1: float
    b2: float
    eps: float
    half_batch: bool = False      # fault: mean over half of the users


def cache_plan(written, news_ids, step, rng, spec: StepSpec):
    """Algorithm 2 with a static encode budget: which merged-set slots
    reuse a fresh cache entry and which are encoded (must-encode first,
    in merged-set order)."""
    p_t = 1.0 - jnp.exp(-spec.beta * step.astype(jnp.float32))
    use = (jax.random.uniform(rng) < p_t) & (spec.gamma > 0)
    age = step - written[news_ids]
    fresh = (age >= 0) & (age <= spec.gamma)
    pad = news_ids == 0
    reuse = use & fresh & ~pad
    must = ~reuse & ~pad
    order = jnp.argsort(-must.astype(jnp.int32), stable=True)
    enc_pos = order[:spec.encode_budget]
    return enc_pos, must[enc_pos]


def step_loss(params, cache_emb, written, batch, tokens, freq, step, rng,
              spec: StepSpec, nx: Numerics):
    """Loss of one step and what the cache refresh needs."""
    rng_cache, rng_neg = jax.random.split(rng)
    ids = batch["news_ids"]
    enc_pos, enc_valid = cache_plan(written, ids, step, rng_cache, spec)
    new = encode(params["plm"], tokens[enc_pos], freq[enc_pos],
                 n_heads=spec.n_heads, max_freq=spec.max_freq, nx=nx)
    cached = jax.lax.stop_gradient(cache_emb[ids]).astype(new.dtype)
    emb = cached.at[enc_pos].set(
        jnp.where(enc_valid[:, None], new, cached[enc_pos]))
    emb = emb * (ids != 0)[:, None]
    theta = emb[batch["hist_inv"]]
    mask = batch["hist_mask"]
    up = _cast(params["user"], nx)
    mu = causal_user(up, theta, mask, nx)
    M = ids.shape[0]
    neg = jax.random.randint(rng_neg, mask[:, 1:].shape + (spec.n_neg,), 1, M)
    users = None
    if spec.half_batch:
        users = jnp.arange(mask.shape[0]) % 2 == 0
    loss = ar_loss(mu, theta, mask, emb, ids, neg, batch["hist_inv"], nx,
                   users)
    return loss.astype(jnp.float32), (new, enc_pos, enc_valid)


def _global_clip(grads, max_norm):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads)


def adam(params, grads, m, v, count, spec: StepSpec, nx: Numerics):
    """Clip, then Adam with the PLM group at ``plm_lr_scale`` of the lr.
    Parameters are stored in ``nx``'s dtype."""
    g = _global_clip(grads, spec.grad_clip)
    count = count + 1
    bc1 = 1 - spec.b1 ** count
    bc2 = 1 - spec.b2 ** count

    def one(path, p, g, m, v):
        scale = spec.plm_lr_scale if path[0].key == "plm" else 1.0
        m = spec.b1 * m + (1 - spec.b1) * g
        v = spec.b2 * v + (1 - spec.b2) * g * g
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + spec.eps)
        new = p.astype(jnp.float32) - spec.lr * scale * upd
        return new.astype(nx.jdtype), m, v

    out = jax.tree_util.tree_map_with_path(one, params, g, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,      # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), g


@functools.partial(jax.jit, static_argnames=("spec", "nx"),
                   donate_argnames=("params", "m", "v", "cache_emb",
                                    "written"))
def train_step(params, m, v, count, cache_emb, written, batch, tokens, freq,
               step, rng, *, spec: StepSpec, nx: Numerics):
    """One step of Algorithm 1 with its Adam update; returns the new
    state, the loss and the clipped gradient as the optimizer got it."""
    (loss, (new, enc_pos, enc_valid)), grads = jax.value_and_grad(
        step_loss, has_aux=True)(params, cache_emb, written, batch, tokens,
                                 freq, step, rng, spec, nx)
    params, m, v, g = adam(params, grads, m, v, count, spec, nx)
    tgt = jnp.where(enc_valid, batch["news_ids"][enc_pos], cache_emb.shape[0])
    cache_emb = cache_emb.at[tgt].set(
        jax.lax.stop_gradient(new).astype(cache_emb.dtype), mode="drop")
    written = written.at[tgt].set(step.astype(jnp.int32), mode="drop")
    return params, m, v, count + 1, cache_emb, written, loss, g


# ------------------------------------------------------------- retrieval

@functools.partial(jax.jit, static_argnames=("nx",))
def exact_scores(user_params, corpus, hist, hist_mask, *, nx: Numerics = F32):
    """User embeddings from histories over ``corpus`` [N, d] (row 0 the
    pad article) and their exact inner products with every article."""
    u = user_embedding(user_params, corpus[hist], hist_mask, nx)
    s = jnp.einsum("bd,nd->bn", u, corpus.astype(nx.jdtype),
                   precision=nx.prec)
    return u.astype(jnp.float32), s.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("nx",))
def corpus_scores(u, corpus, *, nx: Numerics = F32):
    """Exact inner products of user embeddings u [B, d] with every
    article of ``corpus`` [N, d]."""
    return jnp.einsum("bd,nd->bn", u.astype(nx.jdtype),
                      corpus.astype(nx.jdtype),
                      precision=nx.prec).astype(jnp.float32)
