"""SpeedyFeed light-weighted encoding pipeline (Algorithm 1), end to end.

One training step over a centralized batch:
  1. merged news set M (deduplicated by the loader or by gather_dedup)
  2. cache plan: which news reuse cached embeddings, which get encoded
     (fixed budget E; p_t scheduler; gamma expiry)                  §4.1.2
  3. BusLM-encode the encode set, chunk by chunk, skipping the
     chunks that hold no news to encode                             §4.1.3
  4. assemble + dispatch embeddings to history positions            §4.1.1
  5. autoregressive user modeling + Eq.5 loss over all L positions  §4.1.4
  6. refresh cache

Also provides the *conventional workflow* step (per-instance encoding, no
dedup/cache/AR) used as the speedup baseline in benchmarks (paper Table 4).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .plm import PLMConfig, init_plm
from .buslm import buslm_encode, decoder_encode_set
from .cache import (CacheConfig, CacheState, assemble_embeddings, cache_plan,
                    cache_refresh, init_cache)
from .centralized import dispatch
from .loss import ar_loss, click_loss, sample_negatives
from .user_model import (UserModelConfig, attentive_user, init_user_model,
                         user_embeddings)


@dataclasses.dataclass(frozen=True)
class SpeedyFeedConfig:
    plm: PLMConfig
    user: UserModelConfig
    cache: CacheConfig
    batch_users: int = 32     # B
    hist_len: int = 100       # L
    merged_cap: int = 512     # M
    n_neg: int = 4            # negatives per prediction

    @property
    def attn_impl(self) -> str:
        """Attention implementation for the training hot path — auto
        (pallas on TPU, xla elsewhere) | xla | pallas.  The PLM config is
        the single source of truth (the encoder owns the kernels); this
        is a read-through so per-step code and configs can't diverge."""
        return self.plm.attn_impl


def make_config(*, vocab=30522, n_layers=12, d_model=768, n_heads=12,
                d_ff=3072, n_segments=3, seg_len=32, news_dim=64,
                n_news=1_202_576, gamma=20, beta=2e-3, encode_budget=256,
                batch_users=32, hist_len=100, merged_cap=512, n_neg=4,
                user_kind="attentive", use_bus=True, use_freq=True,
                remat=False, attn_impl="auto",
                decoder=None) -> SpeedyFeedConfig:
    plm = PLMConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                    n_heads=n_heads, d_ff=d_ff, n_segments=n_segments,
                    seg_len=seg_len, news_dim=news_dim, use_bus=use_bus,
                    use_freq_embedding=use_freq, remat=remat,
                    attn_impl=attn_impl, decoder=decoder)
    user = UserModelConfig(news_dim=news_dim, kind=user_kind, causal=True)
    cache = CacheConfig(n_news=n_news, news_dim=news_dim, gamma=gamma,
                        beta=beta, encode_budget=encode_budget)
    return SpeedyFeedConfig(plm=plm, user=user, cache=cache,
                            batch_users=batch_users, hist_len=hist_len,
                            merged_cap=merged_cap, n_neg=n_neg)


def init_speedyfeed(key, cfg: SpeedyFeedConfig, param_dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {"plm": init_plm(k1, cfg.plm, param_dtype),
            "user": init_user_model(k2, cfg.user, param_dtype)}


def encode_chunk_rows(n_rows: int) -> int:
    """Rows per chunk of an ``n_rows``-row encode set: a sixteenth of it
    when ``n_rows`` is a multiple of 128 (so a chunk is a whole number of
    the bus kernel's 8-row blocks), else the whole set in one chunk."""
    return n_rows // 16 if n_rows % 128 == 0 else n_rows


def encode_set(plm_params, cfg: SpeedyFeedConfig, tokens, freq, n_valid):
    """BusLM-encode the encode set's ``n_valid`` rows that need encoding.

    ``cache_plan`` orders the must-encode rows first, so they are the first
    ``n_valid`` of the E rows.  The set runs in chunks of
    ``encode_chunk_rows(E)`` rows, and a chunk that holds none of them is
    not run: its rows are zeros, which neither ``assemble_embeddings`` nor
    ``cache_refresh`` reads (both select by ``enc_valid``).  A scan over
    the chunks with a cond in its body keeps one copy of the encoder,
    forward and backward, in the program, and only the taken branch runs.
    A single chunk always runs.  An expert encoder's set runs in the same
    chunks with a backward pass of its own
    (``core.buslm.decoder_encode_set``).  Returns ([E, news_dim]
    embeddings, the number of rows the encoder ran, and the expert
    encoder's counts -- none for BusLM).
    """
    from repro.distributed import sharding as shx

    def encode(p, t, f):
        # the merged set is replicated (global dedup/argsort); the encode
        # set is data-sharded so the PLM runs data-parallel -- without
        # this constraint XLA keeps the whole encoder replicated
        return buslm_encode(p, cfg.plm, shx.constrain(t, "encode_batch"),
                            shx.constrain(f, "encode_batch"),
                            impl=cfg.attn_impl)

    E = tokens.shape[0]
    G = encode_chunk_rows(E)
    if cfg.plm.decoder is not None:
        emb, counts = decoder_encode_set(plm_params, cfg.plm, tokens,
                                         n_valid, G)
        return emb, jnp.minimum(-(-n_valid // G), E // G) * G, counts
    if G == E:
        return encode(plm_params, tokens, freq), jnp.int32(E), {}
    C = E // G
    chunks = (jnp.arange(C), tokens.reshape(C, G, *tokens.shape[1:]),
              freq.reshape(C, G, *freq.shape[1:]))
    out = jax.eval_shape(encode, plm_params, chunks[1][0], chunks[2][0])

    def skip(p, t, f):
        return jnp.zeros(out.shape, out.dtype)

    def body(carry, xs):
        c, t, f = xs
        return carry, jax.lax.cond(c * G < n_valid, encode, skip,
                                   plm_params, t, f)

    _, emb = jax.lax.scan(body, None, chunks)
    return emb.reshape(E, -1), jnp.minimum(-(-n_valid // G), C) * G, {}


class StepOut(NamedTuple):
    loss: jax.Array
    cache: CacheState
    metrics: dict


def speedyfeed_forward(params, cfg: SpeedyFeedConfig, batch, cache: CacheState,
                       step, rng) -> StepOut:
    """Algorithm 1. batch keys (loader-produced, already centralized):
      news_tokens [M, K, S]  news_freq [M, K, S]  news_ids [M]
      hist_inv [B, L]        hist_mask [B, L]
    """
    rng_cache, rng_neg = jax.random.split(rng)
    news_ids = batch["news_ids"]

    # Each stage runs under a named scope (plm_encode, cache, user_model,
    # loss; the optimizer adds update), so every device op of the step
    # carries its stage in its name stack -- through autodiff
    # (``transpose(jvp(plm_encode))``) and remat (``checkpoint``) too.

    # (2) cache plan + (3) encode the budget set
    with jax.named_scope("cache"):
        plan = cache_plan(cache, news_ids, step, rng_cache, cfg.cache)
        enc_tokens = jnp.take(batch["news_tokens"], plan.enc_pos, axis=0)
        enc_freq = jnp.take(batch["news_freq"], plan.enc_pos, axis=0)
        encoded = plan.enc_valid.sum()
    with jax.named_scope("plm_encode"):
        new_emb, rows_run, enc_counts = encode_set(
            params["plm"], cfg, enc_tokens, enc_freq, encoded)

    # (4) assemble merged-set embeddings and dispatch
    with jax.named_scope("cache"):
        emb_m = assemble_embeddings(cache, plan, news_ids, new_emb)
        theta = dispatch(emb_m, batch["hist_inv"])           # [B, L, d]
    mask = batch["hist_mask"]

    # (5) autoregressive user modeling + Eq. 5
    with jax.named_scope("user_model"):
        mu = user_embeddings(params["user"], cfg.user, theta, mask)
    with jax.named_scope("loss"):
        neg_idx = sample_negatives(rng_neg, cfg.merged_cap,
                                   mask[:, 1:].shape, cfg.n_neg)
        loss, m = ar_loss(mu, theta, mask, emb_m, news_ids, neg_idx,
                          hist_inv=batch["hist_inv"])

    # (6) refresh, and the step's counts of the cache and the encode set
    with jax.named_scope("cache"):
        new_cache = cache_refresh(cache, plan, news_ids, new_emb, step)
        _, K, S = enc_tokens.shape
        m.update({
            "p_t": plan.p_t,
            "encoded": encoded,
            "reused": plan.reuse.sum(),
            "cache_overflow": plan.overflow,
            # cache hit/miss/expired device scalars (cache.py age math); the
            # Trainer's MetricsBuffer drain folds them into obs counters —
            # the paper's headline cache-reuse signal, no extra syncs
            "cache_hits": plan.reuse.sum(),
            "cache_misses": plan.missing.sum(),
            "cache_expired": plan.expired.sum(),
            # the encoder's padding: rows of the E-row encode set that
            # needed encoding, and their real tokens of the K x S slots
            # each such row runs (the Trainer sums them per drain)
            "encode_rows": jnp.int32(plan.enc_pos.shape[0]),
            # rows of the chunks the encoder ran (encode_set)
            "encode_rows_run": rows_run,
            "enc_tokens": ((enc_tokens != 0)
                           & plan.enc_valid[:, None, None]).sum(),
            "enc_token_slots": encoded * (K * S),
            "merged_news": (news_ids != 0).sum(),
        })
        if enc_counts:
            # an expert encoder's rows routed to the experts held here,
            # the grouped matmuls' row slots run, and the least and most
            # rows any held expert took, over the step's layers and chunks
            rows = enc_counts["expert_rows"]
            m.update({"moe_routed": enc_counts["routed"],
                      "moe_slots": enc_counts["slots"],
                      "moe_rows_least": rows.min(),
                      "moe_rows_most": rows.max()})
    return StepOut(loss, new_cache, m)


# ---------------------------------------------------------------------------
# conventional workflow (the paper's baseline; Figure 1 left)
# ---------------------------------------------------------------------------

def conventional_forward(params, cfg: SpeedyFeedConfig, batch):
    """Typical workflow: every training instance encodes its *own* history
    and candidates with the PLM; one click prediction per instance.

    batch: hist_tokens [B, L, K, S], hist_freq, hist_mask [B, L],
           cand_tokens [B, C, K, S], cand_freq, label [B], cand_mask [B, C].
    """
    B, L, K, S = batch["hist_tokens"].shape
    C = batch["cand_tokens"].shape[1]
    flat_tokens = jnp.concatenate([
        batch["hist_tokens"].reshape(B * L, K, S),
        batch["cand_tokens"].reshape(B * C, K, S)], axis=0)
    flat_freq = jnp.concatenate([
        batch["hist_freq"].reshape(B * L, K, S),
        batch["cand_freq"].reshape(B * C, K, S)], axis=0)
    emb = buslm_encode(params["plm"], cfg.plm, flat_tokens, flat_freq,
                       impl=cfg.attn_impl)
    theta = emb[:B * L].reshape(B, L, -1)
    cand = emb[B * L:].reshape(B, C, -1)
    user = attentive_user(params["user"], theta, batch["hist_mask"])
    return click_loss(user, cand, batch["label"], batch["cand_mask"])


def speedyfeed_state(cfg: SpeedyFeedConfig, key=None, param_dtype=jnp.float32):
    """(params, cache) convenience initializer."""
    key = key if key is not None else jax.random.PRNGKey(0)
    return init_speedyfeed(key, cfg, param_dtype), init_cache(cfg.cache)
