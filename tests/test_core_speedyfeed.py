"""SpeedyFeed core behaviour: cache invariants, centralized dedup,
autoregressive user modeling, Algorithm-1 pipeline semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import core


def tiny_cfg(**over):
    base = dict(vocab=300, n_layers=1, d_model=32, n_heads=4, d_ff=64,
                n_segments=2, seg_len=8, news_dim=16, n_news=128,
                gamma=5, beta=1.0, encode_budget=12, batch_users=4,
                hist_len=8, merged_cap=32, n_neg=3)
    base.update(over)
    return core.make_config(**base)


def make_batch(cfg, key, n_real=None):
    M, K, S = cfg.merged_cap, cfg.plm.n_segments, cfg.plm.seg_len
    B, L = cfg.batch_users, cfg.hist_len
    n_real = n_real or M - 1
    ks = jax.random.split(key, 4)
    ids = jnp.zeros(M, jnp.int32).at[1:n_real + 1].set(
        jnp.arange(1, n_real + 1, dtype=jnp.int32))
    return {
        "news_tokens": jax.random.randint(ks[0], (M, K, S), 1, cfg.plm.vocab),
        "news_freq": jax.random.randint(ks[1], (M, K, S), 0, 8),
        "news_ids": ids,
        "hist_inv": jax.random.randint(ks[2], (B, L), 1, n_real + 1),
        "hist_mask": jnp.ones((B, L), bool),
    }


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2000), st.integers(1, 30), st.integers(4, 30))
def test_cache_plan_invariants(step, gamma, budget):
    ccfg = core.CacheConfig(n_news=64, news_dim=8, gamma=gamma, beta=5e-3,
                            encode_budget=budget)
    state = core.init_cache(ccfg)
    ids = jnp.arange(0, 40, dtype=jnp.int32)   # includes pad id 0
    plan = core.cache_plan(state, ids, jnp.int32(step),
                           jax.random.PRNGKey(step), ccfg)
    # pads never encoded nor reused
    assert not bool(plan.reuse[0])
    enc_ids = ids[plan.enc_pos]
    assert not bool((enc_ids[plan.enc_valid] == 0).any())
    # encode + reuse + overflow covers every real news exactly once
    n_real = int((ids != 0).sum())
    covered = int(plan.enc_valid.sum()) + int(plan.reuse.sum()) \
        + int(plan.overflow)
    assert covered == n_real
    # a cold cache can never be reused
    assert int(plan.reuse.sum()) == 0


def test_cache_reuse_lifecycle():
    """Fresh entries are reused until gamma expires them."""
    ccfg = core.CacheConfig(n_news=32, news_dim=4, gamma=3, beta=100.0,
                            encode_budget=8)
    state = core.init_cache(ccfg)
    ids = jnp.arange(0, 9, dtype=jnp.int32)     # 8 real news
    emb = jnp.ones((8, 4))
    plan0 = core.cache_plan(state, ids, jnp.int32(0), jax.random.PRNGKey(0),
                            ccfg)
    assert int(plan0.enc_valid.sum()) == 8
    state = core.cache_refresh(state, plan0, ids,
                               emb[:ccfg.encode_budget], jnp.int32(0))
    plan1 = core.cache_plan(state, ids, jnp.int32(2), jax.random.PRNGKey(1),
                            ccfg)
    assert int(plan1.reuse.sum()) == 8          # fresh within gamma
    plan2 = core.cache_plan(state, ids, jnp.int32(10), jax.random.PRNGKey(2),
                            ccfg)
    assert int(plan2.reuse.sum()) == 0          # expired after gamma


def test_cached_embeddings_carry_no_gradient():
    cfg = tiny_cfg(beta=100.0)   # p_t ~ 1 immediately
    key = jax.random.PRNGKey(0)
    params, cache = core.speedyfeed_state(cfg, key)
    batch = make_batch(cfg, key, n_real=12)

    def warm(cache):
        out = core.speedyfeed_forward(params, cfg, batch, cache,
                                      jnp.int32(0), key)
        return out.cache

    cache = warm(cache)   # everything cached at step 0

    def loss_fn(p):
        return core.speedyfeed_forward(p, cfg, batch, cache, jnp.int32(1),
                                       jax.random.PRNGKey(1)).loss

    g = jax.grad(loss_fn)(params)
    # with all news reused, PLM grads must be exactly zero
    plm_norm = sum(float(jnp.abs(x).sum())
                   for x in jax.tree.leaves(g["plm"]))
    user_norm = sum(float(jnp.abs(x).sum())
                    for x in jax.tree.leaves(g["user"]))
    assert plm_norm == 0.0
    assert user_norm > 0.0


# ---------------------------------------------------------------------------
# centralized encoding
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 20), min_size=4, max_size=24))
def test_gather_dedup_roundtrip(ids):
    ids = ids[:len(ids) // 2 * 2]
    arr = jnp.asarray(ids, jnp.int32).reshape(2, -1)
    m = core.gather_dedup(arr, m_cap=32)
    restored = m.ids[m.inv_hist]
    assert bool((restored == arr).all())
    # merged set has no duplicate non-pad ids
    real = np.asarray(m.ids)
    real = real[real != 0]
    assert len(real) == len(set(real))


def test_gather_dedup_overflow_counts():
    arr = jnp.arange(1, 21, dtype=jnp.int32).reshape(2, 10)
    m = core.gather_dedup(arr, m_cap=8)
    assert int(m.overflow) > 0
    # overflowed ids map to the pad slot 0
    assert bool((m.ids[m.inv_hist] == 0).any())


# ---------------------------------------------------------------------------
# autoregressive user modeling
# ---------------------------------------------------------------------------

def test_causal_user_matches_per_prefix_recompute():
    """mu_t from the O(L) prefix-sum == non-causal pooling over the prefix —
    the exact equivalence that makes one-shot AR training valid (§4.1.4)."""
    cfg = core.UserModelConfig(news_dim=16, kind="attentive")
    key = jax.random.PRNGKey(0)
    p = core.init_user_model(key, cfg)
    theta = jax.random.normal(key, (3, 7, 16))
    mask = jnp.ones((3, 7), bool)
    mu_fast = core.attentive_user_causal(p, theta, mask)
    for t in range(7):
        mu_slow = core.attentive_user(p, theta[:, :t + 1],
                                      mask[:, :t + 1])
        np.testing.assert_allclose(np.array(mu_fast[:, t]),
                                   np.array(mu_slow), rtol=2e-4, atol=2e-5)


def test_causal_user_respects_mask():
    cfg = core.UserModelConfig(news_dim=8, kind="attentive")
    p = core.init_user_model(jax.random.PRNGKey(1), cfg)
    theta = jax.random.normal(jax.random.PRNGKey(2), (2, 6, 8))
    mask = jnp.array([[True] * 4 + [False] * 2, [True] * 6])
    mu = core.attentive_user_causal(p, theta, mask)
    # masked tail positions must equal the last valid prefix embedding
    np.testing.assert_allclose(np.array(mu[0, 3]), np.array(mu[0, 5]),
                               rtol=1e-5)


def test_ar_loss_counts_only_valid_transitions():
    cfg = tiny_cfg()
    key = jax.random.PRNGKey(0)
    params, cache = core.speedyfeed_state(cfg, key)
    batch = make_batch(cfg, key)
    batch["hist_mask"] = batch["hist_mask"].at[:, 4:].set(False)
    out = core.speedyfeed_forward(params, cfg, batch, cache, jnp.int32(0),
                                  key)
    assert int(out.metrics["n_predictions"]) == cfg.batch_users * 3


# ---------------------------------------------------------------------------
# pipeline / Algorithm 1
# ---------------------------------------------------------------------------

def test_speedyfeed_step_trains():
    from repro.configs.speedyfeed_arch import make_sf_train_step
    from repro import optim
    cfg = tiny_cfg(beta=2e-3)
    key = jax.random.PRNGKey(0)
    params, cache = core.speedyfeed_state(cfg, key)
    opt = optim.adam_init(params)
    step = jax.jit(make_sf_train_step(cfg))
    batch = make_batch(cfg, key)
    losses = []
    for i in range(8):
        # fixed rng: negatives stay the same so the re-fit objective is
        # stationary (per-step resampling drowns 8 steps of lr=1e-4 in noise)
        params, opt, cache, m = step(params, opt, cache, jnp.int32(i),
                                     jax.random.fold_in(key, 0), batch)
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]    # same batch re-fit: loss must drop


@pytest.mark.parametrize("n_real,warm,n_valid", [
    (5, False, 5),        # fewer must-encode rows than a chunk: one runs
    (20, False, 20),      # mid-set: the last chunk run is partly valid
    (200, False, 128),    # overflow: every chunk runs
    (110, True, 10),      # 100 cache hits: the misses sort to the front
], ids=["one_chunk", "partial", "overflow", "hits"])
def test_chunked_encode_matches_all_rows(monkeypatch, n_real, warm, n_valid):
    """Skipping the chunks past the must-encode prefix gives the loss,
    gradients and cache of encoding all E rows; only the order of the
    weight-gradient sums differs."""
    from repro.core import pipeline
    cfg = tiny_cfg(n_news=300, encode_budget=128, merged_cap=256, beta=100.0,
                   remat=True)
    E = cfg.cache.encode_budget
    G = pipeline.encode_chunk_rows(E)
    assert G < E
    key = jax.random.PRNGKey(0)
    params, cache = core.speedyfeed_state(cfg, key)
    step = jnp.int32(0)
    if warm:             # news 1..100 written at step 0, fresh at step 1
        cache = core.speedyfeed_forward(params, cfg,
                                        make_batch(cfg, key, n_real=100),
                                        cache, step, key).cache
        step = jnp.int32(1)
    batch = make_batch(cfg, key, n_real=n_real)

    def run():
        def loss_fn(p):
            out = core.speedyfeed_forward(p, cfg, batch, cache, step, key)
            return out.loss, out
        return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params)

    (loss, out), grads = run()
    monkeypatch.setattr(pipeline, "encode_chunk_rows", lambda n: n)
    (loss_all, out_all), grads_all = run()

    assert int(out.metrics["encoded"]) == n_valid
    assert int(out.metrics["encode_rows_run"]) == -(-n_valid // G) * G
    assert int(out_all.metrics["encode_rows_run"]) == E
    np.testing.assert_allclose(float(loss), float(loss_all), rtol=1e-5)
    top = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads_all))
    for g, g_all in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_all)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_all),
                                   rtol=1e-4, atol=1e-6 * top)
    np.testing.assert_allclose(np.asarray(out.cache.emb),
                               np.asarray(out_all.cache.emb),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out.cache.written_step),
                                  np.asarray(out_all.cache.written_step))


def test_conventional_and_speedy_share_encoder_semantics():
    """Encoding N news via the pipeline's encoder == encoding them via the
    conventional path (the speedup must come from scheduling, not from a
    different model)."""
    cfg = tiny_cfg()
    key = jax.random.PRNGKey(0)
    params, _ = core.speedyfeed_state(cfg, key)
    toks = jax.random.randint(key, (6, 2, 8), 1, 300)
    freq = jnp.ones((6, 2, 8), jnp.int32)
    e1 = core.buslm_encode(params["plm"], cfg.plm, toks, freq)
    e2 = core.buslm_encode(params["plm"], cfg.plm, toks, freq)
    np.testing.assert_allclose(np.array(e1), np.array(e2))


def test_dummy_vector_for_pad_news():
    cfg = tiny_cfg()
    key = jax.random.PRNGKey(0)
    params, cache = core.speedyfeed_state(cfg, key)
    batch = make_batch(cfg, key, n_real=10)
    plan = core.cache_plan(cache, batch["news_ids"], jnp.int32(0), key,
                           cfg.cache)
    enc = core.buslm_encode(params["plm"], cfg.plm,
                            batch["news_tokens"][plan.enc_pos],
                            batch["news_freq"][plan.enc_pos])
    emb = core.assemble_embeddings(cache, plan, batch["news_ids"], enc)
    # pad slot 0 and any slot with id 0 must be exactly zero
    assert float(jnp.abs(emb[0]).max()) == 0.0
    assert float(jnp.abs(emb[11:]).max()) == 0.0
