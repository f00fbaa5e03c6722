"""Training-runtime contracts: epoch sentinel vs timeout, config-derived
buckets, per-bucket compile hygiene + buffer donation, async device
prefetch, and TrainState checkpoint compatibility (incl. the pre-Trainer
on-disk layout)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import checkpoint as ckpt
from repro import core, data, optim, training
from repro.launch.train import make_loader, small_speedyfeed_config


def tiny_cfg(**over):
    base = dict(vocab=500, n_layers=1, d_model=32, n_heads=2, d_ff=64,
                n_segments=3, seg_len=16, news_dim=16, n_news=301,
                gamma=20, beta=2e-2, encode_budget=16, batch_users=4,
                hist_len=12, merged_cap=48, n_neg=3)
    base.update(over)
    return core.make_config(**base)


def synth_batch(cfg, seg_len, seed=0):
    """A centralized batch at a given seg-length bucket."""
    return data.synth_centralized_batch(
        m_cap=cfg.merged_cap, n_segments=cfg.plm.n_segments, seg_len=seg_len,
        b_cap=cfg.batch_users, hist_len=cfg.hist_len, vocab=cfg.plm.vocab,
        seed=seed)


# ---------------------------------------------------------------------------
# DynamicBatcher: end-of-epoch sentinel vs timeout (regression: a slow
# worker used to be indistinguishable from an exhausted epoch)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def loader():
    cfg = small_speedyfeed_config()
    corpus, log, store, lcfg = make_loader(cfg, n_news=150, n_users=30,
                                           seed=3)
    return cfg, log, store, lcfg


def test_timeout_returns_none_not_epoch_end(loader):
    cfg, log, store, lcfg = loader
    b = data.DynamicBatcher(log, store, lcfg, n_threads=2)
    # workers not started: nothing can arrive, but the epoch is NOT over
    out = b.get(timeout=0.05)
    assert out is None
    assert out is not data.EPOCH_END


def test_exhausted_epoch_returns_sentinel(loader):
    cfg, log, store, lcfg = loader
    b = data.DynamicBatcher(log, store, lcfg, n_threads=2).start()
    seen, out = 0, None
    try:
        for _ in range(200):
            out = b.get(timeout=10.0)
            if out is data.EPOCH_END:
                break
            assert out is not None, "timeout before epoch end"
            seen += 1
    finally:
        b.stop()
    assert out is data.EPOCH_END
    assert repr(out) == "EPOCH_END"
    assert seen >= 1
    # idempotent: a drained loader keeps reporting end-of-epoch
    assert b.get(timeout=0.05) is data.EPOCH_END


def test_worker_error_surfaces_instead_of_hanging(loader):
    """A dead worker must raise from get(), not leave the epoch open."""
    cfg, log, store, lcfg = loader
    bad_log = data.ClickLog([np.array([10 ** 6, 10 ** 6 + 1])] * 4)
    b = data.DynamicBatcher(bad_log, store, lcfg, n_threads=2).start()
    try:
        with pytest.raises(IndexError):
            for _ in range(10):
                out = b.get(timeout=5.0)
                if out is data.EPOCH_END:
                    pytest.fail("epoch ended despite worker crash")
    finally:
        b.stop()


def test_batches_carry_bucket_key(loader):
    cfg, log, store, lcfg = loader
    b = data.DynamicBatcher(log, store, lcfg, n_threads=1).start()
    try:
        batch = b.get(timeout=10.0)
    finally:
        b.stop()
    assert batch is not None and batch is not data.EPOCH_END
    assert batch["_bucket"] in lcfg.buckets
    assert batch["_bucket"] == batch["_stats"]["seg_len"]


# ---------------------------------------------------------------------------
# bucket sets derive from config (regression: make_loader hardcoded
# {seg_len//2, seg_len})
# ---------------------------------------------------------------------------

def test_default_buckets_derivation():
    assert data.default_buckets(32) == (8, 16, 24, 32)
    assert data.default_buckets(16) == (8, 16)
    assert data.default_buckets(8) == (8,)
    assert data.default_buckets(24, base=(6, 12, 18, 24)) == (6, 12, 18, 24)
    # seg_len beyond the default base must still be the top bucket, or
    # every news would be silently truncated to max(base)
    assert data.default_buckets(64) == (8, 16, 24, 32, 64)


def test_make_loader_uses_config_buckets():
    cfg32 = small_speedyfeed_config(seg_len=32)
    _, _, _, lcfg = make_loader(cfg32, n_news=40, n_users=10)
    assert lcfg.buckets == (8, 16, 24, 32)     # 4-bucket configs exercisable
    cfg16 = small_speedyfeed_config(seg_len=16)
    _, _, _, lcfg16 = make_loader(cfg16, n_news=40, n_users=10)
    assert lcfg16.buckets == (8, 16)
    _, _, _, lover = make_loader(cfg16, n_news=40, n_users=10,
                                 buckets=(4, 16))
    assert lover.buckets == (4, 16)


# ---------------------------------------------------------------------------
# recompile hygiene + donation
# ---------------------------------------------------------------------------

def test_k_buckets_compile_exactly_k_executables():
    cfg = tiny_cfg()
    trainer = training.get_trainer("speedyfeed", cfg=cfg)
    state = trainer.init_state(seed=0)
    buckets = (8, 16)
    # N steps over K buckets -> exactly K compilations
    for i in range(6):
        b = buckets[i % 2]
        batch = jax.device_put(synth_batch(cfg, b, seed=i))
        state, metrics = trainer.step(state, batch, bucket=b)
    assert trainer.executable_count() == len(buckets)
    assert set(trainer.compile_counts) == set(buckets)
    assert all(c >= 1 for c in trainer.compile_counts.values())
    # warm buckets never recompile
    with training.CompileCounter() as cc:
        for i in range(4):
            b = buckets[i % 2]
            batch = jax.device_put(synth_batch(cfg, b, seed=10 + i))
            state, metrics = trainer.step(state, batch, bucket=b)
    assert cc.count == 0
    assert trainer.executable_count() == len(buckets)
    assert trainer.warm_compiles == {}
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


def test_warm_bucket_recompile_is_recorded():
    """A second shape under a bucket label that already compiled is a
    warm recompile: the Trainer records it per bucket, and an enclosing
    CompileCounter still sees it."""
    cfg = tiny_cfg()
    trainer = training.get_trainer("speedyfeed", cfg=cfg)
    state = trainer.init_state(seed=0)
    state, _ = trainer.step(state, jax.device_put(synth_batch(cfg, 8)),
                            bucket=8)
    assert trainer.warm_compiles == {}
    with training.CompileCounter() as cc:
        state, _ = trainer.step(
            state, jax.device_put(synth_batch(cfg, 16, seed=1)), bucket=8)
    assert trainer.warm_compiles == {8: 1}
    assert cc.count >= 1
    assert trainer.bucket_steps == {8: 2}


def test_fit_with_pallas_attention_under_remat():
    """The trainable-kernel path end to end: a full Trainer.fit run with
    attn_impl='pallas' (interpret mode on CPU) and cfg.remat=True must
    update params through the custom-VJP backward kernels with finite
    loss and per-bucket compile hygiene."""
    cfg = tiny_cfg(remat=True, attn_impl="pallas")
    assert cfg.attn_impl == "pallas" and cfg.plm.attn_impl == "pallas"
    trainer = training.get_trainer("speedyfeed", cfg=cfg)

    # one donated step first: params must move and stay finite
    state = trainer.init_state(seed=0)
    batch = jax.device_put(synth_batch(cfg, 16))
    new, metrics = trainer.step(state, batch, bucket=16)
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
    baseline = trainer.init_state(seed=0)      # state was donated: re-init
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        new.params, baseline.params)
    assert max(jax.tree.leaves(moved)) > 0.0
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in jax.tree.leaves(new.params))

    # and a short fit over the real loader (bucketed stream, warm reuse)
    corpus, log, store, lcfg = make_loader(cfg, n_news=120, n_users=30,
                                           seed=2)

    def make_batcher(epoch):
        return data.DynamicBatcher(log, store, lcfg, n_threads=2,
                                   seed=epoch).start()

    res = trainer.fit(make_batcher, steps=3, state=new, log_every=0)
    assert res.steps_done == 3
    assert np.isfinite(res.losses).all()
    assert all(c == 1 for c in res.compile_counts.values())


def test_step_donates_state_buffers():
    cfg = tiny_cfg()
    trainer = training.get_trainer("speedyfeed", cfg=cfg)
    old = trainer.init_state(seed=1)
    batch = jax.device_put(synth_batch(cfg, 8))
    new, _ = trainer.step(old, batch, bucket=8)
    # donated inputs must not be referenced again: jax marks them deleted
    old_leaves = (jax.tree.leaves(old.params) + jax.tree.leaves(old.opt)
                  + [old.cache.emb, old.cache.written_step])
    assert all(leaf.is_deleted() for leaf in old_leaves)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(new.params))


# ---------------------------------------------------------------------------
# async device prefetch
# ---------------------------------------------------------------------------

def test_prefetcher_streams_device_batches(loader):
    cfg, log, store, lcfg = loader

    def make_batcher(epoch):
        return data.DynamicBatcher(log, store, lcfg, n_threads=2,
                                   seed=epoch).start()

    pf = training.DevicePrefetcher(make_batcher, depth=2,
                                   max_epochs=1).start()
    got, out = [], None
    try:
        while True:
            out = pf.get(timeout=15.0)
            if out is training.STREAM_END:
                break
            assert out is not None, "timeout is not a clean finish"
            got.append(out)
        # idempotent, and distinct from the timeout signal
        assert pf.get(timeout=0.05) is training.STREAM_END
    finally:
        pf.stop()
    assert len(got) >= 1
    for pb in got:
        assert pb.bucket in lcfg.buckets
        assert "_stats" not in pb.arrays and "_bucket" not in pb.arrays
        assert all(isinstance(v, jax.Array) for v in pb.arrays.values())
        assert pb.arrays["news_tokens"].shape[-1] == pb.bucket
    assert pf.epochs_done == 1


def test_prefetcher_surfaces_producer_errors():
    def bad_factory(epoch):
        raise ValueError("loader exploded")

    pf = training.DevicePrefetcher(bad_factory).start()
    with pytest.raises(ValueError, match="loader exploded"):
        pf.get(timeout=5.0)
    pf.stop()


# ---------------------------------------------------------------------------
# TrainState checkpointing (incl. pre-refactor layout)
# ---------------------------------------------------------------------------

def _init_state(cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    params, cache = core.speedyfeed_state(cfg, key)
    return training.make_state(params, optim.adam_init(params), cache,
                               step=4, rng=key)


def test_trainstate_roundtrip(tmp_path):
    cfg = tiny_cfg()
    state = _init_state(cfg, seed=2)
    training.save_state(str(tmp_path), 4, state)
    like = _init_state(cfg, seed=9)
    step, restored = training.restore_state(str(tmp_path), like)
    assert step == 4 and int(restored.step) == 4
    np.testing.assert_array_equal(np.asarray(restored.rng),
                                  np.asarray(state.rng))
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_pre_refactor_layout(tmp_path):
    """Checkpoints written by the old loop ({params, opt, cache:{emb, age}},
    no step/rng leaves) must load into a TrainState via the alias."""
    cfg = tiny_cfg()
    key = jax.random.PRNGKey(5)
    params, cache = core.speedyfeed_state(cfg, key)
    opt = optim.adam_init(params)
    legacy = {"params": params, "opt": opt,
              "cache": {"emb": cache.emb + 2.0,
                        "age": cache.written_step + 11}}
    ckpt.save(str(tmp_path), 7, legacy)

    like = training.make_state(params, opt, cache, rng=key)
    step, state = training.restore_state(str(tmp_path), like)
    assert step == 7 and int(state.step) == 7
    np.testing.assert_array_equal(
        np.asarray(state.cache.written_step),
        np.asarray(cache.written_step) + 11)            # age -> written_step
    assert np.allclose(np.asarray(state.cache.emb),
                       np.asarray(cache.emb) + 2.0)
    np.testing.assert_array_equal(np.asarray(state.rng), np.asarray(key))


def test_fit_resumes_from_pre_refactor_checkpoint(tmp_path):
    """End-to-end: Trainer.fit picks up a legacy-layout checkpoint and
    continues training through the TrainState path."""
    cfg = tiny_cfg()
    corpus, log, store, lcfg = make_loader(cfg, n_news=120, n_users=30,
                                           seed=1)
    trainer = training.get_trainer("speedyfeed", cfg=cfg)
    init = trainer.init_state(seed=0)
    legacy = {"params": init.params, "opt": init.opt,
              "cache": {"emb": init.cache.emb,
                        "age": init.cache.written_step}}
    ckpt.save(str(tmp_path), 5, legacy)

    def make_batcher(epoch):
        return data.DynamicBatcher(log, store, lcfg, n_threads=2,
                                   seed=epoch).start()

    res = trainer.fit(make_batcher, steps=8, ckpt_dir=str(tmp_path),
                      ckpt_every=100, log_every=0)
    assert res.resumed_from == 5
    assert res.steps_done == 8
    assert len(res.losses) == 3                      # only the new steps
    assert np.isfinite(res.losses).all()


def test_registry_exposes_trainers():
    names = training.registered_trainers()
    assert "speedyfeed" in names
    assert "speedyfeed_conventional" in names
    with pytest.raises(KeyError):
        training.get_trainer("no-such-arch")
