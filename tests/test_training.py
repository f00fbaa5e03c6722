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

def expert_cfg():
    """``tiny_cfg`` with a small expert (DeepSeek-V3) news encoder."""
    from repro.nn.moe import RoutedMoEConfig
    moe = RoutedMoEConfig(d_model=32, d_expert=16, n_experts=8, top_k=2,
                          n_held=4, n_shared=1)
    dec = core.DecoderConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                             qk_rope_head_dim=8, v_head_dim=8, n_dense=1,
                             moe=moe)
    return tiny_cfg(n_layers=2, decoder=dec, use_freq=False)


@pytest.mark.parametrize("encoder,executables", [
    ("buslm", 1),     # the encode set picks its lengths: one program
    ("expert", 2),    # one program per bucket
])
def test_k_buckets_compile_exactly_k_executables(encoder, executables):
    cfg = tiny_cfg() if encoder == "buslm" else expert_cfg()
    trainer = training.get_trainer("speedyfeed", cfg=cfg)
    state = trainer.init_state(seed=0)
    buckets = (8, 16)

    def batch(b, seed):
        return jax.device_put(trainer.prepare_batch(synth_batch(cfg, b,
                                                                seed=seed)))

    # N steps over K buckets -> the expected number of compilations
    for i in range(6):
        b = buckets[i % 2]
        state, metrics = trainer.step(state, batch(b, i), bucket=b)
    assert trainer.executable_count() == executables
    assert set(trainer.compile_counts) == set(buckets)
    assert sum(c >= 1 for c in trainer.compile_counts.values()) \
        == executables
    # warm buckets never recompile
    with training.CompileCounter() as cc:
        for i in range(4):
            b = buckets[i % 2]
            state, metrics = trainer.step(state, batch(b, 10 + i), bucket=b)
    assert cc.count == 0
    assert trainer.executable_count() == executables
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


# ---------------------------------------------------------------------------
# the step on the profiler's clock: one train_step span per dispatch, the
# drained steps' counts as train_window events, and a program scope on
# every op of the step
# ---------------------------------------------------------------------------

SCOPES = ("plm_encode", "cache", "user_model", "loss", "update")


def test_traced_fit_writes_step_spans_and_window_counts(tmp_path):
    from jax.profiler import ProfileData
    from repro import obs
    obs.reset()
    # lookups from the first steps on; a 128-row encode set runs in chunks
    cfg = tiny_cfg(beta=5.0, encode_budget=128, merged_cap=256)
    corpus, log, store, lcfg = make_loader(cfg, n_news=60, n_users=30,
                                           seed=2)
    trainer = training.get_trainer("speedyfeed", cfg=cfg)

    def make_batcher(epoch):
        return data.DynamicBatcher(log, store, lcfg, n_threads=2,
                                   seed=epoch).start()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # as the benchmark traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = trainer.fit(make_batcher, steps=7, log_every=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.glob("**/*.xplane.pb"))
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    steps = [st for n, st in events if n == "train_step"]
    assert len(steps) == res.steps_done == 7
    for st in steps:
        assert int(st["bucket"]) in lcfg.buckets
        assert 1 <= st["users"] <= st["user_slots"] == cfg.batch_users
    # drains at steps 3 and 6 and the final one inside fit
    window = [st for n, st in events if n == "train_window"]
    assert [st["steps"] for st in window] == [3, 3, 1]
    hist = trainer.metrics_buffer.history
    for key in ("encoded", "encode_rows_run", "cache_hits", "enc_tokens",
                "enc_token_slots_run", "encode_chunks_short", "merged_news"):
        assert sum(st[key] for st in window) == sum(hist[key])
    assert sum(hist["cache_hits"]) > 0
    E, K = cfg.cache.encode_budget, cfg.plm.n_segments
    assert sum(st["encode_rows"] for st in window) == 7 * E
    # at most 48 news a step need encoding: most of the 128 rows never run
    for st in window:
        assert st["encoded"] <= st["encode_rows_run"] < st["steps"] * E
    assert 0 < sum(st["enc_tokens"] for st in window) \
        <= sum(st["enc_token_slots"] for st in window)
    # every bucket runs padded to seg_len; each chunk of the encode set
    # runs at the length its rows need, at most seg_len
    S = cfg.plm.seg_len
    assert sum(st["enc_token_slots"] for st in window) \
        == sum(hist["encoded"]) * K * S
    assert sum(st["enc_tokens"] for st in window) \
        <= sum(st["enc_token_slots_run"] for st in window) \
        <= sum(st["encode_rows_run"] for st in window) * K * S
    assert obs.counter("train_window_steps_total").value == 7
    assert obs.histogram("span_ms", name="train_step",
                         bucket=str(steps[0]["bucket"])).count >= 1
    obs.reset()


def _scope_names(op_name: str) -> set:
    """Every name in a name stack, wrappers such as
    ``transpose(jvp(plm_encode))`` unwrapped."""
    import re
    names = set()
    for comp in op_name.split("/"):
        while (m := re.fullmatch(r"([^()]*)\((.*)\)", comp)):
            names.add(m.group(1))
            comp = m.group(2)
        names.add(comp)
    return names


def _step_text_and_scopes(cfg):
    """The compiled step's text, and the scopes its dot, convolution and
    custom-call ops carry (each exactly one)."""
    import re
    trainer = training.get_trainer("speedyfeed", cfg=cfg)
    state = trainer.init_state(seed=0)
    batch = jax.device_put(synth_batch(cfg, 16))
    text = trainer.compiled_text(state, batch)
    ops = [ln for ln in text.splitlines()
           if re.search(r" (dot|convolution|custom-call)\(", ln)]
    assert ops
    seen = set()
    for ln in ops:
        m = re.search(r'op_name="([^"]*)"', ln)
        assert m, ln
        names = _scope_names(m.group(1)) & set(SCOPES)
        assert len(names) == 1, m.group(1)
        seen |= names
    return text, seen


@pytest.mark.parametrize("remat,attn_impl", [(False, "xla"), (True, "xla"),
                                             (True, "pallas")])
def test_step_ops_carry_program_scopes(remat, attn_impl):
    """Forward, recompute and backward -- the bus kernel's custom VJP
    included -- keep the stage's scope in every op's name stack.  The
    encode set encodes each chunk again for its backward pass whether or
    not the config asks for remat, so the recompute is there either way."""
    import re
    text, seen = _step_text_and_scopes(tiny_cfg(remat=remat,
                                                 attn_impl=attn_impl))
    assert {"plm_encode", "user_model", "loss"} <= seen
    assert re.search(r'op_name="[^"]*transpose\(jvp\(plm_encode\)\)'
                     r'/plm_encode/[^"]*/jvp\(', text)


def test_chunked_encode_step_keeps_scopes():
    """A 128-row encode set runs in chunks, each behind a conditional; the
    encoder's ops inside them still carry ``plm_encode``."""
    text, seen = _step_text_and_scopes(tiny_cfg(
        remat=True, attn_impl="pallas", encode_budget=128, merged_cap=256))
    assert {"plm_encode", "user_model", "loss"} <= seen
    assert " conditional(" in text
