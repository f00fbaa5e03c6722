"""Mesh scale-out acceptance: sharded TrainState training, sharded-restore
checkpoints, and device-sharded IVF retrieval — on 8 XLA-forced host devices.

Run with:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m pytest -q tests/test_mesh.py

Under a plain tier-1 run (1 visible device) every test here skips: the
mesh path is exercised by the CI multi-device smoke job instead.
"""
import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro import data, obs, serving, training
from repro.launch.mesh import make_mesh_for, parse_mesh_arg
from repro.launch.train import make_loader, small_speedyfeed_config
from repro.training import (CompileCounter, restore_state, save_state,
                            state_shardings)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh_for(8)


@pytest.fixture(scope="module")
def cfg():
    return small_speedyfeed_config()


def _synth(cfg, seed):
    return data.synth_centralized_batch(
        m_cap=cfg.merged_cap, n_segments=cfg.plm.n_segments,
        seg_len=cfg.plm.seg_len, b_cap=cfg.batch_users,
        hist_len=cfg.hist_len, vocab=cfg.plm.vocab, seed=seed)


def _fit(trainer, cfg, steps, *, seed=0, hosts=None, log_every=0):
    # n_threads=1 keeps the batch ORDER deterministic, so the mesh and
    # single-device fits train over the identical stream
    corpus, log, store, lcfg = make_loader(cfg, seed=seed)

    def make_batcher(epoch):
        return data.DynamicBatcher(log, store, lcfg, n_threads=1,
                                   seed=seed + 1_000_003 * epoch).start()

    return trainer.fit(make_batcher, steps=steps, seed=seed,
                       log_every=log_every, hosts=hosts)


# ---------------------------------------------------------------- training

def test_sharded_step_matches_single_device(mesh, cfg):
    """Pure-DP semantics: the sharded executable computes the SAME step as
    the single-device one — per-step losses agree on matched batches."""
    tr1 = training.get_trainer("speedyfeed", cfg=cfg)
    trm = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh)
    s1, sm = tr1.init_state(0), trm.init_state(0)
    for i in range(4):
        b = _synth(cfg, i)
        s1, m1 = tr1.step(s1, jax.device_put(b))
        sm, mm = trm.step(sm, b)
        np.testing.assert_allclose(float(mm["loss"]), float(m1["loss"]),
                                   rtol=0, atol=1e-5)
    # every state leaf lives on the mesh; the cache rows shard over data
    # when they divide (guard_divisible falls back to replicated otherwise)
    emb = sm.cache.emb
    assert isinstance(emb.sharding, NamedSharding)
    assert emb.sharding.mesh.devices.size == 8
    if emb.shape[0] % 8 == 0:
        assert emb.sharding.spec[0] is not None


def test_pallas_bus_kernel_runs_per_shard_under_mesh(mesh):
    """XLA cannot partition a Mosaic call, so under the Trainer's mesh the
    bus kernel runs inside a shard_map over the news axis: the sharded
    step still computes the single-device step."""
    cfg = small_speedyfeed_config(attn_impl="pallas")
    tr1 = training.get_trainer("speedyfeed", cfg=cfg)
    trm = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh)
    s1, sm = tr1.init_state(0), trm.init_state(0)
    for i in range(2):
        b = _synth(cfg, i)
        s1, m1 = tr1.step(s1, jax.device_put(b))
        sm, mm = trm.step(sm, b)
        np.testing.assert_allclose(float(mm["loss"]), float(m1["loss"]),
                                   rtol=0, atol=1e-5)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_chunked_encode_under_mesh_matches_single_device(mesh, attn_impl):
    """A 128-row encode set runs in 8-row chunks, each skipped or run
    across the mesh: the sharded step still computes the single-device
    step, and both run the same rows."""
    cfg = small_speedyfeed_config(encode_budget=128, attn_impl=attn_impl)
    tr1 = training.get_trainer("speedyfeed", cfg=cfg)
    trm = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh)
    s1, sm = tr1.init_state(0), trm.init_state(0)
    for i, n_real in enumerate((20, 60)):
        b = _synth(cfg, i)              # only the first n_real news are real
        b["news_ids"][n_real + 1:] = 0
        b["hist_inv"] %= n_real + 1
        s1, m1 = tr1.step(s1, jax.device_put(b))
        sm, mm = trm.step(sm, b)
        np.testing.assert_allclose(float(mm["loss"]), float(m1["loss"]),
                                   rtol=0, atol=1e-5)
        assert int(m1["encoded"]) == n_real
        assert int(mm["encode_rows_run"]) == int(m1["encode_rows_run"]) \
            == -(-n_real // 8) * 8


def test_sharded_step_donates_state(mesh, cfg):
    trm = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh)
    s0, _ = trm.step(trm.init_state(0), _synth(cfg, 0))   # committed state
    s1, _ = trm.step(s0, _synth(cfg, 1))
    assert jax.tree.leaves(s0.params)[0].is_deleted()     # donated
    assert not jax.tree.leaves(s1.params)[0].is_deleted()


def test_sharded_fit_loss_parity_and_compile_hygiene(mesh, cfg):
    steps = 6
    r1 = _fit(training.get_trainer("speedyfeed", cfg=cfg), cfg, steps)
    trm = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh)
    rm = _fit(trm, cfg, steps)
    assert rm.steps_done == r1.steps_done == steps
    np.testing.assert_allclose(rm.losses, r1.losses, rtol=0, atol=1e-4)
    # second fit on the warm trainer: every bucket executable is reused
    rm2 = _fit(trm, cfg, steps)
    assert rm2.compile_counts == {}
    np.testing.assert_allclose(rm2.losses, rm.losses, rtol=0, atol=1e-4)


def test_multi_host_monitor_gauges(mesh, cfg):
    """Simulated multi-host fit exports the straggler control plane:
    ``straggler_hosts`` and per-host ``microbatch_alloc`` gauges."""
    obs.reset()
    trm = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh)
    _fit(trm, cfg, 6, hosts=4, log_every=2)
    assert obs.gauge("straggler_hosts").value is not None
    allocs = [obs.gauge("microbatch_alloc", host=str(h)).value
              for h in range(4)]
    assert all(a >= 1 for a in allocs)       # rebalance never drops a host
    assert sum(allocs) == 4                  # global batch invariant


# ------------------------------------------------------------- checkpoints

def test_ckpt_single_device_to_mesh_and_back(tmp_path, mesh, cfg):
    ckpt_dir = str(tmp_path)
    tr1 = training.get_trainer("speedyfeed", cfg=cfg)
    state, _ = tr1.step(tr1.init_state(3), jax.device_put(_synth(cfg, 0)))
    save_state(ckpt_dir, 1, state)

    # single-device checkpoint -> 8-way mesh, leaves land placed
    like = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh) \
        .init_state(4)
    step, sharded = restore_state(ckpt_dir, like,
                                  shardings=state_shardings(like, mesh))
    assert step == 1
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for leaf in jax.tree.leaves(sharded):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.mesh.devices.size == 8

    # sharded run's checkpoint -> back onto one device (format is
    # mesh-agnostic host arrays; no conversion step)
    save_state(ckpt_dir, 2, sharded)
    step2, back = restore_state(ckpt_dir, tr1.init_state(5))
    assert step2 == 2 and int(back.step) == 2   # directory step is authority
    for a, b in zip(jax.tree.leaves(state._replace(step=None)),
                    jax.tree.leaves(back._replace(step=None))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------- serving

@pytest.mark.parametrize("kind", ["ivf-flat", "ivf-pq"])
def test_sharded_index_topk_parity(mesh, kind):
    """Global probing over replicated centroids makes the sharded candidate
    set identical to the unsharded one — so the merged top-k must match the
    unsharded oracle id-for-id (nlist=37: the pad-row tail path)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3000, 32)).astype(np.float32)
    ids = np.arange(1, 3001)
    q = rng.normal(size=(16, 32)).astype(np.float32)
    ivf = serving.IVFConfig(nlist=37, nprobe=8)
    pq = serving.PQConfig(n_subvec=8, n_codes=32)
    plain = serving.IndexBuilder(kind, 32, ivf=ivf, pq=pq, seed=0)
    shard = serving.IndexBuilder(kind, 32, ivf=ivf, pq=pq, seed=0,
                                 devices=jax.devices()[:8])
    snap, ssnap = plain.build(ids, x), shard.build(ids, x)
    assert isinstance(ssnap, serving.ShardedIndexSnapshot)
    assert ssnap.ntotal == snap.ntotal

    s_ref, i_ref = snap.search(q, 10)
    s_got, i_got = ssnap.search(q, 10)
    np.testing.assert_array_equal(np.asarray(i_got), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_ref),
                               atol=1e-4)

    # warm merge executable: repeat searches and same-builder rebuilds
    # (same cap bucket, same mesh) compile NOTHING new
    with CompileCounter() as cc:
        ssnap.search(q, 10)
    assert cc.count == 0
    ssnap2 = shard.build(ids, x)
    with CompileCounter() as cc:
        ssnap2.search(q, 10)
    assert cc.count == 0

    # host-gather roundtrip reassembles the exact unsharded snapshot view
    back = serving.unshard_snapshot(ssnap)
    _, i_back = back.search(q, 10)
    np.testing.assert_array_equal(np.asarray(i_back), np.asarray(i_ref))


def test_sharded_compact_absorbs_rows(mesh):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2000, 32)).astype(np.float32)
    fresh = rng.normal(size=(64, 32)).astype(np.float32)
    shard = serving.IndexBuilder(
        "ivf-flat", 32, ivf=serving.IVFConfig(nlist=16, nprobe=8),
        devices=jax.devices()[:8])
    snap = shard.build(np.arange(1, 2001), x)
    snap2 = shard.compact(snap, np.arange(2001, 2065), fresh)
    assert isinstance(snap2, serving.ShardedIndexSnapshot)
    assert snap2.ntotal == 2064 and snap2.version > snap.version
    q = fresh[:4]
    _, got = snap2.search(q, 1)           # fresh rows are retrievable
    np.testing.assert_array_equal(np.asarray(got)[:, 0],
                                  np.arange(2001, 2005))


# ------------------------------------------------------------------ launch

def test_parse_mesh_arg_contract(cfg):
    assert parse_mesh_arg(None) is None
    assert parse_mesh_arg("data=1") is None     # exact pre-mesh path
    m = parse_mesh_arg("data=8")
    assert m is not None and m.devices.size == 8
    with pytest.raises(SystemExit):
        parse_mesh_arg("bogus")
    with pytest.raises(SystemExit):
        parse_mesh_arg("model=4")
    with pytest.raises(SystemExit):
        parse_mesh_arg(f"data={jax.device_count() * 2}")
    # mesh-less Trainer is bit-for-bit the old path: the jit exists from
    # __init__ and nothing consults a mesh again
    tr = training.get_trainer("speedyfeed", cfg=cfg)
    assert tr.mesh is None and tr._step_jit is not None
