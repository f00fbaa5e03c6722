"""Serving subsystem: PQ reconstruction (uint8 codes), IVF recall vs exact
MIPS, the versioned IndexSnapshot lifecycle (builder, atomic swap under
concurrent queries, off-path compaction, delta watermark/prune), online
delta/compaction equivalence, Pallas LUT-kernel parity (interpret), and
the padded-CSR device storage (mutation sequences checked against an
exact-MIPS / code-reconstruction oracle, compile hygiene per cap bucket
and across swaps, probe-metric recall regression, hybrid over-fetch
contract).  Index classes are mutated directly only here, where the
write surface itself is under test — production call sites go through
the lifecycle API (publish/rebuild/swap)."""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro import serving
from repro.kernels import ref
from repro.kernels.pq_scoring import pq_lut_scores as pq_raw


def make_corpus(n=2000, d=32, rank=8, seed=0):
    """Low-rank + noise vectors — the spectral shape of PLM embeddings
    (iid Gaussian is the PQ-adversarial case and not what encoders emit)."""
    rng = np.random.default_rng(seed)
    basis = rng.normal(size=(rank, d))
    x = rng.normal(size=(n, rank)) @ basis + 0.1 * rng.normal(size=(n, d))
    return x.astype(np.float32)


def recall_at_k(ids, ref_ids):
    k = ids.shape[1]
    return np.mean([len(set(ids[b]) & set(ref_ids[b])) / k
                    for b in range(ids.shape[0])])


@pytest.fixture(scope="module")
def corpus():
    x = make_corpus()
    q = make_corpus(16, seed=7)
    ids = np.arange(1, x.shape[0] + 1)
    exact = serving.FlatIndex(x.shape[1])
    exact.add(ids, x)
    _, ref_ids = exact.search(q, 10)
    return x, q, ids, ref_ids


# ---------------------------------------------------------------- PQ core
def test_pq_reconstruction_error_bound():
    x = make_corpus(1000)
    cfg = serving.PQConfig(n_subvec=16, n_codes=64)
    cb = serving.pq_train(jax.random.PRNGKey(0), jnp.asarray(x), cfg)
    codes = serving.pq_encode(cb, jnp.asarray(x))
    assert codes.shape == (1000, 16) and codes.dtype == jnp.uint8
    assert int(codes.max()) < cfg.n_codes and int(codes.min()) >= 0
    rec = np.asarray(serving.pq_decode(cb, codes))
    rel = np.linalg.norm(rec - x) / np.linalg.norm(x)
    assert rel < 0.25, f"PQ relative reconstruction error {rel:.3f}"


def test_pq_lut_matches_decoded_dot():
    """ADC score == <q, decode(codes)> exactly (same codebook arithmetic)."""
    x, q = make_corpus(256), make_corpus(4, seed=3)
    cb = serving.pq_train(jax.random.PRNGKey(1), jnp.asarray(x),
                          serving.PQConfig())
    codes = serving.pq_encode(cb, jnp.asarray(x))
    lut = serving.pq_lut(cb, jnp.asarray(q))
    scores = ref.pq_lut_scores(lut, codes[None])
    exp = q @ np.asarray(serving.pq_decode(cb, codes)).T
    np.testing.assert_allclose(np.asarray(scores), exp, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- Pallas LUT
@pytest.mark.parametrize("variant", ["onehot", "gather"])
@pytest.mark.parametrize("B,M,K,N,block_n,shared", [
    (4, 8, 32, 300, 128, False),    # per-query candidate lists (IVF path)
    (4, 8, 32, 300, 128, True),     # one shared corpus scan (flat-PQ path)
    (1, 4, 256, 64, 64, True),      # K=256 (uint8-style codebooks)
    (3, 16, 16, 129, 32, False),    # N not a multiple of block_n
])
def test_pq_kernel_matches_xla_reference(B, M, K, N, block_n, shared,
                                         variant):
    key = jax.random.PRNGKey(B * 100 + N)
    k1, k2 = jax.random.split(key)
    lut = jax.random.normal(k1, (B, M, K))
    codes = jax.random.randint(k2, (1 if shared else B, N, M), 0, K)
    out = pq_raw(lut, codes, block_n=block_n, interpret=True,
                 variant=variant)
    exp = ref.pq_lut_scores(lut, codes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_pq_search_flat_scan(corpus):
    """Full ADC scan through the kernel: the compressed top-50 covers the
    true top-10 (the stage-1 recall property two-stage serving rests on)."""
    x, q, ids, ref_ids = corpus
    cb = serving.pq_train(jax.random.PRNGKey(2), jnp.asarray(x),
                          serving.PQConfig(n_subvec=16, n_codes=32))
    codes = serving.pq_encode(cb, jnp.asarray(x))
    _, rows = serving.pq_search(cb, codes, q, 50)
    got = ids[np.asarray(rows)]
    covered = np.mean([len(set(got[b]) & set(ref_ids[b])) / ref_ids.shape[1]
                       for b in range(got.shape[0])])
    assert covered >= 0.9


# -------------------------------------------------------------- IVF recall
# Recall is a property of the index, not of one PRNG draw: a single build
# seed sits inside a seed-to-seed spread of several points (JAX 0.5's
# threefry_partitionable default changed every jax.random stream and moved
# seed 0 of the old uniformly-seeded k-means from 0.95 to 0.894), so the
# bar applies to the mean over several build seeds.
BUILD_SEEDS = range(4)


def test_ivf_flat_recall_at_10(corpus):
    x, q, ids, ref_ids = corpus
    recalls = []
    for seed in BUILD_SEEDS:
        idx = serving.make_index("ivf-flat", x.shape[1],
                                 ivf=serving.IVFConfig(nlist=32, nprobe=8))
        idx.train(jax.random.PRNGKey(seed), jnp.asarray(x))
        idx.add(ids, x)
        _, got = idx.search(q, 10)
        recalls.append(recall_at_k(got, ref_ids))
    assert np.mean(recalls) >= 0.9, recalls


def test_ivfpq_two_stage_recall_at_10(corpus):
    """The served configuration: IVF-PQ recall@k' + exact re-rank, built
    and installed through the lifecycle API."""
    x, q, ids, ref_ids = corpus
    store = np.zeros((x.shape[0] + 1, x.shape[1]), np.float32)
    store[ids] = x
    recalls = []
    for seed in BUILD_SEEDS:
        builder = serving.IndexBuilder(
            "ivf-pq", x.shape[1], ivf=serving.IVFConfig(nlist=32, nprobe=8),
            seed=seed)
        svc = serving.RetrievalService(builder, store, k=10, k_prime=100)
        svc.swap(builder.build(ids, x))
        _, got = svc.query(q)
        recalls.append(recall_at_k(got, ref_ids))
    assert np.mean(recalls) >= 0.9, recalls


def test_exact_index_is_the_oracle(corpus):
    x, q, ids, ref_ids = corpus
    idx = serving.make_index("exact", x.shape[1])
    idx.train(jax.random.PRNGKey(0), x)
    idx.add(ids, x)
    _, got = idx.search(q, 10)
    assert recall_at_k(got, ref_ids) == 1.0


# ------------------------------------------------------------ online delta
def test_delta_hybrid_equals_post_compaction(corpus):
    """Hybrid (main + delta) top-k == top-k after compacting the delta into
    the main index, with an exhaustive scan (nprobe = nlist)."""
    x, q, ids, _ = corpus
    n_main = 1800
    cfg = serving.IVFConfig(nlist=16, nprobe=16)
    a = serving.make_index("ivf-flat", x.shape[1], ivf=cfg)
    a.train(jax.random.PRNGKey(0), jnp.asarray(x[:n_main]))
    a.add(ids[:n_main], x[:n_main])
    delta = serving.DeltaBuffer(x.shape[1], compact_threshold=10 ** 9)
    delta.add(ids[n_main:], x[n_main:])
    s_h, i_h = serving.hybrid_search(a, delta, q, 10)

    delta.compact_into(a)
    assert len(delta) == 0 and a.ntotal == x.shape[0]
    s_c, i_c = a.search(q, 10)
    np.testing.assert_array_equal(i_h, i_c)
    np.testing.assert_allclose(s_h, s_c, rtol=1e-5, atol=1e-5)


def test_delta_upsert_freshest_wins(corpus):
    """A re-published id is served from the delta tier, not the stale row."""
    x, q, ids, _ = corpus
    main = serving.FlatIndex(x.shape[1])
    main.add(ids, x)
    delta = serving.DeltaBuffer(x.shape[1])
    # republish id 1 with an embedding that should now win every query
    fresh = 10.0 * q[0] / np.linalg.norm(q[0])
    delta.add([1], fresh[None])
    _, i_h = serving.hybrid_search(main, delta, q[:1], 5)
    assert i_h[0, 0] == 1
    assert (i_h[0] != serving.PAD_ID).all()
    assert len(set(i_h[0].tolist())) == 5       # no duplicate ids


def test_ingest_from_cache():
    from repro.core.cache import CacheConfig, CacheState, NEVER, init_cache
    cfg = CacheConfig(n_news=50, news_dim=8)
    state = init_cache(cfg)
    emb = jnp.arange(50 * 8, dtype=jnp.float32).reshape(50, 8)
    written = state.written_step.at[jnp.array([3, 7])].set(5)
    state = CacheState(emb, written)
    delta = serving.DeltaBuffer(8)
    n = serving.ingest_from_cache(delta, state, [3, 7, 9])
    assert n == 2 and len(delta) == 2           # id 9 was never encoded
    np.testing.assert_allclose(delta.emb[0], np.asarray(emb[3]))


def test_republish_then_compact_does_not_duplicate(corpus):
    """A re-published id compacted into the main index replaces the stale
    row (index add() is an upsert) — no duplicate ids in top-k."""
    x, q, ids, _ = corpus
    for kind in ("exact", "ivf-flat"):
        idx = serving.make_index(kind, x.shape[1],
                                 ivf=serving.IVFConfig(nlist=8, nprobe=8))
        idx.train(jax.random.PRNGKey(0), jnp.asarray(x))
        idx.add(ids, x)
        delta = serving.DeltaBuffer(x.shape[1], compact_threshold=1)
        fresh = 10.0 * q[0] / np.linalg.norm(q[0])
        delta.add([5], fresh[None])
        delta.compact_into(idx)
        assert idx.ntotal == x.shape[0]         # replaced, not appended
        _, got = idx.search(q[:1], 5)
        assert got[0, 0] == 5
        assert len(set(got[0].tolist())) == 5   # no duplicates


def test_service_publish_compacts_past_threshold(corpus):
    """Publish stays O(append); crossing the threshold schedules an
    off-path compaction that absorbs the delta and bumps the version."""
    x, q, ids, _ = corpus
    builder = serving.IndexBuilder("ivf-flat", x.shape[1],
                                   ivf=serving.IVFConfig(nlist=8, nprobe=8))
    store = np.zeros((x.shape[0] + 1, x.shape[1]), np.float32)
    store[ids[:1000]] = x[:1000]
    svc = serving.RetrievalService(builder, store, k=10, k_prime=64,
                                   compact_threshold=600)
    svc.swap(builder.build(ids[:1000], x[:1000]))
    svc.publish(ids[1000:1500], x[1000:1500])   # below threshold: delta tier
    assert svc.n_pending == 500 and svc.ntotal == 1000
    v0 = svc.version
    svc.publish(ids[1500:2000], x[1500:2000])   # crosses: compaction fires
    svc.wait_for_build()
    assert svc.n_pending == 0 and svc.ntotal == 2000
    assert svc.version > v0
    _, got = svc.query(q)
    assert (got != serving.PAD_ID).all()


# ----------------------------------------------------- masked LUT kernel
@pytest.mark.parametrize("shared_v", [False, True])
def test_pq_kernel_masked_matches_xla_reference(shared_v):
    """The padded-CSR gather path: invalid slots must score -inf."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(42), 3)
    B, M, K, N = 3, 8, 32, 200
    lut = jax.random.normal(k1, (B, M, K))
    codes = jax.random.randint(k2, (B, N, M), 0, K)
    valid = jax.random.bernoulli(k3, 0.7, (1 if shared_v else B, N))
    out = np.asarray(pq_raw(lut, codes, valid, block_n=64, interpret=True))
    exp = np.asarray(ref.pq_lut_scores(lut, codes, valid))
    np.testing.assert_allclose(out, exp, rtol=1e-5, atol=1e-5)
    invalid = np.broadcast_to(~np.asarray(valid), (B, N))
    assert np.isneginf(out[invalid]).all()
    assert np.isfinite(out[~invalid]).all()


# ----------------------------------- padded-CSR vs exact/decode oracles
# (the legacy host layout — and the device/host parity scaffolding that
# verified it — is gone; mutation correctness is now checked against an
# exact-MIPS FlatIndex oracle for ivf-flat and a numpy reconstruction of
# the CSR codes for ivf-pq)

MUTATION_SEQUENCES = [
    [("add", 120, 60), ("remove", 30, 40), ("upsert", 10, 20),
     ("compact", 180, 60), ("remove", 200, 39), ("upsert", 100, 50)],
    [("remove", 0, 120), ("add", 120, 120), ("compact", 0, 120)],
    [("upsert", 0, 240), ("remove", 100, 60), ("add", 100, 60)],
]


def _apply_ops(idx, ops, x, ids):
    """Replay an add/remove/upsert/compact sequence onto one index (the
    FlatIndex oracle supports the same API: add() is an upsert)."""
    n = x.shape[0]
    for op, start, length in ops:
        lo, hi = start % n, min(start % n + length, n)
        sel = slice(lo, hi)
        if op == "add":
            idx.add(ids[sel], x[sel])
        elif op == "remove":
            idx.remove(ids[sel])
        elif op == "upsert":                 # re-add with changed vectors
            idx.add(ids[sel], x[sel] + 0.25)
        elif op == "compact":                # delta tier -> bulk device add
            delta = serving.DeltaBuffer(x.shape[1],
                                        compact_threshold=10 ** 9)
            delta.add(ids[sel], x[sel])
            delta.compact_into(idx)


def _csr_members(idx):
    """{id: (cell, slot)} read straight off the device CSR arrays."""
    ids_dev = np.asarray(idx._ids_dev)
    lens = np.asarray(idx._lens)
    out = {}
    for cell in range(ids_dev.shape[0]):
        for slot in range(lens[cell]):
            assert ids_dev[cell, slot] != serving.PAD_ID
            assert ids_dev[cell, slot] not in out, "duplicate id in lists"
            out[int(ids_dev[cell, slot])] = (cell, slot)
    return out


@pytest.mark.parametrize("ops", MUTATION_SEQUENCES)
def test_csr_mutations_match_exact_oracle(ops):
    """IVF-Flat with exhaustive probing (nprobe == nlist) must agree with
    an exact-MIPS FlatIndex replaying the same mutation sequence: same
    membership, same top-k id sets, same scores."""
    x = make_corpus(240, d=16, rank=4, seed=20)
    ids = np.arange(1, 241)
    q = make_corpus(4, d=16, rank=4, seed=11)
    idx = serving.make_index("ivf-flat", 16,
                             ivf=serving.IVFConfig(nlist=8, nprobe=8))
    idx.train(jax.random.PRNGKey(0), jnp.asarray(x))
    oracle = serving.FlatIndex(16)
    for target in (idx, oracle):
        _apply_ops(target, [("add", 0, 120)] + ops, x, ids)
    assert idx.ntotal == oracle.ntotal
    assert set(_csr_members(idx)) == set(oracle._ids)
    s_d, i_d = idx.search(q, 10)
    s_o, i_o = oracle.search(q, 10)
    np.testing.assert_allclose(-np.sort(-s_d, axis=1),
                               -np.sort(-s_o, axis=1), rtol=1e-4, atol=1e-4)
    for b in range(q.shape[0]):
        assert set(i_d[b]) == set(i_o[b]), (b, i_d[b], i_o[b])


@pytest.mark.parametrize("ops", MUTATION_SEQUENCES)
def test_csr_pq_search_matches_code_reconstruction(ops):
    """IVF-PQ exhaustive search must equal the score every stored uint8
    code row reconstructs to in numpy: <q, cell_mean> + <q, decode(code)>
    — a direct oracle over the CSR payload content after any mutations."""
    x = make_corpus(240, d=16, rank=4, seed=21)
    ids = np.arange(1, 241)
    q = make_corpus(4, d=16, rank=4, seed=12)
    idx = serving.make_index(
        "ivf-pq", 16, ivf=serving.IVFConfig(nlist=8, nprobe=8),
        pq=serving.PQConfig(n_subvec=4, n_codes=16))
    idx.train(jax.random.PRNGKey(0), jnp.asarray(x))
    _apply_ops(idx, [("add", 0, 120)] + ops, x, ids)

    members = _csr_members(idx)
    assert idx._payload_dev.dtype == jnp.uint8           # 4x code memory
    codes = np.asarray(idx._payload_dev)
    rows = sorted(members)                               # ids ascending
    cells = np.array([members[i][0] for i in rows])
    row_codes = np.stack([codes[members[i]] for i in rows])
    decoded = np.asarray(serving.pq_decode(idx.codebook,
                                           jnp.asarray(row_codes)))
    expected = (q @ idx.centroids_raw[cells].T            # coarse term
                + q @ decoded.T)                          # [B, n_members]

    k = 10
    s_d, i_d = idx.search(q, k)
    order = np.argsort(-expected, axis=1)[:, :k]
    exp_ids = np.asarray(rows)[order]
    exp_s = np.take_along_axis(expected, order, axis=1)
    np.testing.assert_allclose(s_d, exp_s, rtol=1e-4, atol=1e-4)
    for b in range(q.shape[0]):
        assert set(i_d[b]) == set(exp_ids[b]), (b, i_d[b], exp_ids[b])


@pytest.mark.parametrize("kind", ["ivf-flat", "ivf-pq"])
def test_csr_one_executable_per_cap_bucket(kind):
    """Searches across batches with different candidate loads reuse ONE
    warm executable per (index kind, cap bucket); growing into the next
    power-of-two bucket compiles exactly one more."""
    from repro import training
    x = make_corpus(4000, d=16, rank=4, seed=5)
    ids = np.arange(1, 4001)
    q = make_corpus(8, d=16, rank=4, seed=6)
    idx = serving.make_index(
        kind, 16, ivf=serving.IVFConfig(nlist=8, nprobe=4),
        pq=serving.PQConfig(n_subvec=4, n_codes=16))
    idx.train(jax.random.PRNGKey(0), jnp.asarray(x))
    idx.add(ids[:200], x[:200])
    cap0 = idx.cap
    # warm: the cap0 search executable plus the fixed-shape mutation ops
    idx.search(q, 10)
    idx.remove(ids[:8]); idx.add(ids[:8], x[:8])
    idx.search(q, 10)
    with training.CompileCounter() as cc:
        for i in range(3):       # net-zero mutations: load varies, cap fixed
            lo = 8 * i + 8
            idx.remove(ids[lo:lo + 8])
            idx.search(q, 10)
            idx.add(ids[lo:lo + 8], x[lo:lo + 8])
            idx.search(q, 10)
    assert idx.cap == cap0
    assert cc.count == 0, f"warm cap bucket recompiled {cc.count}x"
    # overflow -> next pow2 bucket: add rows until the bucket really grows
    # (how many that takes depends on the partition k-means drew)
    n = 200
    while idx.cap == cap0 and n < len(ids):
        idx.add(ids[n:n + 100], x[n:n + 100])
        n += 100
    cap1 = idx.cap
    assert cap1 > cap0
    with training.CompileCounter() as cc2:
        idx.search(q, 10)                    # first search at the new cap
    assert cc2.count >= 1
    idx.remove(ids[:8]); idx.add(ids[:8], x[:8])   # warm mutations @ new cap
    idx.search(q, 10)
    with training.CompileCounter() as cc3:
        for i in range(3):
            lo = 8 * i + 8
            idx.remove(ids[lo:lo + 8])
            idx.search(q, 10)
            idx.add(ids[lo:lo + 8], x[lo:lo + 8])
            idx.search(q, 10)
    assert idx.cap == cap1
    assert cc3.count == 0, f"new cap bucket recompiled {cc3.count}x"


# ------------------------------------------------- probe-metric recall
def make_clustered_unit(n=2000, d=32, n_dir=16, noise=0.25, seed=0):
    """Unit-norm direction clusters — the spectral shape of PLM news
    embeddings (topically clustered, norm-concentrated)."""
    rng = np.random.default_rng(seed)
    dirs = rng.normal(size=(n_dir, d))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    x = dirs[rng.integers(0, n_dir, n)] + noise * rng.normal(size=(n, d))
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x.astype(np.float32), dirs


def test_l2_probe_recall_not_worse_than_ip():
    """Regression (metric mismatch): probing by the partition's own
    spherical/L2 metric must never lose to the legacy inner-product
    ranking against the raw cell means, at any fixed nprobe."""
    x, dirs = make_clustered_unit()
    rng = np.random.default_rng(7)
    q = dirs[rng.integers(0, 16, 24)] + 0.15 * rng.normal(size=(24, 32))
    q = (q / np.linalg.norm(q, axis=1, keepdims=True)).astype(np.float32)
    ids = np.arange(1, x.shape[0] + 1)
    exact = serving.FlatIndex(x.shape[1])
    exact.add(ids, x)
    _, ref_ids = exact.search(q, 10)

    recalls = {}
    for metric in ("l2", "ip"):
        idx = serving.make_index(
            "ivf-flat", x.shape[1],
            ivf=serving.IVFConfig(nlist=32, nprobe=1, metric=metric))
        idx.train(jax.random.PRNGKey(0), jnp.asarray(x))
        idx.add(ids, x)
        for nprobe in (1, 2, 4):
            idx.cfg = dataclasses.replace(idx.cfg, nprobe=nprobe)
            _, got = idx.search(q, 10)
            recalls[metric, nprobe] = recall_at_k(got, ref_ids)
    for nprobe in (1, 2, 4):
        assert recalls["l2", nprobe] >= recalls["ip", nprobe], recalls
    assert recalls["l2", 4] >= 0.9


# --------------------------------------------- hybrid over-fetch contract
def test_hybrid_returns_exactly_k_from_joint_tiers():
    """Whenever the two tiers jointly hold >= k distinct ids, the merged
    result is exactly k valid distinct ids."""
    rng = np.random.default_rng(3)
    d, k = 8, 5
    xm = rng.normal(size=(3, d)).astype(np.float32)
    xd = rng.normal(size=(4, d)).astype(np.float32)
    q = rng.normal(size=(2, d)).astype(np.float32)
    main = serving.FlatIndex(d)
    main.add(np.array([1, 2, 3]), xm)
    delta = serving.DeltaBuffer(d, compact_threshold=10 ** 9)
    delta.add(np.array([2, 3, 4, 5]), xd)    # jointly {1..5}: exactly k
    s, i = serving.hybrid_search(main, delta, q, k)
    for b in range(q.shape[0]):
        assert (i[b] != serving.PAD_ID).all()
        assert len(set(i[b].tolist())) == k
        assert set(i[b].tolist()) == {1, 2, 3, 4, 5}
        assert np.isfinite(s[b]).all()


def test_hybrid_equals_compaction_under_stale_saturation():
    """Regression (hybrid under-fill / window loss): when every id in the
    main tier's top-k window is stale (republished into the delta with
    embeddings that now rank at the bottom), the merged result must still
    equal the post-compaction search — the fresh main ids that the stale
    entries pushed out of the window must be recovered."""
    rng = np.random.default_rng(4)
    d, n, k = 16, 60, 5
    x = rng.normal(size=(n, d)).astype(np.float32)
    q = rng.normal(size=(2, d)).astype(np.float32)
    ids = np.arange(1, n + 1)
    # republish the ids dominating BOTH queries' rankings, demoted so far
    # they drop out of the true top-k entirely
    top = np.unique(np.argsort(-(q @ x.T), axis=1)[:, :10])
    stale_ids = ids[top]

    def build():
        main = serving.FlatIndex(d)
        main.add(ids, x)
        delta = serving.DeltaBuffer(d, compact_threshold=10 ** 9)
        delta.add(stale_ids, -x[top])
        return main, delta

    main, delta = build()
    s_h, i_h = serving.hybrid_search(main, delta, q, k)
    main2, delta2 = build()
    delta2.compact_into(main2)
    s_c, i_c = main2.search(q, k)
    np.testing.assert_array_equal(i_h, i_c)
    np.testing.assert_allclose(s_h, s_c, rtol=1e-5, atol=1e-5)
    assert (i_h != serving.PAD_ID).all()


# ------------------------------------------------- publish scatter path
def test_publish_scatters_rows_without_full_reupload():
    """Regression (publish H2D storm): publishing a handful of fresh ids
    must not re-upload the whole [N, d] store to device.  The service's
    EmbeddingStore owns the grow-and-scatter for BOTH the host store and
    the device mirror (previously copy-pasted between service and
    launcher); everything but the explicit device_put of the changed rows
    runs under a host->device transfer guard."""
    d, n = 16, 50
    store = np.zeros((n, d), np.float32)
    svc = serving.RetrievalService(
        serving.IndexBuilder("exact", d), store, k=5,
        compact_threshold=10 ** 9, auto_compact=False)
    svc.store.attach_device_mirror()
    svc.publish(np.array([3, 7]), np.ones((2, d), np.float32))  # warm
    fresh = 2.0 * np.ones((2, d), np.float32)
    with jax.transfer_guard_host_to_device("disallow"):
        svc.publish(np.array([9, 11]), fresh)
    np.testing.assert_allclose(np.asarray(svc.store.device)[[9, 11]], fresh)
    np.testing.assert_allclose(np.asarray(svc.store.device[3]), np.ones(d))
    assert svc.store.device.shape == (n, d)
    # growth path: out-of-range ids extend both store and device mirror
    svc.publish(np.array([n + 2]), 3.0 * np.ones((1, d), np.float32))
    assert svc.store_emb.shape[0] == n + 3
    assert svc.store.device.shape == (n + 3, d)
    np.testing.assert_allclose(np.asarray(svc.store.device[n + 2]),
                               3.0 * np.ones(d))
    # a duplicated id within one batch resolves last-write-wins in BOTH
    # the numpy store and the device mirror (scatter order for duplicate
    # indices is undefined, so the store dedups before scattering)
    dup = np.stack([4.0 * np.ones(d), 5.0 * np.ones(d)]).astype(np.float32)
    svc.publish(np.array([13, 13]), dup)
    np.testing.assert_allclose(svc.store_emb[13], dup[1])
    np.testing.assert_allclose(np.asarray(svc.store.device[13]), dup[1])
    # ...and the delta tier serves the deduped row, not both
    _, got = svc.query(np.ones((1, d), np.float32), k=5)
    assert len(set(got[0].tolist())) == 5
    # ids the device index could never hold are rejected at the entry
    # point, not at some later compaction
    with pytest.raises(ValueError, match="2\\*\\*31"):
        svc.publish(np.array([2 ** 31]), np.ones((1, d), np.float32))
    with pytest.raises(ValueError, match="2\\*\\*31"):
        svc.publish(np.array([-1]), np.ones((1, d), np.float32))


def test_hybrid_overfetch_width_is_quantized():
    """Regression: the over-fetch width k + len(delta) is a static shape
    of the device index's jitted search, so it is rounded up to a power
    of two — publishes that grow the delta inside one bucket must not
    mint new search executables (the delta tier's own brute-force scan
    recompiling per size is separate, known PR-1 behavior)."""
    from repro.serving.index import _search_flat_csr
    x = make_corpus(400, d=16, rank=4, seed=8)
    ids = np.arange(1, 401)
    q = make_corpus(8, d=16, rank=4, seed=9)
    idx = serving.make_index("ivf-flat", 16,
                             ivf=serving.IVFConfig(nlist=8, nprobe=4))
    idx.train(jax.random.PRNGKey(0), jnp.asarray(x))
    idx.add(ids[:380], x[:380])
    delta = serving.DeltaBuffer(16, compact_threshold=10 ** 9)
    delta.add(ids[380:385], x[380:385])          # len 5 -> fetch width 16
    serving.hybrid_search(idx, delta, q, 8)      # warm the width-16 entry
    n0 = _search_flat_csr._cache_size()
    for hi in (386, 387, 388):                   # len 6, 7, 8 -> still 16
        delta.add(ids[hi - 1:hi], x[hi - 1:hi])
        _, i = serving.hybrid_search(idx, delta, q, 8)
        assert (i != serving.PAD_ID).all()
    assert _search_flat_csr._cache_size() == n0, \
        "delta growth within a pow2 bucket minted a new search executable"


# ------------------------------------------- snapshot lifecycle (PR 5)
def _store_for(x, ids):
    store = np.zeros((int(ids.max()) + 1, x.shape[1]), np.float32)
    store[ids] = x
    return store


def test_merge_topk_dedup_matches_reference_loop():
    """The vectorized hybrid merge must equal the per-query Python loop it
    replaced, exactly, on duplicated / staled / padded candidate sets."""

    def reference(scores, ids, k):        # the pre-vectorization merge loop
        B = scores.shape[0]
        out_s = np.full((B, k), -np.inf, np.float32)
        out_i = np.full((B, k), serving.PAD_ID, np.int64)
        for b in range(B):
            order = np.argsort(-scores[b], kind="stable")
            seen, picked = set(), []
            for p in order:
                if ids[b, p] == serving.PAD_ID or int(ids[b, p]) in seen:
                    continue
                seen.add(int(ids[b, p]))
                picked.append(p)
                if len(picked) == k:
                    break
            out_s[b, :len(picked)] = scores[b, picked]
            out_i[b, :len(picked)] = ids[b, picked]
        return out_s, out_i

    rng = np.random.default_rng(5)
    for trial in range(25):
        B = int(rng.integers(1, 5))
        C = int(rng.integers(1, 40))
        k = int(rng.integers(1, 13))
        ids = rng.integers(1, 15, size=(B, C)).astype(np.int64)  # many dups
        scores = rng.normal(size=(B, C)).astype(np.float32)
        scores = (np.round(scores * 4) / 4).astype(np.float32)   # force ties
        stale = rng.random(size=(B, C)) < 0.3      # nulled main-tier hits
        ids = np.where(stale, serving.PAD_ID, ids)
        scores = np.where(stale, -np.inf, scores).astype(np.float32)
        sunk = rng.random(size=(B, C)) < 0.1       # valid id, -inf score
        scores = np.where(sunk, -np.inf, scores).astype(np.float32)
        got_s, got_i = serving.merge_topk_dedup(scores, ids, k)
        exp_s, exp_i = reference(scores, ids, k)
        np.testing.assert_array_equal(got_i, exp_i, err_msg=f"trial {trial}")
        np.testing.assert_array_equal(got_s, exp_s, err_msg=f"trial {trial}")


def test_query_k_exceeding_k_prime_raises():
    """Regression: query(k > k_prime) used to silently return PAD-padded
    junk rows beyond the candidate set; now it is a clear error."""
    builder = serving.IndexBuilder("exact", 8)
    svc = serving.RetrievalService(builder, np.zeros((4, 8), np.float32),
                                   k=4, k_prime=8)
    with pytest.raises(ValueError, match="k_prime"):
        svc.query(np.zeros((1, 8), np.float32), k=9)
    svc.query(np.zeros((1, 8), np.float32), k=8)   # k == k_prime is fine


def test_snapshot_immutable_across_builder_mutation():
    """A snapshot's results can never change after it is taken — builder
    compaction produces a NEW snapshot and leaves the old one frozen."""
    x = make_corpus(200, d=16, rank=4, seed=30)
    ids = np.arange(1, 201)
    q = make_corpus(4, d=16, rank=4, seed=31)
    for kind in ("exact", "ivf-flat", "ivf-pq"):
        builder = serving.IndexBuilder(
            kind, 16, ivf=serving.IVFConfig(nlist=8, nprobe=8),
            pq=serving.PQConfig(n_subvec=4, n_codes=16))
        snap1 = builder.build(ids[:120], x[:120])
        s1, i1 = snap1.search(q, 10)
        snap2 = builder.compact(snap1, ids[120:], x[120:])
        assert snap2.version > snap1.version
        assert snap1.ntotal == 120 and snap2.ntotal == 200
        s1b, i1b = snap1.search(q, 10)         # old snapshot: bit-identical
        np.testing.assert_array_equal(i1, i1b)
        np.testing.assert_array_equal(s1, s1b)
        assert set(snap2.member_ids) == set(ids.tolist())
        assert set(snap1.member_ids) == set(ids[:120].tolist())


def test_delta_watermark_prune():
    """A build absorbs the delta up to its watermark; ids re-published
    during the build keep their newer rows after the prune."""
    d = 8
    delta = serving.DeltaBuffer(d, compact_threshold=10 ** 9)
    delta.add([1, 2, 3], np.ones((3, d), np.float32))
    wm = delta.watermark()
    delta.add([4], np.ones((1, d), np.float32))          # after the build cut
    delta.add([2], 2.0 * np.ones((1, d), np.float32))    # re-published
    delta.prune(wm)
    assert set(delta.ids.tolist()) == {2, 4}
    row2 = delta.emb[delta.ids.tolist().index(2)]
    np.testing.assert_allclose(row2, 2.0 * np.ones(d))   # the NEWER row
    delta.prune(delta.watermark())
    assert len(delta) == 0


def test_lifecycle_compaction_equivalence(corpus):
    """Query through (snapshot, delta) == query after the builder compacts
    the delta and the new snapshot is swapped in (exhaustive probing)."""
    x, q, ids, _ = corpus
    n_main = 1800
    builder = serving.IndexBuilder("ivf-flat", x.shape[1],
                                   ivf=serving.IVFConfig(nlist=16, nprobe=16))
    svc = serving.RetrievalService(builder, _store_for(x, ids), k=10,
                                   k_prime=64, compact_threshold=10 ** 9,
                                   auto_compact=False)
    svc.swap(builder.build(ids[:n_main], x[:n_main]))
    svc.publish(ids[n_main:], x[n_main:])
    view = svc._view
    s_h, i_h = serving.hybrid_search(view.snapshot, view.delta, q, 10)
    v0 = svc.version
    svc.rebuild(mode="compact", block=True)
    assert svc.version > v0 and svc.n_pending == 0
    assert svc.ntotal == x.shape[0]
    s_c, i_c = svc.snapshot().search(q, 10)
    np.testing.assert_array_equal(i_h, i_c)
    np.testing.assert_allclose(s_h, s_c, rtol=1e-5, atol=1e-5)


def test_publish_never_builds_on_the_request_thread(corpus):
    """publish is O(delta append): IVF assignment / PQ encode run on the
    builder, and past the threshold the compaction happens on a
    background thread — never on the publishing (request) thread."""
    x, q, ids, _ = corpus
    build_threads = []
    orig = serving.IndexBuilder.compact

    def spy(self, snap, i, e):
        build_threads.append(threading.get_ident())
        return orig(self, snap, i, e)

    builder = serving.IndexBuilder(
        "ivf-pq", x.shape[1], ivf=serving.IVFConfig(nlist=8, nprobe=8),
        pq=serving.PQConfig(n_subvec=4, n_codes=16))
    svc = serving.RetrievalService(builder, _store_for(x, ids), k=10,
                                   k_prime=64, compact_threshold=60)
    svc.swap(builder.build(ids[:100], x[:100]))
    try:
        serving.IndexBuilder.compact = spy
        svc.publish(ids[100:140], x[100:140])     # below threshold
        assert not build_threads and svc.n_pending == 40
        svc.publish(ids[140:180], x[140:180])     # crosses -> background
        svc.wait_for_build()
    finally:
        serving.IndexBuilder.compact = orig
    assert build_threads, "threshold crossing never scheduled a compaction"
    assert all(t != threading.get_ident() for t in build_threads), \
        "compaction ran on the publishing thread"
    assert svc.n_pending == 0 and svc.ntotal == 180
    _, got = svc.query(q)
    assert (got != serving.PAD_ID).all()


def test_swap_atomicity_under_concurrent_queries():
    """Queries racing a swapper thread must return results consistent with
    exactly one snapshot version — never a mix.  The two versions hold
    disjoint id ranges, so any mixed-version batch would be caught."""
    d = 16
    rng = np.random.default_rng(9)
    xa = rng.normal(size=(120, d)).astype(np.float32)
    xb = rng.normal(size=(120, d)).astype(np.float32)
    ids_a = np.arange(1, 121)
    ids_b = np.arange(201, 321)
    q = rng.normal(size=(4, d)).astype(np.float32)
    builder = serving.IndexBuilder("ivf-flat", d,
                                   ivf=serving.IVFConfig(nlist=8, nprobe=8))
    store = np.zeros((321, d), np.float32)
    store[ids_a] = xa
    store[ids_b] = xb
    svc = serving.RetrievalService(builder, store, k=8, k_prime=32,
                                   auto_compact=False)
    snap_a = builder.build(ids_a, xa)
    snap_b = builder.build(ids_b, xb)
    set_a, set_b = set(ids_a.tolist()), set(ids_b.tolist())
    for snap in (snap_a, snap_b):         # warm both executables
        svc.swap(snap)
        svc.query(q)
    done = threading.Event()

    def swapper():
        for i in range(120):
            svc.swap(snap_a if i % 2 else snap_b)
            time.sleep(0.001)
        done.set()

    t = threading.Thread(target=swapper)
    t.start()
    n = 0
    try:
        while not done.is_set():
            _, got = svc.query(q)
            n += 1
            returned = set(got[got != serving.PAD_ID].tolist())
            assert returned and (returned <= set_a or returned <= set_b), \
                f"mixed-version result: {sorted(returned)}"
    finally:
        t.join()
    assert n > 0


def test_background_rebuild_never_blocks_or_mixes(corpus):
    """A full rebuild on a background thread: queries keep answering from
    the old snapshot until the swap, then from the new one — and the
    final version serves every published id."""
    x, q, ids, _ = corpus
    builder = serving.IndexBuilder("ivf-flat", x.shape[1],
                                   ivf=serving.IVFConfig(nlist=16, nprobe=16))
    svc = serving.RetrievalService(builder, _store_for(x, ids), k=10,
                                   k_prime=64, compact_threshold=10 ** 9,
                                   auto_compact=False)
    svc.swap(builder.build(ids[:1000], x[:1000]))
    svc.publish(ids[1000:], x[1000:])
    t = svc.rebuild(mode="full", block=False)
    assert t is not None
    versions = set()
    while t.is_alive():
        versions.add(svc.version)
        _, got = svc.query(q)
        assert (got != serving.PAD_ID).all()
    svc.wait_for_build()
    assert svc.version == 2 and svc.n_pending == 0
    assert svc.ntotal == x.shape[0]
    versions.add(svc.version)
    assert versions <= {1, 2}
    # a second concurrent rebuild request while one is in flight is a no-op
    t1 = svc.rebuild(mode="full", block=False)
    t2 = svc.rebuild(mode="full", block=False)
    assert t1 is not None and t2 is None
    svc.wait_for_build()


def test_swap_preserves_warm_executables():
    """Post-swap queries must hit the warm jitted executables: a rebuild
    over identical data lands in the same (kind, cap bucket), and the
    swap + query recompile NOTHING (still exactly one executable per
    bucket — the PR-3 compile-hygiene contract, now across versions)."""
    from repro import training
    x = make_corpus(4000, d=16, rank=4, seed=5)
    ids = np.arange(1, 4001)
    q = make_corpus(8, d=16, rank=4, seed=6)
    for kind in ("ivf-flat", "ivf-pq"):
        builder = serving.IndexBuilder(
            kind, 16, ivf=serving.IVFConfig(nlist=8, nprobe=4),
            pq=serving.PQConfig(n_subvec=4, n_codes=16), seed=3)
        svc = serving.RetrievalService(builder, _store_for(x, ids), k=10,
                                       k_prime=16, auto_compact=False)
        svc.swap(builder.build(ids, x))
        svc.query(q)                             # warm the executables
        cap0 = svc.snapshot().cap
        fresh = builder.build(ids, x)            # same data, same seed
        assert fresh.cap == cap0, "rebuild changed the cap bucket"
        with training.CompileCounter() as cc:
            svc.swap(fresh)
            _, got = svc.query(q)
        assert cc.count == 0, \
            f"{kind}: post-swap query recompiled {cc.count}x"
        assert (got != serving.PAD_ID).all()
        assert svc.version == 2


def test_device_layout_rejects_int32_overflow_ids():
    """Device lists store ids as int32; ids that would silently wrap (or
    collide with PAD_ID) must be rejected, not truncated."""
    x = make_corpus(64, d=16, rank=4, seed=12)
    idx = serving.make_index("ivf-flat", 16,
                             ivf=serving.IVFConfig(nlist=4, nprobe=4))
    idx.train(jax.random.PRNGKey(0), jnp.asarray(x))
    with pytest.raises(ValueError, match="2\\*\\*31"):
        idx.add(np.array([2 ** 31 + 5]), x[:1])
    with pytest.raises(ValueError, match="2\\*\\*31"):
        idx.add(np.array([-3]), x[:1])
    idx.add(np.arange(1, 65), x)                 # in-range ids still fine
    assert idx.ntotal == 64
