"""SpeedyFeed train -> publish -> serve on a TPU, at PROD's widths.

    python chip_smoke.py              # one chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: data-parallel training
                                      # and the sharded index, each against
                                      # its one-device counterpart

One process drives the chip(s).  It exits 1 before any work when JAX
finds no TPU, and it prints its result as the last line of stdout:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check raises, so nothing after it runs and no result prints.

One chip:
  kernels    the Mosaic bus-attention (fwd + grad) and LUT-scan kernels
             against kernels/ref.py on a small input.
  (b) train  ``launch/train.py::train_speedyfeed`` on ``prod_1chip`` (12
             layers x 768 x 12 heads, K=3 x S=32, L=100, the 1,204,224-row
             cache; random weights from SEED) for a few steps fed by the
             DynamicBatcher.  Checks: finite losses, moved parameters, no
             compile on a warm bucket, Pallas attention with the Mosaic
             kernel (``tpu_custom_call``) in the compiled step.
  (c) serve  the trained parameters in a full-width ``Recommender``:
             encode the corpus, build IVF-PQ, publish a fresh block and
             rebuild, answer requests through the ``RequestScheduler``,
             and check recall@10 against the exact-MIPS oracle.
Four chips (``--chips 4``), at prod_1chip's widths and global batch with
the depth cut to 2 layers:
  mesh      ``Trainer(mesh=data=4)`` against device 0 alone over one
            deterministic batch stream: per-step losses agree within
            MESH_LOSS_RTOL.
  index     a ``ShardedIndexSnapshot`` search against the unsharded
            snapshot, id for id, for IVF-Flat and IVF-PQ.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

SEED = 0
TRAIN_STEPS = 8
TRAIN_NEWS, TRAIN_USERS = 4096, 2048   # synthetic click log for training
SERVE_NEWS = 16384                     # corpus the Recommender serves
N_REQUESTS, SERVE_BATCH, K = 32, 8, 10
# Half of the 64 cells: a barely trained encoder's embeddings sit in a
# narrow cone (mean cosine 0.96 to their mean direction at PROD width), so
# cells separate poorly; at 16 probes recall@10 ranged 0.75-0.99 over
# build seeds, at 32 it was 1.0 (2-layer full-width encoder, CPU).
SERVE_NPROBE = 32
KERNEL_BUS_TOL = 2e-2                  # bf16-pass matmuls vs HIGHEST
MESH_STEPS, MESH_LAYERS = 4, 2
MESH_LOSS_RTOL = 5e-3                  # f32 all-reduce order vs one device
INDEX_ROWS, INDEX_QUERIES = 32768, 16


def log(msg: str):
    print(msg, flush=True)


def check(cond, msg: str):
    if not cond:
        raise AssertionError(msg)


def tpu_devices(n_chips: int):
    """The TPU devices, or exit 1 naming what JAX found instead."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(1)
    if len(devs) < n_chips:
        print(f"chip_smoke: --chips {n_chips} but JAX found {len(devs)} "
              f"TPU device(s)", file=sys.stderr)
        sys.exit(1)
    log(f"[device] {devs[0].device_kind} x{len(devs)}")
    return devs


def _peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def _finite(xs) -> bool:
    import numpy as np
    return len(xs) > 0 and bool(np.all(np.isfinite(xs)))


# ---------------------------------------------------------------- one chip

def kernel_phase(cfg):
    """The main-path Mosaic kernels against kernels/ref.py on a small
    input at the PROD encoder's head shapes: BusLM attention forward and
    gradient through the ops custom VJP, and the masked IVF-PQ LUT scan.
    The references run at HIGHEST matmul precision; the bus kernel's f32
    dots may take single bf16 MXU passes, hence KERNEL_BUS_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.serving import pq_config_for

    M, K, S = 16, cfg.plm.n_segments, cfg.plm.seg_len
    H = cfg.plm.n_heads
    D = cfg.plm.d_model // H
    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    q = jax.random.normal(ks[0], (M, K, S, H, D))
    k = jax.random.normal(ks[1], (M, K, S + K, H, D))
    v = jax.random.normal(ks[2], (M, K, S + K, H, D))
    mask = jax.random.bernoulli(ks[3], 0.75, (M, K, S + K))
    mask = mask.at[:, :, 0].set(True).at[:, -1, :].set(False)
    g = jax.random.normal(ks[4], (M, K, S, H, D))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, mask) * g).sum()

    got = jax.jit(jax.value_and_grad(loss(ops.bus_attention),
                                     argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        exp = jax.jit(jax.value_and_grad(loss(ref.bus_attention),
                                         argnums=(0, 1, 2)))(q, k, v)
        o_exp = ref.bus_attention(q, k, v, mask)
    o_got = ops.bus_attention(q, k, v, mask)
    errs = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip((o_got,) + got[1], (o_exp,) + exp[1])]
    log(f"[kernels] bus attention (M={M}, K={K}, S={S}, H={H}, D={D}) "
        f"vs reference, max error / max |ref| for o, dq, dk, dv: "
        f"{['%.2e' % e for e in errs]}")
    check(max(errs) <= KERNEL_BUS_TOL, f"bus attention off the reference "
          f"by {max(errs):.2e}")

    pq = pq_config_for(cfg.plm.news_dim)      # as the Recommender serves
    B, n_sub, n_codes, N = 8, pq.n_subvec, pq.n_codes, 4096
    lut = jax.random.normal(ks[5], (B, n_sub, n_codes))
    codes = jax.random.randint(ks[6], (B, N, n_sub), 0, n_codes
                               ).astype(jnp.uint8)
    valid = jax.random.bernoulli(ks[7], 0.9, (B, N))
    got = np.asarray(ops.pq_lut_scores(lut, codes, valid, block_n=N))
    exp = np.asarray(ref.pq_lut_scores(lut, codes, valid))
    check(np.array_equal(np.isinf(got), np.isinf(exp)),
          "LUT scan masks other slots than the reference")
    fin = np.isfinite(exp)
    err = float(np.max(np.abs(got[fin] - exp[fin])))
    log(f"[kernels] masked LUT scan (B={B}, N={N}, M={n_sub}, K={n_codes}) "
        f"vs reference: max abs error {err:.2e}")
    check(err <= 1e-4, f"LUT scan off the reference by {err:.2e}")


def train_phase(cfg, device, *, steps=TRAIN_STEPS, loader_kw=None):
    """(b): a few Trainer.fit steps through the launcher; returns the
    trained parameters."""
    import jax
    import numpy as np
    from repro import core, data, obs, training
    from repro.kernels.ops import resolve_attn_impl
    from repro.launch.train import train_speedyfeed

    impl = resolve_attn_impl(cfg.plm.attn_impl)
    log(f"[train] attention impl: {impl}")
    check(impl == "pallas", f"attention resolved to {impl!r}, not pallas")
    loader_kw = loader_kw or dict(
        n_news=TRAIN_NEWS, n_users=TRAIN_USERS,
        token_budget=data.LoaderConfig.token_budget)
    obs.reset()
    t0 = time.perf_counter()
    res = train_speedyfeed(steps=steps, cfg=cfg, seed=SEED,
                           log_every=steps, loader_kw=loader_kw,
                           loader_threads=1)
    log(f"[train] {res.steps_done} steps in {time.perf_counter() - t0:.1f}s "
        f"(set-up included); buckets {res.bucket_steps}; first-step "
        f"compiles {res.compile_counts}; warm recompiles "
        f"{res.warm_compiles}")
    compile_s = obs.histogram("xla_compile_ms").sum / 1e3
    log(f"[train] compile seconds (all backend compiles): {compile_s:.1f}")
    log(f"[train] losses {res.losses}")
    check(res.steps_done == steps, f"ran {res.steps_done} of {steps} steps")
    check(len(res.losses) == steps and _finite(res.losses),
          f"non-finite or missing losses: {res.losses}")
    check(not res.warm_compiles,
          f"warm buckets compiled again: {res.warm_compiles}")
    single = [b for b, n in res.bucket_steps.items() if n < 2]
    check(not single, f"buckets {single} got no warm step")

    state = res.state
    res.state = None
    init = core.init_speedyfeed(jax.random.PRNGKey(SEED), cfg)
    moved = max(float(jax.numpy.max(jax.numpy.abs(a - b)))
                for a, b in zip(jax.tree.leaves(state.params),
                                jax.tree.leaves(init)))
    del init
    log(f"[train] max |param - init| = {moved:.3e}")
    check(moved > 0.0, "parameters did not move")

    # the step executable for the bucket the run used: Mosaic kernel in
    # it, and the warm step's time (set-up information, not a benchmark)
    bucket = max(res.bucket_steps, key=res.bucket_steps.get)
    batch = jax.device_put(data.synth_centralized_batch(
        m_cap=cfg.merged_cap, n_segments=cfg.plm.n_segments, seg_len=bucket,
        b_cap=cfg.batch_users, hist_len=cfg.hist_len, vocab=cfg.plm.vocab,
        seed=SEED), device)
    trainer = training.get_trainer("speedyfeed", cfg=cfg)
    text = trainer.compiled_text(state, batch)
    n_kernels = text.count("tpu_custom_call")
    log(f"[train] compiled step (bucket {bucket}): {n_kernels} "
        f"tpu_custom_call sites")
    check(n_kernels > 0, "no Mosaic kernel in the compiled train step")
    state, m = trainer.step(state, batch)
    jax.block_until_ready(m["loss"])
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        state, m = trainer.step(state, batch)
        jax.block_until_ready(m["loss"])
        times.append(time.perf_counter() - t0)
    check(_finite([float(m["loss"])]), "non-finite loss on the warm step")
    log(f"[train] warm step seconds {['%.3f' % t for t in times]}")
    log(f"[train] peak_bytes_in_use {_peak_bytes(device)}")
    return state.params


def serve_phase(cfg, params, device, *, n_news=SERVE_NEWS):
    """(c): full-width Recommender over a synthetic corpus."""
    import numpy as np
    from repro import obs
    from repro.launch.serve import (RECALL_THRESHOLD, Recommender,
                                    measure_recall, micro_batch_loop)
    from repro.launch.train import make_loader

    obs.reset()
    t0 = time.perf_counter()
    _, clicks, store, _ = make_loader(cfg, n_news=n_news,
                                      n_users=4 * N_REQUESTS, seed=SEED + 1)
    log(f"[serve] corpus of {store.tokens.shape[0] - 1} news built in "
        f"{time.perf_counter() - t0:.1f}s")
    rec = Recommender(cfg, params, store, k=K, index_kind="ivf-pq",
                      nprobe=SERVE_NPROBE, k_prime=64)
    t0 = time.perf_counter()
    svc = rec.build_index()
    log(f"[serve] encoded + built ivf-pq: ntotal {svc.ntotal}, "
        f"v{svc.version} in {time.perf_counter() - t0:.1f}s")
    check(svc.ntotal == n_news, f"index holds {svc.ntotal} of {n_news}")

    n0 = svc.store.host.shape[0]
    rng = np.random.default_rng(SEED)
    fresh = (svc.store.host[1:33]
             + 0.01 * rng.normal(size=(32, svc.store.dim))).astype(np.float32)
    v0 = svc.version
    rec.publish(np.arange(n0, n0 + 32), fresh)
    svc.rebuild(mode="full", block=True)
    log(f"[serve] published 32 fresh news and rebuilt: ntotal "
        f"{svc.ntotal}, v{svc.version}")
    check(svc.version > v0 and svc.ntotal == n_news + 32,
          "the rebuild did not absorb the fresh block")

    reqs = [h for h in clicks.histories if len(h)][:N_REQUESTS]
    t0 = time.perf_counter()
    results, n_batches = micro_batch_loop(rec, reqs, max_batch=SERVE_BATCH)
    e2e = obs.histogram("query_latency_ms", phase="e2e")
    log(f"[serve] {len(results)} requests in {n_batches} batches, "
        f"{time.perf_counter() - t0:.2f}s (compiles included); e2e p50 "
        f"{e2e.percentile(50):.1f} ms")
    check(len(results) == len(reqs) == N_REQUESTS,
          f"{len(results)} answers to {len(reqs)} requests")
    for r in results:
        r = np.asarray(r)
        check(r.shape == (K,) and np.all(r > 0) and np.all(r < n0 + 32),
              f"bad top-{K} row {r}")
    recall = measure_recall(rec, reqs, k=K, probe=16)
    log(f"[serve] recall@{K} vs exact MIPS: {recall:.3f} "
        f"(threshold {RECALL_THRESHOLD})")
    check(recall >= RECALL_THRESHOLD, f"recall@{K} {recall:.3f} below "
          f"{RECALL_THRESHOLD}")
    log(f"[serve] peak_bytes_in_use {_peak_bytes(device)}")


# ------------------------------------------------------------- four chips

def mesh_phase(cfg, devices):
    import numpy as np
    from repro import data
    from repro.launch.mesh import make_mesh_for
    from repro.launch.train import train_speedyfeed

    def losses(mesh):
        # one loader thread: both runs see the same batches in one order
        return train_speedyfeed(
            steps=MESH_STEPS, cfg=cfg, seed=SEED, log_every=0, mesh=mesh,
            loader_threads=1, loader_kw=dict(
                n_news=TRAIN_NEWS, n_users=TRAIN_USERS,
                token_budget=data.LoaderConfig.token_budget)).losses

    one = losses(None)
    log(f"[mesh] device 0 alone: losses {one}")
    gc.collect()              # device 0's whole-cache state goes first
    many = losses(make_mesh_for(len(devices)))
    log(f"[mesh] data={len(devices)}: losses {many}")
    check(_finite(one) and _finite(many) and len(one) == len(many)
          == MESH_STEPS, "missing or non-finite losses")
    gap = np.max(np.abs(np.asarray(many) - np.asarray(one))
                 / np.abs(np.asarray(one)))
    log(f"[mesh] max relative loss gap {gap:.3e} (tolerance "
        f"{MESH_LOSS_RTOL})")
    check(gap <= MESH_LOSS_RTOL, f"mesh losses differ by {gap:.3e}")


def index_phase(dim, devices):
    import numpy as np
    from repro import serving

    rng = np.random.default_rng(SEED)
    basis = rng.normal(size=(64, dim))          # low-rank, like encodings
    x = (rng.normal(size=(INDEX_ROWS, 64)) @ basis
         + 0.1 * rng.normal(size=(INDEX_ROWS, dim))).astype(np.float32)
    q = (rng.normal(size=(INDEX_QUERIES, 64)) @ basis).astype(np.float32)
    ids = np.arange(1, INDEX_ROWS + 1)
    ivf = serving.IVFConfig(nlist=64, nprobe=16)
    for kind in ("ivf-flat", "ivf-pq"):
        plain = serving.IndexBuilder(kind, dim, ivf=ivf, seed=SEED)
        shard = serving.IndexBuilder(kind, dim, ivf=ivf, seed=SEED,
                                     devices=list(devices))
        snap, ssnap = plain.build(ids, x), shard.build(ids, x)
        check(isinstance(ssnap, serving.ShardedIndexSnapshot),
              "the sharded builder froze an unsharded snapshot")
        _, i_ref = snap.search(q, K)
        _, i_got = ssnap.search(q, K)
        i_got, i_ref = np.asarray(i_got), np.asarray(i_ref)
        same = i_got == i_ref
        log(f"[index] {kind}: {INDEX_ROWS} rows x {dim} over "
            f"{ssnap.n_shards} shards; top-{K} ids equal unsharded at "
            f"{int(same.sum())}/{same.size} positions")
        for b, j in zip(*np.nonzero(~same)):      # exact scores of both ids
            log(f"[index]   query {b} rank {j}: sharded id {i_got[b, j]} "
                f"({q[b] @ x[i_got[b, j] - 1]:.6f}), unsharded id "
                f"{i_ref[b, j]} ({q[b] @ x[i_ref[b, j] - 1]:.6f})")
        check(same.all(), f"{kind}: sharded top-{K} differs from unsharded")


# -------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    devices = tpu_devices(args.chips)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import configure_compile_cache
    from repro.launch.train import speedyfeed_config
    log(f"[cache] compilation cache: {configure_compile_cache()}")

    cfg = speedyfeed_config("prod_1chip")
    t0 = time.perf_counter()
    if args.chips == 1:
        kernel_phase(cfg)
        params = train_phase(cfg, devices[0])
        gc.collect()          # the Trainer's optimizer state and cache go
        serve_phase(cfg, params, devices[0])
    else:
        cut = dataclasses.replace(
            cfg, plm=dataclasses.replace(cfg.plm, n_layers=MESH_LAYERS))
        mesh_phase(cut, devices[:args.chips])
        index_phase(cfg.plm.news_dim, devices[:args.chips])
    log(f"[done] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
