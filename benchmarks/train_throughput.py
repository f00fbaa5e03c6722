"""Old loop vs unified training runtime, and XLA vs Pallas attention:
steps/sec + host-stall fraction + a fwd+bwd attention microbenchmark.

  PYTHONPATH=src python benchmarks/train_throughput.py [--epochs 2] \
      [--repeats 2] [--attn-impl xla pallas] \
      [--out benchmarks/BENCH_train.json]

Legacy loop (pre-Trainer ``launch/train.py``, replicated verbatim here):
pads every bucketed batch back to the global max seg length (defeating the
loader's bucketing), converts batches synchronously on the step thread, and
drains metrics with ``float(...)`` every step (blocking dispatch). No
donation.

Trainer: one warm donated executable for every bucket (batches padded to
the global max too, but the encode set runs each chunk at the length its
news need), async device prefetch, lazy metrics drain. With
``--attn-impl`` taking several values, the Trainer side runs once per
attention implementation over the SAME batch stream (same
loader epochs, same seeds), writing per-impl entries under
``by_attn_impl`` — the xla-vs-pallas comparison of the trainable fused
kernels in the real training loop. ``attention_microbench`` additionally
times one jitted fwd+bwd (value_and_grad) of each attention kernel pair in
isolation.

Methodology: every side is warmed on synthetic batches (compilation is
excluded; per-bucket compile counts are reported separately), then trains
over the *identical* batch stream whose exact step count is measured up
front — so the comparison is per unit of identical work, not per window of
whichever bucket mix happened to stream by. Best of ``--repeats`` runs per
side (shared-box noise suppression).

CPU-scale note: on this container Pallas runs in interpret mode, so the
absolute pallas numbers measure the correctness path, not Mosaic; the
per-impl entries exist so the same command reports the real speedup on
TPU, and CI asserts the pallas loop's compile hygiene + finite loss.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import data, optim, training
from repro.configs.speedyfeed_arch import make_sf_train_step
from repro.core import speedyfeed_state
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.train import make_loader, small_speedyfeed_config


def _synth_batch(cfg, seg_len, seed=0):
    return data.synth_centralized_batch(
        m_cap=cfg.merged_cap, n_segments=cfg.plm.n_segments, seg_len=seg_len,
        b_cap=cfg.batch_users, hist_len=cfg.hist_len, vocab=cfg.plm.vocab,
        seed=seed)


def count_epoch_steps(make_batcher, epochs):
    """Batches per epoch for the deterministic loader streams (and the
    bucket mix), so both loops can be timed over identical work."""
    counts, mix = [], {}
    for e in range(epochs):
        b = make_batcher(e)
        n = 0
        try:
            while True:
                item = b.get(timeout=30.0)
                if item is data.EPOCH_END:
                    break
                if item is None:
                    raise RuntimeError("loader stalled while counting")
                n += 1
                k = item["_bucket"]
                mix[k] = mix.get(k, 0) + 1
        finally:
            b.stop()
        counts.append(n)
    return counts, mix


def legacy_loop(cfg, make_batcher, *, steps, epochs, repeats):
    """Pre-refactor train loop: pad-to-max, sync convert, per-step drain."""
    key = jax.random.PRNGKey(0)
    params0, cache0 = speedyfeed_state(cfg, key)
    opt0 = optim.adam_init(params0)
    step_fn = jax.jit(make_sf_train_step(cfg))
    warm = {k: jnp.asarray(v)
            for k, v in _synth_batch(cfg, cfg.plm.seg_len).items()}
    # compile + warm outside the measured stream; outputs are DISCARDED so
    # the random-token step never pollutes the measured params/opt/cache
    out = step_fn(params0, opt0, cache0, jnp.int32(0), key, warm)
    jax.block_until_ready(out[-1]["loss"])

    walls, losses, stalls = [], [], []
    for rep in range(repeats):
        params, opt, cache = params0, opt0, cache0   # fresh state per run
        step, epoch, stall = 0, 0, 0.0
        t0 = time.perf_counter()     # include loader startup (the Trainer
        batcher = make_batcher(0)    # side times prefetcher startup too)
        try:
            while step < steps:
                tw = time.perf_counter()
                batch = batcher.get(timeout=30.0)
                stall += time.perf_counter() - tw
                if batch is data.EPOCH_END:
                    batcher.stop()
                    epoch += 1
                    batcher = make_batcher(epoch % epochs)
                    continue
                if batch is None:
                    raise RuntimeError(f"loader stalled at step {step}")
                batch.pop("_stats", None)
                batch.pop("_bucket", None)
                batch = data.pad_segments(batch, cfg.plm.seg_len)
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                params, opt, cache, metrics = step_fn(
                    params, opt, cache, jnp.int32(step),
                    jax.random.fold_in(key, step), batch)
                losses.append(float(metrics["loss"]))  # blocking, every step
                step += 1
        finally:
            batcher.stop()
        wall = time.perf_counter() - t0
        walls.append(wall)
        stalls.append(stall / wall)
    i = int(np.argmin(walls))
    return {"steps_per_sec": round(steps / walls[i], 3),
            "wall_s": round(walls[i], 3),
            "host_stall_fraction": round(stalls[i], 4),
            "mean_loss_last10": round(float(np.mean(losses[-10:])), 4)}


def trainer_loop(cfg, make_batcher, lcfg, *, steps, repeats, mesh=None):
    trainer = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh)
    # warm every bucket executable on synthetic batches (compile excluded);
    # on a mesh the first step builds the sharded jit, and the uncommitted
    # numpy batch is placed by its in_shardings
    state = trainer.init_state(0)
    for b in lcfg.buckets:
        wb = trainer.prepare_batch(_synth_batch(cfg, b))
        if mesh is None:
            wb = jax.device_put(wb)
        state, m = trainer.step(state, wb, bucket=b)
    jax.block_until_ready(m["loss"])
    compiles_warm = dict(trainer.compile_counts)

    # a live CompileCounter across the measured fits (not Trainer's
    # first-step-per-bucket accounting, which by construction sees nothing
    # after warmup) makes the recompile-hygiene invariant falsifiable
    runs = []
    with training.CompileCounter() as cc:
        for _ in range(repeats):
            # pre-build the state so fit's wall clock starts at the same
            # place as the legacy timer (state ready, input pipeline not)
            st = trainer.init_state(0)
            runs.append(trainer.fit(make_batcher, steps=steps, state=st,
                                    log_every=0))
    i = int(np.argmin([r.wall_seconds for r in runs]))
    res = runs[i]
    return {"steps_per_sec": round(res.steps_done / res.wall_seconds, 3),
            "wall_s": round(res.wall_seconds, 3),
            "host_stall_fraction": round(res.host_stall_fraction, 4),
            "compile_counts": {str(k): v for k, v in compiles_warm.items()},
            "recompiles_measured": cc.count,
            "bucket_steps": {str(k): v
                             for k, v in res.bucket_steps.items()},
            "mean_loss_last10": round(float(np.mean(res.losses[-10:])), 4)}


def mesh_sweep(cfg, make_batcher, lcfg, *, steps, repeats, specs):
    """Trainer throughput per mesh size over the identical batch stream.

    ``specs`` are launcher-style ``data=N`` strings; ``data=1`` runs the
    exact mesh-less path (the scaling baseline).  On CPU the devices are
    XLA-forced host slices of one physical machine, so the entries
    document the SCALING SHAPE (and the sharded path's compile hygiene),
    not absolute speed — N forced devices split the same cores N ways.
    """
    from repro.launch.mesh import parse_mesh_arg
    out = {}
    for spec in specs:
        mesh = parse_mesh_arg(spec)
        r = trainer_loop(cfg, make_batcher, lcfg, steps=steps,
                         repeats=repeats, mesh=mesh)
        r["mesh_devices"] = 1 if mesh is None else int(mesh.devices.size)
        out[spec] = r
    return out


def obs_overhead_guard(cfg, make_batcher, lcfg, *, steps, repeats,
                       max_pct=2.0):
    """Instrumentation-overhead guard: the identical Trainer stream timed
    with the obs layer enabled vs disabled, best-of-``repeats`` (>= 2)
    PER SIDE so neither side gets more bites at the noise.  The telemetry
    tentpole's budget is <= ``max_pct`` steps/s; negative overhead is
    shared-box noise (the real per-op cost is ~1µs against ~100ms
    steps)."""
    from repro import obs
    reps = max(repeats, 2)
    obs.set_enabled(True)
    on = trainer_loop(cfg, make_batcher, lcfg, steps=steps, repeats=reps)
    obs.set_enabled(False)
    try:
        off = trainer_loop(cfg, make_batcher, lcfg, steps=steps,
                           repeats=reps)
    finally:
        obs.set_enabled(True)
    overhead = 100.0 * (1.0 - on["steps_per_sec"] / off["steps_per_sec"])
    return {"enabled_steps_per_sec": on["steps_per_sec"],
            "disabled_steps_per_sec": off["steps_per_sec"],
            "overhead_pct": round(overhead, 3),
            "max_pct": max_pct,
            "ok": bool(overhead <= max_pct)}


def attention_microbench(repeats=3, iters=5, seed=0):
    """Jitted fwd+bwd (value_and_grad) per attention kernel pair on fixed
    inputs, best-of-``repeats`` over ``iters``-call windows. Flash runs the
    LM-family shape, bus the BusLM encode-set shape."""
    from repro.kernels import ops, ref

    def time_call(fn, *args):
        grad = jax.jit(jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                                argnums=(0, 1, 2)))
        jax.block_until_ready(grad(*args))          # compile + warm
        best = np.inf
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = grad(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / iters)
        return round(best * 1e3, 3)                 # ms per fwd+bwd call

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    B, S, H, D = 4, 128, 4, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    flash = {
        "shape": {"B": B, "S": S, "H": H, "D": D, "causal": True},
        "xla_ms": time_call(
            lambda q, k, v: ref.flash_attention(q, k, v, causal=True),
            q, k, v),
        "pallas_ms": time_call(
            lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                block_q=64, block_k=64),
            q, k, v),
    }
    M, K, S, H, D = 96, 3, 16, 4, 16
    Sk = S + K
    qb = jax.random.normal(ks[0], (M, K, S, H, D))
    kb = jax.random.normal(ks[1], (M, K, Sk, H, D))
    vb = jax.random.normal(ks[2], (M, K, Sk, H, D))
    mask = jax.random.bernoulli(ks[3], 0.85, (M, K, Sk)).at[:, :, 0].set(True)
    bus = {
        "shape": {"M": M, "K": K, "S": S, "H": H, "D": D},
        "xla_ms": time_call(
            lambda q, k, v: ref.bus_attention(q, k, v, mask), qb, kb, vb),
        "pallas_ms": time_call(
            lambda q, k, v: ops.bus_attention(q, k, v, mask), qb, kb, vb),
    }
    return {"flash": flash, "bus": bus}


def run(epochs=2, repeats=2, seed=0, out=None, seg_len=32,
        attn_impls=("xla",), micro=True, obs_overhead=False,
        obs_overhead_pct=2.0, mesh=(), mesh_merge=False):
    # seg_len=32 -> the 4-bucket set (8, 16, 24, 32): the legacy loop pads
    # every sub-max bucket back to 32, the Trainer runs them at length.
    # The workload is the bucketed regime the paper targets (MIND-like:
    # overwhelmingly headline news, short histories), so a meaningful share
    # of batches land below the top bucket.
    cfgs = {impl: small_speedyfeed_config(seg_len=seg_len, attn_impl=impl)
            for impl in attn_impls}
    first = attn_impls[0]
    corpus, log, store, lcfg = make_loader(
        cfgs[first], seed=seed, corpus_kw={"short_frac": 0.9},
        log_kw={"mean_clicks": 5.0})

    def make_batcher(epoch):
        return data.DynamicBatcher(log, store, lcfg, n_threads=2,
                                   seed=seed + 1_000_003 * epoch).start()

    epoch_steps, bucket_mix = count_epoch_steps(make_batcher, epochs)
    steps = sum(epoch_steps)
    by_mesh = mesh_sweep(cfgs[first], make_batcher, lcfg, steps=steps,
                         repeats=repeats, specs=mesh) if mesh else None
    if mesh_merge:
        # record the mesh scaling entries into an EXISTING result file
        # without re-running the (expensive) legacy/impl/microbench
        # sections — the sweep replays the same deterministic stream, so
        # its entries are comparable to the file's trainer numbers
        if not (out and os.path.exists(out)):
            raise SystemExit("--mesh-merge needs an existing --out JSON")
        with open(out) as f:
            result = json.load(f)
        result["by_mesh"] = by_mesh or {}
        result.setdefault("config", {})["mesh"] = {
            "specs": list(mesh), "epochs": epochs, "steps": steps,
            "repeats": repeats, "backend": jax.default_backend(),
            "visible_devices": jax.device_count()}
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        return result
    # every Trainer side (and the legacy loop) replays this same stream:
    # per-impl numbers are per unit of identical work
    by_impl = {impl: trainer_loop(cfgs[impl], make_batcher, lcfg,
                                  steps=steps, repeats=repeats)
               for impl in attn_impls}
    new = by_impl[first]
    result = {
        "config": {"n_layers": cfgs[first].plm.n_layers,
                   "d_model": cfgs[first].plm.d_model,
                   "seg_len": cfgs[first].plm.seg_len,
                   "buckets": list(lcfg.buckets),
                   "merged_cap": cfgs[first].merged_cap, "epochs": epochs,
                   "steps": steps, "repeats": repeats,
                   "attn_impls": list(attn_impls),
                   "stream_bucket_mix": {str(k): v for k, v
                                         in sorted(bucket_mix.items())},
                   "backend": jax.default_backend()},
        "trainer": new,
        "by_attn_impl": by_impl,
    }
    if by_mesh:
        result["by_mesh"] = by_mesh
        result["config"]["mesh"] = {
            "specs": list(mesh), "visible_devices": jax.device_count()}
    if "xla" in cfgs:
        legacy = legacy_loop(cfgs["xla"], make_batcher, steps=steps,
                             epochs=epochs, repeats=repeats)
        result["legacy_loop"] = legacy
        result["speedup"] = round(
            by_impl["xla"]["steps_per_sec"] / legacy["steps_per_sec"], 3)
    if obs_overhead:
        result["obs_overhead"] = obs_overhead_guard(
            cfgs[first], make_batcher, lcfg, steps=steps,
            repeats=repeats, max_pct=obs_overhead_pct)
    if micro:
        result["attention_microbench"] = attention_microbench(
            repeats=max(repeats, 2), seed=seed)
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    return result


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seg-len", type=int, default=32)
    ap.add_argument("--attn-impl", nargs="+", default=["xla"],
                    choices=["xla", "pallas"],
                    help="attention impls to run the Trainer side with "
                         "(each over the identical batch stream)")
    ap.add_argument("--no-micro", action="store_true",
                    help="skip the fwd+bwd attention microbenchmark")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="re-time the Trainer stream with the obs layer "
                         "disabled and fail if instrumentation costs more "
                         "than --obs-overhead-pct steps/s")
    ap.add_argument("--obs-overhead-pct", type=float, default=2.0)
    ap.add_argument("--mesh", nargs="+", default=[], metavar="data=N",
                    help="run the Trainer side on each N-way data mesh "
                         "(data=1 = the exact mesh-less baseline); on CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first — entries document scaling shape, "
                         "not absolute speed")
    ap.add_argument("--mesh-merge", action="store_true",
                    help="merge the --mesh sweep into the existing --out "
                         "JSON instead of re-running every section")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "BENCH_train.json"))
    args = ap.parse_args()
    result = run(epochs=args.epochs, repeats=args.repeats, seed=args.seed,
                 out=args.out, seg_len=args.seg_len,
                 attn_impls=tuple(dict.fromkeys(args.attn_impl)),
                 micro=not args.no_micro, obs_overhead=args.obs_overhead,
                 obs_overhead_pct=args.obs_overhead_pct,
                 mesh=tuple(dict.fromkeys(args.mesh)),
                 mesh_merge=args.mesh_merge)
    for spec, r in result.get("by_mesh", {}).items():
        print(f"train_throughput,mesh[{spec}]_steps_per_sec,"
              f"{r['steps_per_sec']}")
    if args.mesh_merge:
        return
    print(json.dumps(result, indent=2))
    if "legacy_loop" in result:
        print(f"\ntrain_throughput,legacy_steps_per_sec,"
              f"{result['legacy_loop']['steps_per_sec']}")
        print(f"train_throughput,speedup,{result['speedup']}")
    for impl, r in result["by_attn_impl"].items():
        print(f"train_throughput,{impl}_steps_per_sec,{r['steps_per_sec']}")
    oh = result.get("obs_overhead")
    if oh:
        print(f"train_throughput,obs_overhead_pct,{oh['overhead_pct']}")
        if not oh["ok"]:     # guard fires AFTER the JSON is written
            sys.exit(f"obs overhead {oh['overhead_pct']}% exceeds "
                     f"{oh['max_pct']}% budget")


if __name__ == "__main__":
    main()
