"""End-to-end training launcher with fault tolerance.

  python -m repro.launch.train --arch speedyfeed --steps 200 \
      --ckpt-dir /tmp/ckpt --ckpt-every 50

The speedyfeed path runs through the unified training runtime
(``repro.training``): registry-built Trainer with one warm donated
executable per seg-length bucket (batches run at their bucket length —
nothing is padded back to the global max), async host->device prefetch fed
by the DynamicBatcher with explicit end-of-epoch turnover, lazy metrics
drain, and TrainState checkpoints that still restore pre-Trainer
``{params, opt, cache:{emb, age}}`` snapshots.

Features exercised here (and tested in tests/test_system.py +
tests/test_training.py):
  * SpeedyFeed Algorithm-1 loop on synthetic Microsoft-News-like data with
    the dynamic-batching loader (background threads, work stealing),
  * checkpoint/restart: atomic snapshots incl. the news-embedding cache;
    on boot the trainer resumes from the latest checkpoint,
  * straggler monitor hooks (per-step timing EMA),
  * any other arch trains on synthetic batches at reduced scale (CPU).

``--config`` picks the speedyfeed config by name (``speedyfeed_config``):
``small`` for CPU runs, ``prod_1chip`` for PROD's widths on one chip.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro import configs, core, data, obs, training
from repro.launch.compile_cache import configure_compile_cache
from repro.resilience import FaultPlan, faults, fit_supervised
from repro.training import TrainResult  # re-export (legacy import path)


def small_speedyfeed_config(**over):
    """2 layers x 64 wide: the config of the CPU tests and CI."""
    base = dict(vocab=5000, n_layers=2, d_model=64, n_heads=4, d_ff=128,
                n_segments=3, seg_len=16, news_dim=32, n_news=2001,
                gamma=20, beta=2e-2, encode_budget=96, batch_users=16,
                hist_len=30, merged_cap=256, n_neg=4)
    base.update(over)
    return core.make_config(**base)


CONFIG_NAMES = ("small", "prod_1chip", "prod")


def speedyfeed_config(name: str):
    """A SpeedyFeed config by name: ``small`` (CPU tests and CI),
    ``prod_1chip`` (PROD's widths at one chip's batch share) or ``prod``
    (the paper's production config, configs/speedyfeed_arch.py)."""
    if name == "small":
        return small_speedyfeed_config()
    from repro.configs import speedyfeed_arch
    if name == "prod_1chip":
        return speedyfeed_arch.PROD_1CHIP
    if name == "prod":
        return speedyfeed_arch.PROD
    raise ValueError(f"unknown config {name!r}; have {CONFIG_NAMES}")


def make_loader(cfg, *, n_news=2000, n_users=400, seed=0, buckets=None,
                token_budget=4000, corpus_kw=None, log_kw=None):
    rng = np.random.default_rng(seed)
    corpus = data.make_corpus(rng, n_news=n_news, **(corpus_kw or {}))
    log = data.make_click_log(rng, corpus, n_users=n_users,
                              max_hist=cfg.hist_len, **(log_kw or {}))
    stats = data.build_corpus_stats(
        [corpus.text(i) for i in range(corpus.n_news)])
    lcfg = data.LoaderConfig(
        vocab=cfg.plm.vocab, n_segments=cfg.plm.n_segments,
        seg_len=cfg.plm.seg_len,
        buckets=buckets or data.default_buckets(cfg.plm.seg_len),
        token_budget=token_budget, b_cap=cfg.batch_users, m_cap=cfg.merged_cap,
        hist_len=cfg.hist_len)
    store = data.NewsStore(corpus, stats, lcfg)
    return corpus, log, store, lcfg


def train_speedyfeed(*, steps: int, ckpt_dir: str | None = None,
                     ckpt_every: int = 50, seed: int = 0, cfg=None,
                     fail_at: int | None = None, log_every: int = 20,
                     async_ckpt: bool = True, prefetch_depth: int = 2,
                     mesh=None, max_restarts: int = 0,
                     backoff_s: float = 0.05, loader_kw=None,
                     loader_threads: int = 2) -> TrainResult:
    """The end-to-end driver. ``fail_at`` injects a crash (restart tests).
    ``mesh`` runs the sharded Trainer path (see docs/sharding.md).
    ``loader_kw`` goes to ``make_loader`` (corpus size, buckets, token
    budget); ``loader_threads=1`` makes the batch order deterministic.

    ``max_restarts > 0`` runs the loop under ``fit_supervised``: a
    transient crash (injected fault, lost batch, non-finite-loss bailout)
    restarts from the latest valid checkpoint with backoff, up to
    ``max_restarts`` times (docs/resilience.md)."""
    cfg = cfg or small_speedyfeed_config()
    corpus, log, store, lcfg = make_loader(cfg, seed=seed,
                                           **(loader_kw or {}))
    trainer = training.get_trainer("speedyfeed", cfg=cfg, mesh=mesh)

    def make_batcher(epoch: int):
        return data.DynamicBatcher(log, store, lcfg, n_threads=loader_threads,
                                   seed=seed + 1_000_003 * epoch).start()

    fit_kw = dict(seed=seed, ckpt_every=ckpt_every, async_ckpt=async_ckpt,
                  log_every=log_every, fail_at=fail_at,
                  prefetch_depth=prefetch_depth)
    if max_restarts > 0:
        return fit_supervised(trainer, make_batcher, steps=steps,
                              ckpt_dir=ckpt_dir, max_restarts=max_restarts,
                              backoff_s=backoff_s, **fit_kw)
    return trainer.fit(make_batcher, steps=steps, ckpt_dir=ckpt_dir,
                       **fit_kw)


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="speedyfeed")
    ap.add_argument("--config", default="small", choices=CONFIG_NAMES,
                    help="speedyfeed config: small (CPU), prod_1chip (PROD "
                         "widths at one chip's batch share), prod")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None,
                    help="append obs-registry JSONL snapshots here "
                         "(periodic + one final)")
    ap.add_argument("--metrics-every", type=float, default=10.0,
                    help="periodic snapshot cadence, seconds")
    ap.add_argument("--mesh", default=None, metavar="data=N",
                    help="train on an N-way data mesh (data=1 / omitted = "
                         "the exact single-device path); on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="supervise the run: restart from the latest valid "
                         "checkpoint up to N times on transient failures "
                         "(docs/resilience.md)")
    ap.add_argument("--chaos-crash-at", type=int, default=None, metavar="STEP",
                    help="fault injection: crash the step loop ONCE at STEP "
                         "(fires through repro.resilience.faults, so the "
                         "restarted attempt runs through); pair with "
                         "--max-restarts to smoke-test auto-resume")
    args = ap.parse_args()
    from repro.launch.mesh import parse_mesh_arg
    mesh = parse_mesh_arg(args.mesh)
    obs.reset()      # this run's registry export is exactly this run
    if args.metrics_out:
        obs.configure_reporter(path=args.metrics_out,
                               every_s=args.metrics_every)
    if args.chaos_crash_at is not None:
        faults.arm(FaultPlan().fail("train.step", step=[args.chaos_crash_at]))
    try:
        if args.arch == "speedyfeed":
            res = train_speedyfeed(steps=args.steps, ckpt_dir=args.ckpt_dir,
                                   ckpt_every=args.ckpt_every, seed=args.seed,
                                   cfg=speedyfeed_config(args.config),
                                   mesh=mesh, max_restarts=args.max_restarts)
            loss = (f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}; "
                    if res.losses else "no new steps (already trained); ")
            print(f"done: {res.steps_done} steps in {res.wall_seconds:.1f}s; "
                  + loss
                  + f"buckets {res.bucket_steps} compiles "
                  f"{res.compile_counts} warm recompiles "
                  f"{res.warm_compiles}; "
                  f"host stall {res.host_stall_fraction:.1%}"
                  + (f" (restarts {res.restarts})" if res.restarts else "")
                  + (f" (resumed from {res.resumed_from})" if res.resumed_from
                     else ""))
        else:
            arch = configs.get_arch(args.arch)
            print(f"running reduced-config smoke train for {args.arch}")
            print(arch.smoke())
    finally:
        faults.disarm()
    if args.metrics_out:
        obs.tick(force=True)
        print(f"metrics snapshot -> {args.metrics_out}")


if __name__ == "__main__":
    main()
