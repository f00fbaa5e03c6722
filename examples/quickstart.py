"""Quickstart: train a tiny SpeedyFeed news recommender end to end.

  PYTHONPATH=src python examples/quickstart.py

Builds a synthetic Microsoft-News-like click log, then runs Algorithm 1
(centralized encoding -> cache -> BusLM -> autoregressive loss) for 40
steps through the unified training runtime: a registry-built Trainer with
one warm donated executable for every seg-length bucket (each encode-set
chunk picks its length on the device), fed by the async device
prefetcher.
"""
from repro import data, training
from repro.launch.train import make_loader, small_speedyfeed_config


def main():
    cfg = small_speedyfeed_config()
    corpus, log, store, lcfg = make_loader(cfg, seed=0)
    print(f"corpus: {corpus.n_news} news, {log.n_users} users; "
          f"PLM {cfg.plm.n_layers}L x {cfg.plm.d_model}d, "
          f"K={cfg.plm.n_segments} segments; buckets {lcfg.buckets}")

    trainer = training.get_trainer("speedyfeed", cfg=cfg)

    def make_batcher(epoch):
        return data.DynamicBatcher(log, store, lcfg, n_threads=2,
                                   seed=epoch).start()

    res = trainer.fit(make_batcher, steps=40, log_every=10)
    print(f"done in {res.wall_seconds:.1f}s — loss "
          f"{res.losses[0]:.4f} -> {res.losses[-1]:.4f}, "
          f"final ar_acc={res.metrics.get('ar_acc', 0):.3f}")
    print(f"bucket executables: {res.compile_counts} (compiles/bucket), "
          f"steps/bucket {res.bucket_steps}, "
          f"host stall {res.host_stall_fraction:.1%}")
    print("the cache reuse count should rise as p_t grows.")


if __name__ == "__main__":
    main()
