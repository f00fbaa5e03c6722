"""Small configurations and cells of the benchmark's drivers, for tests
that run a whole cell on the CPU (the widths are cut; everything else is
the cell's own path)."""
from __future__ import annotations

import copy
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]

PLM = {"vocab": 500, "n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128,
       "n_segments": 3, "seg_len": 16, "news_dim": 32, "max_len": 512,
       "max_freq": 32}

CONFIG = {
    "name": "small", "plm": PLM,
    "cache": {"n_news": 4000, "gamma": 20, "beta": 0.002, "encode_budget": 48},
    "batch_users": 8, "hist_len": 20, "merged_cap": 128, "n_neg": 4,
    "remat": True,
    "optimizer": {"lr": 1e-4, "plm_lr_scale": 0.08, "grad_clip": 1.0,
                  "b1": 0.9, "b2": 0.999, "eps": 1e-8}
}


def cell(name: str) -> dict:
    """The committed cell file with its traffic cut to the small size."""
    c = json.loads((ROOT / "bench" / "cells" / f"{name}.json").read_text())
    t = c["traffic"]
    if c["driver"] == "train":
        t.update(n_users=512, buckets=[12, 16], token_budget=1500,
                 warm_steps=6, loader_threads=1, check_users=32)
    elif c["driver"] == "encode":
        t.update(n_articles=600, chunk=64, check_rows=64)
    return c


def run_small(name: str, *, seed: int = 5, seconds: float = 2.0,
              config=None, cell_over=None):
    """One whole run of cell ``name`` (a file under bench/cells) at the
    small size on the CPU."""
    import jax

    from bench import run as harness
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    c = cell(name)
    if cell_over:
        c = cell_over(copy.deepcopy(c))
    wl = {"name": name, "config": c["config"], "chips": 1}
    return harness.run_cell(b, wl, c, copy.deepcopy(config or CONFIG),
                            seed=seed, seconds=seconds, trace=False,
                            devices=jax.devices())
