"""Pieces the drivers share: the program's configuration built from a
configuration file, the time bound of a window, and per-leaf norms."""
from __future__ import annotations

import time

import numpy as np


def program_config(c: dict):
    """The program's SpeedyFeedConfig for a SpeedyFeed configuration file
    (every size as the file states it)."""
    from repro import core
    p, ca = c["plm"], c["cache"]
    return core.make_config(
        vocab=p["vocab"], n_layers=p["n_layers"], d_model=p["d_model"],
        n_heads=p["n_heads"], d_ff=p["d_ff"], n_segments=p["n_segments"],
        seg_len=p["seg_len"], news_dim=p["news_dim"], n_news=ca["n_news"],
        gamma=ca["gamma"], beta=ca["beta"], encode_budget=ca["encode_budget"],
        batch_users=c["batch_users"], hist_len=c["hist_len"],
        merged_cap=c["merged_cap"], n_neg=c["n_neg"], remat=c["remat"])


class Deadline:
    """A step bound that a training loop's ``while step < steps`` reads as
    "until the window closes"."""

    def __init__(self, t_end: float):
        self.t_end = t_end

    def __gt__(self, step):
        return time.perf_counter() < self.t_end


def leaf_norms(tree) -> np.ndarray:
    """Float64 L2 norm of every leaf, in tree-flatten order."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return np.asarray(jax.device_get(norms), np.float64)


def leaf_diff_norms(a, b) -> np.ndarray:
    """Norm of a - b for every leaf of two trees of one structure."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda x, y: [jnp.sqrt(jnp.sum(jnp.square(
        u.astype(jnp.float32) - v.astype(jnp.float32))))
        for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y))])(a, b)
    return np.asarray(jax.device_get(norms), np.float64)


def worst_leaf_gap(got: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Largest |got - ref| over leaves, each against the larger of its
    reference norm and the median leaf's."""
    keep = np.ones(ref.shape, bool) if keep is None else keep
    floor = np.median(ref[keep])
    return float(np.max(np.abs(got - ref)[keep]
                        / np.maximum(ref[keep], floor)))

