"""Product quantization: codebook training, encode/decode, ADC scoring.

A d-dim embedding is split into M subvectors of d/M dims; each subspace gets
a K-entry codebook trained with k-means, so a vector compresses to M uint8
codes (d * 4 bytes -> M bytes at the K <= 256 ceiling — the paper's
1.2M-news corpus drops from ~1.2 GB fp32 to ~10 MB, and the code arrays
themselves are 4x smaller than the previous int32 storage).  Query scoring
is asymmetric (ADC): the query
stays full precision, one [M, K] table of sub-inner-products is built per
query, and every candidate's score is a LUT gather+sum over its codes —
the hot loop served by kernels/pq_scoring.py (Pallas) or kernels/ref.py.

Training scales past the corpus: ``pq_train`` fits codebooks on a bounded
uniform sample (``PQConfig.train_sample``) with mini-batch k-means
(``kmeans_minibatch``: k-means++ seeding, fixed iteration budget, Lloyd
polish), so codebook cost is a constant once the corpus outgrows the
sample — the property million-vector ``IndexBuilder.build`` rests on.
``opq_train`` adds the OPQ rotation: an orthogonal ``R`` learned by
alternating PQ training with a Procrustes solve, carried inside
``PQCodebook.rot`` so every encode/decode/LUT path applies it
consistently (``rot=None`` means identity — the pre-OPQ format).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PQConfig:
    n_subvec: int = 8      # M: subvectors per embedding (d % M == 0)
    n_codes: int = 32      # K: codebook entries per subspace (<= 256 so
    #                        codes pack into uint8)
    train_iters: int = 15  # Lloyd iterations per subspace (mini-batch path
    #                        runs 2x this many cheap batch steps, see
    #                        fit_kmeans)
    train_sample: int = 16384   # codebooks train on at most this many rows
    #                             — build cost stops growing with ntotal
    train_batch: int = 2048     # mini-batch size past which Lloyd's is
    #                             replaced by kmeans_minibatch
    opq_iters: int = 0     # OPQ alternations (0 = no rotation, plain PQ)

    def __post_init__(self):
        if not 0 < self.n_codes <= 256:
            raise ValueError(
                f"n_codes must be in (0, 256] for uint8 codes, "
                f"got {self.n_codes}")


def pq_config_for(dim: int) -> PQConfig:
    """PQ sized to the embedding: 8 dimensions per sub-quantizer (at least
    8 sub-quantizers) and the whole uint8 code range.  The fixed default
    (8 x 32 codes, 40 bits a vector) is sized for small test embeddings;
    at news_dim 768 it left recall@10 of the served path at 0.47 against
    0.975 for 96 x 256 codes (2-layer full-width encoder, CPU)."""
    return PQConfig(n_subvec=max(8, dim // 8), n_codes=256)


class PQCodebook(NamedTuple):
    centers: jax.Array     # [M, K, d/M]
    rot: Any = None        # [d, d] orthogonal OPQ rotation; None = identity
    #                        (the pre-OPQ snapshot format loads as None and
    #                        serves identically to an explicit eye(d))


# ---------------------------------------------------------------------------
# k-means: full Lloyd's and mini-batch, both with dead-centroid reseeding
# ---------------------------------------------------------------------------

def _dist2(x, cent):
    return (jnp.sum(x * x, 1)[:, None] - 2.0 * x @ cent.T
            + jnp.sum(cent * cent, 1)[None, :])


def _assign(x, cent):
    return jnp.argmin(_dist2(x, cent), axis=1)


def _lloyd_iter(x, cent):
    """One Lloyd update with dead-centroid reseeding: empty clusters are
    re-planted on the farthest points of the largest cluster (instead of
    freezing — a frozen dead centroid never recovers and silently wastes
    a cell/codeword)."""
    n, k = x.shape[0], cent.shape[0]
    d2 = _dist2(x, cent)                              # [n, k]
    a = jnp.argmin(d2, axis=1)
    counts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), a, num_segments=k)
    sums = jax.ops.segment_sum(x, a, num_segments=k)
    new = jnp.where(counts[:, None] > 0,
                    sums / jnp.maximum(counts, 1.0)[:, None], cent)
    dead = counts == 0
    d2a = jnp.take_along_axis(d2, a[:, None], axis=1)[:, 0]
    big = jnp.argmax(counts)
    score = jnp.where(a == big, d2a, -jnp.inf)        # farthest-of-largest
    # at most k centroids can be dead, so a k-wide partial sort suffices
    # (top_k compiles/runs far cheaper than a full argsort over n)
    _, far = jax.lax.top_k(score, min(k, n))
    rank = jnp.clip(jnp.cumsum(dead) - 1, 0, min(k, n) - 1)
    return jnp.where(dead[:, None], x[far[rank]], new)


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(key, x, k: int, iters: int = 15):
    """Lloyd's k-means (L2) on x [N, d] -> (centroids [k, d], assignment).
    Fully jittable/vmappable: fixed iteration count; empty clusters are
    reseeded from the farthest points of the largest cluster each step.
    k-means++ seeded like the mini-batch path: uniformly drawn seeds left
    partition quality (and IVF recall) to the luck of the PRNG stream —
    recall@10 spread 0.87-0.98 across build seeds on the test corpus.
    Jitted at module level so repeated builds at one shape (the
    background-rebuild loop) reuse ONE warm executable."""
    cent = jax.lax.fori_loop(0, iters, lambda _, c: _lloyd_iter(x, c),
                             _kmeanspp_init(key, x, k))
    return cent, _assign(x, cent)


def _kmeanspp_init(key, x, k: int):
    """k-means++-style seeding: new centroids are data points sampled with
    probability proportional to their squared distance from the chosen
    set.  Sampled in ~16 chunked rounds (a whole chunk drawn from one
    D^2 distribution, then distances refreshed — the k-means|| over-
    sampling idea) so seeding costs a fixed number of dense [n, chunk]
    matmuls instead of k sequential matvec steps: at k=1024 the exact
    sequential scan is ~1s of pure dispatch overhead per build."""
    n = x.shape[0]
    k0, k1 = jax.random.split(key)
    c0 = x[jax.random.randint(k0, (), 0, n)]
    if k == 1:
        return c0[None]
    x2 = jnp.sum(x * x, axis=1)
    d2 = jnp.maximum(x2 - 2.0 * x @ c0 + jnp.sum(c0 * c0), 0.0)
    chunk = -(-k // 16)
    rounds = -(-(k - 1) // chunk)

    def step(d2min, kk):
        i = jax.random.categorical(kk, jnp.log(d2min + 1e-12), shape=(chunk,))
        c = x[i]                                            # [chunk, d]
        d2c = jnp.maximum(x2[:, None] - 2.0 * x @ c.T
                          + jnp.sum(c * c, axis=1)[None], 0.0)
        return jnp.minimum(d2min, d2c.min(axis=1)), c

    _, rest = jax.lax.scan(step, d2, jax.random.split(k1, rounds))
    return jnp.concatenate([c0[None], rest.reshape(-1, x.shape[1])],
                           axis=0)[:k]


@functools.partial(jax.jit, static_argnames=("k", "iters", "batch", "polish"))
def kmeans_minibatch(key, x, k: int, *, iters: int = 30, batch: int = 1024,
                     polish: int = 2):
    """Mini-batch k-means (Sculley-style) on x [N, d] -> (centroids [k, d],
    assignment [N]).

    k-means++ seeded, then ``iters`` fixed-size batch steps updating each
    hit centroid toward the cumulative mean of every point ever assigned
    to it, then ``polish`` full Lloyd passes over x (with dead-centroid
    reseeding) to settle boundaries.  Per-step cost is O(batch * k * d)
    regardless of N — callers bound N itself via ``sample_rows``, which
    keeps every shape (and therefore every compiled executable) fixed as
    the corpus grows.
    """
    n = x.shape[0]
    batch = min(batch, n)
    kpp, kmb = jax.random.split(key)
    cent0 = _kmeanspp_init(kpp, x, k)

    def mb_step(carry, kk):
        cent, counts = carry
        xb = x[jax.random.randint(kk, (batch,), 0, n)]
        a = _assign(xb, cent)
        bc = jax.ops.segment_sum(jnp.ones((batch,), x.dtype), a,
                                 num_segments=k)
        bs = jax.ops.segment_sum(xb, a, num_segments=k)
        new_counts = counts + bc
        cent = jnp.where(
            new_counts[:, None] > 0,
            (cent * counts[:, None] + bs)
            / jnp.maximum(new_counts, 1.0)[:, None],
            cent)
        return (cent, new_counts), None

    (cent, _), _ = jax.lax.scan(
        mb_step, (cent0, jnp.zeros((k,), x.dtype)),
        jax.random.split(kmb, iters))
    cent = jax.lax.fori_loop(0, polish, lambda _, c: _lloyd_iter(x, c), cent)
    return cent, _assign(x, cent)


def fit_kmeans(key, x, k: int, *, iters: int = 15, batch: int = 1024):
    """Dispatch: full Lloyd's when x is small (the mini-batch machinery
    buys nothing below ~2 batches of data), else mini-batch with 2x the
    iteration budget (each step sees batch points, not N) plus polish."""
    if x.shape[0] <= max(2 * batch, 4 * k):
        return kmeans(key, x, k, iters)
    return kmeans_minibatch(key, x, k, iters=2 * iters, batch=batch)


def sample_rows(key, x, cap: int | None):
    """Uniform row sample of at most ``cap`` rows, without replacement;
    returns x unchanged when it already fits (small-corpus behavior is
    then exactly the unsampled path)."""
    n = x.shape[0]
    if cap is None or n <= cap:
        return x
    return jnp.take(x, jax.random.choice(key, n, (cap,), replace=False),
                    axis=0)


# ---------------------------------------------------------------------------
# PQ train / encode / decode / LUT
# ---------------------------------------------------------------------------

def _split(x, m):
    n, d = x.shape
    assert d % m == 0, f"dim {d} not divisible by {m} subvectors"
    return x.reshape(n, m, d // m)


def _rotate(x, rot):
    return x if rot is None else x @ rot


@functools.partial(jax.jit, static_argnames=("k", "iters", "batch"))
def _fit_subspaces(keys, xs, k: int, iters: int, batch: int):
    return jax.vmap(
        lambda kk, xx: fit_kmeans(kk, xx, k, iters=iters, batch=batch)[0]
    )(keys, xs)


def pq_train(key, x, cfg: PQConfig) -> PQCodebook:
    """x: [N, d] training vectors -> per-subspace codebooks.

    Trains on at most ``cfg.train_sample`` uniformly sampled rows with
    ``fit_kmeans`` per subspace, so training cost is bounded as N grows
    — and, with the sample cap fixing the training shapes, repeated
    builds reuse the same warm jitted executable.
    """
    x = jnp.asarray(x)
    x = sample_rows(jax.random.fold_in(key, 0x5a), x, cfg.train_sample)
    xs = jnp.swapaxes(_split(x, cfg.n_subvec), 0, 1)       # [M, S, ds]
    keys = jax.random.split(key, cfg.n_subvec)
    cents = _fit_subspaces(keys, xs, cfg.n_codes, cfg.train_iters,
                           cfg.train_batch)
    return PQCodebook(cents)


def opq_train(key, x, cfg: PQConfig) -> PQCodebook:
    """OPQ: learn an orthogonal rotation R minimizing quantization error,
    by alternating (train PQ on x@R) with the Procrustes solve
    R = U V^T from svd(x^T rec) — then train the final codebooks in the
    rotated space.  The returned codebook carries ``rot``; encode/decode/
    LUT apply it transparently, and scores are invariant because
    <q@R, r@R> == <q, r> for orthogonal R.
    """
    x = jnp.asarray(x)
    x = sample_rows(jax.random.fold_in(key, 0x0b), x, cfg.train_sample)
    d = x.shape[1]
    rot = jnp.eye(d, dtype=x.dtype)
    for t in range(cfg.opq_iters):
        xr = x @ rot
        cb = pq_train(jax.random.fold_in(key, t), xr, cfg)
        rec = pq_decode(cb, pq_encode(cb, xr))        # rot=None: rotated space
        u, _, vt = jnp.linalg.svd(x.T @ rec, full_matrices=False)
        rot = u @ vt
    cb = pq_train(jax.random.fold_in(key, cfg.opq_iters), x @ rot, cfg)
    return PQCodebook(cb.centers, rot)


@jax.jit
def pq_encode(cb: PQCodebook, x):
    """x: [N, d] -> codes [N, M] uint8 (nearest codeword per subspace in
    the rotated space when cb carries an OPQ rotation; K <= 256 is
    enforced by PQConfig, so uint8 never wraps).

    The M sub-inner-products are computed as ONE [N, d] @ [d, M*K] GEMM
    against a block-diagonal layout of the codebooks: M times the flops
    of the batched-small-matmul einsum, but a single dense contraction
    the backend tiles well (MXU on TPU; ~1.5x faster even on CPU at
    bulk-add sizes, where this is the build hot path).  The per-(row,
    subspace) ||x_s||^2 term is constant across the K candidates, so
    argmin needs only ||c||^2 - 2<x_s, c>.
    """
    x = _rotate(x, cb.rot)
    m, k, ds = cb.centers.shape
    w = jnp.zeros((m, ds, m, k), cb.centers.dtype)
    w = w.at[jnp.arange(m), :, jnp.arange(m), :].set(
        jnp.swapaxes(cb.centers, 1, 2))                   # block-diagonal
    dots = x @ w.reshape(m * ds, m * k)                   # [N, M*K]
    d2 = jnp.sum(cb.centers * cb.centers, -1).reshape(1, m * k) - 2.0 * dots
    return jnp.argmin(d2.reshape(-1, m, k), axis=-1).astype(jnp.uint8)


@jax.jit
def pq_decode(cb: PQCodebook, codes):
    """codes: [N, M] -> reconstructed vectors [N, d] (de-rotated back to
    the original space when cb carries an OPQ rotation)."""
    rec = jnp.take_along_axis(cb.centers[None],
                              codes[:, :, None, None].astype(jnp.int32),
                              axis=2)[:, :, 0, :]         # [N, M, ds]
    rec = rec.reshape(codes.shape[0], -1)
    return rec if cb.rot is None else rec @ cb.rot.T


@jax.jit
def pq_lut(cb: PQCodebook, q):
    """q: [B, d] queries -> inner-product LUT [B, M, K].  The query is
    rotated into code space, so LUT-sum scores equal <q, decode(codes)>
    with or without OPQ."""
    qs = _split(_rotate(q, cb.rot), cb.centers.shape[0])  # [B, M, ds]
    return jnp.einsum("bmd,mkd->bmk", qs, cb.centers)


def pq_search(cb: PQCodebook, codes, q, k: int):
    """Flat ADC scan: score every code row for every query, return top-k.

    codes: [N, M]; q: [B, d] -> (scores [B, k], rows [B, k]).  Uses the
    Pallas LUT kernel via the ops dispatcher (shared-codes broadcast path).
    """
    from repro.kernels import ops
    lut = pq_lut(cb, jnp.asarray(q))
    scores = ops.pq_lut_scores(lut, jnp.asarray(codes)[None])
    return jax.lax.top_k(scores, min(k, codes.shape[0]))
