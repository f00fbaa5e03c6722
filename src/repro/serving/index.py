"""ANN indexes over news embeddings: exact-flat fallback, IVF-Flat, IVF-PQ.

Replaces the paper's HNSW (§5.1.4) with the TPU-native family: a spherical
k-means coarse quantizer (IVF) partitions the corpus into nlist cells on
the unit sphere — assignment and probing share that one metric (the old
raw-L2 partition probed by inner product ranked cells by a metric that
never built them); a query probes the nprobe nearest cells and scores
only their members, either in full precision (IVF-Flat) or through
residual product-quantization codes around the raw-space cell means
(IVF-PQ, uint8 codes scored with the Pallas LUT kernel).  All indexes
share one API:

    idx.train(key, vectors)          # fit quantizers (no-op for Flat)
    idx.add(ids, vectors)            # incremental — used by online deltas
    idx.snapshot(version) -> IndexSnapshot        # frozen, zero-copy
    idx.search(queries, k) -> (scores [B, k], ids [B, k])   np.float32/int64

``search`` routes through ``snapshot()`` (snapshot.py): the immutable
IndexSnapshot is the ONE query object of the serving tier, and the index
classes are its builders/mutators.  Outside this package, mutation goes
through the lifecycle API (IndexBuilder + RetrievalService.publish/
rebuild/swap), never through add/remove directly.

Storage is device-resident padded CSR: fixed-capacity ``[nlist, cap]``
id/payload arrays plus per-list lengths, where ``cap`` grows in
power-of-two buckets (MIN_CAP, doubling on overflow).  ``add``/``remove``
are device scatters/compactions, and the whole query path — cell probe,
candidate gather, scoring (einsum for IVF-Flat; coarse term + Pallas LUT
for IVF-PQ) and masked top-k — is ONE jitted executable per (index kind,
cap bucket): searches across batches with any fill level reuse the warm
executable, and a cap growth costs exactly one fresh compilation for the
new bucket.  (The legacy ragged host-numpy layout survived PR 3 as the
benchmark baseline; it is gone — BENCH_retrieval.json recorded its
3-6x/1.1-1.4x deficits and nothing references it.)
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .pq import (PQCodebook, PQConfig, fit_kmeans, opq_train, pq_encode,
                 pq_lut, pq_train, sample_rows)

PAD_ID = -1
MIN_CAP = 8            # smallest per-list capacity bucket

# Scan-shape knobs, tuned on this box via benchmarks/retrieval.py (the
# chosen values are recorded in BENCH_retrieval.json entries):
#   DENSE_PROBE_FACTOR  IVF-Flat scores every cell densely (one big matmul)
#                       while nlist <= factor * B * nprobe, else gathers
#                       only probed payloads per query
#   PQ_SCAN_BLOCK_N     cap on the Pallas LUT kernel's candidate block —
#                       wide blocks amortize per-grid-step overhead
#                       (dominant in interpret mode)
#   PQ_SCAN_VARIANT     block-scoring strategy ("auto" = gather when
#                       interpreting, one-hot MXU contraction on TPU)
DENSE_PROBE_FACTOR = 4
PQ_SCAN_BLOCK_N = 4096
PQ_SCAN_VARIANT = "auto"
ENCODE_CHUNK = 65536   # bulk PQ encode chunk: bounds the [chunk, M, K]
#                        distance buffer at million-row adds

# Precision of every full-precision score (flat scans, IVF-Flat, re-rank).
# On TPU a float32 dot at DEFAULT precision may round its operands to
# bf16; there the dense IVF-Flat scan and the per-probe gather of the
# sharded path, two differently shaped dots, disagreed on 2 of 160 top-10
# ids (v5e).
FULL = jax.lax.Precision.HIGHEST

# Module-level so every flat scan (FlatIndex, delta views, snapshots of any
# vintage) shares ONE jit cache: a fresh buffer/snapshot at a shape seen
# before hits the warm executable instead of re-jitting per instance.
_flat_score = jax.jit(lambda q, v: jnp.dot(q, v.T, precision=FULL))


def _next_cap(n: int) -> int:
    """Smallest power-of-two capacity bucket holding n entries per list."""
    return max(MIN_CAP, 1 << max(int(n) - 1, 0).bit_length())


@dataclasses.dataclass(frozen=True)
class IVFConfig:
    nlist: int = 32        # coarse cells
    nprobe: int = 8        # cells scanned per query
    train_iters: int = 15
    train_sample: int = 16384   # coarse k-means fits on at most this many
    #                             sampled rows — build cost stops growing
    #                             with ntotal (full corpus when it fits)
    train_batch: int = 1024     # mini-batch size past which Lloyd's is
    #                             replaced by kmeans_minibatch
    metric: str = "l2"     # cell-probe metric: "l2" ranks cells on the unit
    #                        sphere — the same metric the spherical k-means
    #                        partition was built with; "ip" is the legacy
    #                        mismatched ranking (raw inner product against
    #                        the unnormalized cell means), kept for the
    #                        recall regression test and benchmark


def _topk_padded(scores, cand_ids, k):
    """scores [B, C] device, cand_ids [B, C] np (PAD_ID = invalid)."""
    if cand_ids.shape[1] == 0:
        B = cand_ids.shape[0]
        return (np.full((B, k), -np.inf, np.float32),
                np.full((B, k), PAD_ID, np.int64))
    valid = jnp.asarray(cand_ids != PAD_ID)
    scores = jnp.where(valid, scores, -jnp.inf)
    k_eff = min(k, scores.shape[1])
    s, pos = jax.lax.top_k(scores, k_eff)
    ids = np.take_along_axis(cand_ids, np.asarray(pos), axis=1)
    s = np.asarray(s, np.float32)
    ids = np.where(np.isfinite(s), ids, PAD_ID)
    if k_eff < k:            # fewer candidates than requested: pad out
        s = np.pad(s, ((0, 0), (0, k - k_eff)), constant_values=-np.inf)
        ids = np.pad(ids, ((0, 0), (0, k - k_eff)), constant_values=PAD_ID)
    return s, ids.astype(np.int64)


# ---------------------------------------------------------------------------
# padded-CSR device primitives
# ---------------------------------------------------------------------------

def _normalize(x, eps: float = 1e-9):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), eps)


def _probe_cells(q, cent_unit, cent_raw, nprobe: int, metric: str):
    """Top-nprobe coarse cells per query -> [B, nprobe] int32.

    "l2" ranks cells by the metric that built the spherical partition:
    with unit centroids, argmin ||q_hat - c||^2 == argmax <q, c> (the
    query's own norm is constant per row), so one matmul suffices.  "ip"
    is the legacy mismatched ranking — raw inner product against the
    unnormalized cell means, which biases probing toward loud/coherent
    cells regardless of direction match.
    """
    if metric == "l2":
        aff = q @ cent_unit.T
    elif metric == "ip":
        aff = q @ cent_raw.T
    else:
        raise ValueError(f"unknown probe metric: {metric!r}")
    return jax.lax.top_k(aff, nprobe)[1]


def _masked_topk(scores, cand_ids, valid, k: int):
    """Device top-k over fixed-width candidates; invalid slots -> PAD_ID."""
    scores = jnp.where(valid, scores, -jnp.inf)
    s, pos = jax.lax.top_k(scores, k)
    ids = jnp.take_along_axis(cand_ids, pos, axis=1)
    return s, jnp.where(jnp.isfinite(s), ids, PAD_ID)


def _gather_candidates(q, cent_unit, cent_raw, list_ids, lens, *,
                       nprobe: int, metric: str):
    """Probe cells, then gather the fixed-width candidate window: probed
    cell indices [B, P], candidate ids [B, P*cap], and the slot-validity
    mask from the per-list lengths.  Shared by both search kernels so the
    probe/validity semantics cannot diverge between them."""
    B, cap = q.shape[0], list_ids.shape[1]
    probes = _probe_cells(q, cent_unit, cent_raw, nprobe, metric)  # [B, P]
    cand_ids = list_ids[probes].reshape(B, -1)                # [B, P*cap]
    valid = (jnp.arange(cap)[None, None]
             < lens[probes][:, :, None]).reshape(B, -1)
    return probes, cand_ids, valid


@functools.partial(jax.jit,
                   static_argnames=("nprobe", "k", "metric", "dense"))
def _search_flat_csr(q, cent_unit, cent_raw, list_ids, list_vecs, lens, *,
                     nprobe: int, k: int, metric: str, dense: bool = True):
    """Jitted IVF-Flat search over padded-CSR storage.

    q [B, d]; cent_unit/cent_raw [nlist, d]; list_ids [nlist, cap] int32;
    list_vecs [nlist, cap, d]; lens [nlist] int32.  Shapes are static per
    cap bucket, so every fill level hits the same warm executable.  The
    caller picks ``dense`` from the DENSE_PROBE_FACTOR crossover (the
    flag is static, so each regime has its own warm executable).
    """
    B, cap = q.shape[0], list_ids.shape[1]
    probes, cand_ids, valid = _gather_candidates(
        q, cent_unit, cent_raw, list_ids, lens, nprobe=nprobe, metric=metric)
    if dense:
        # dense coverage (micro-batch serving: B*nprobe probes over few
        # cells): score every cell once in one MXU/BLAS matmul and gather
        # only the probed [B, P, cap] score blocks — far cheaper than
        # duplicating cell payloads per query through a vector gather
        all_s = jnp.einsum("bd,lcd->blc", q, list_vecs,
                           precision=FULL)                  # [B, nlist, cap]
        scores = jnp.take_along_axis(all_s, probes[:, :, None], axis=1)
    else:
        # sparse coverage (nlist >> B*nprobe): gather only probed payloads
        scores = jnp.einsum("bd,bpcd->bpc", q, list_vecs[probes],
                            precision=FULL)
    return _masked_topk(scores.reshape(B, -1), cand_ids, valid, k)


def flat_dense_crossover(nlist: int, batch: int, nprobe: int) -> bool:
    """Dense-vs-gather regime for the IVF-Flat scan (see
    DENSE_PROBE_FACTOR; tuned in benchmarks/retrieval.py)."""
    return nlist <= DENSE_PROBE_FACTOR * batch * nprobe


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "metric",
                                             "block_n", "variant"))
def _search_pq_csr(q, cent_unit, cent_raw, list_ids, list_codes, lens,
                   cb_centers, cb_rot=None, *, nprobe: int, k: int,
                   metric: str, block_n: int = PQ_SCAN_BLOCK_N,
                   variant: str = "auto"):
    """Jitted IVF-PQ search: coarse term + masked Pallas LUT over the
    gathered [B, nprobe*cap, M] padded-CSR uint8 codes.  ``cb_rot`` is
    the optional OPQ rotation (None = identity, the pre-OPQ format) —
    applied inside pq_lut, so probing and the coarse term stay in the
    original space while ADC runs in code space."""
    from repro.kernels import ops
    B, cap = q.shape[0], list_ids.shape[1]
    probes, cand_ids, valid = _gather_candidates(
        q, cent_unit, cent_raw, list_ids, lens, nprobe=nprobe, metric=metric)
    lut = pq_lut(PQCodebook(cb_centers, cb_rot), q)           # [B, M, K]
    codes = list_codes[probes].reshape(B, -1, list_codes.shape[-1])
    # wide blocks amortize per-grid-step overhead (dominant in interpret
    # mode); the caller clamps block_n to the candidate width
    adc = ops.pq_lut_scores(lut, codes, valid, block_n=block_n,
                            variant=variant)                  # [B, P*cap]
    coarse = jnp.take_along_axis(q @ cent_raw.T, probes, axis=1)
    scores = adc + jnp.repeat(coarse, cap, axis=1)
    return _masked_topk(scores, cand_ids, valid, k)


def _csr_append(list_ids, payload, lens, assign, new_ids, new_payload):
    """Scatter n new rows into their lists' next free slots (device ops).

    Each new row i lands at slot lens[assign[i]] + (rank of i among the
    new rows assigned to the same list); ranks come from a stable sort.
    """
    order = jnp.argsort(assign, stable=True)
    a = assign[order]
    rank = jnp.arange(a.shape[0]) - jnp.searchsorted(a, a)
    slot = lens[a] + rank
    list_ids = list_ids.at[a, slot].set(new_ids[order])
    payload = payload.at[a, slot].set(new_payload[order])
    lens = lens + jnp.bincount(assign, length=lens.shape[0]).astype(lens.dtype)
    return list_ids, payload, lens


def _csr_remove(list_ids, payload, lens, drop_ids):
    """Drop matching ids and re-pack every list front-aligned (device ops)."""
    cap = list_ids.shape[1]
    slot = jnp.arange(cap)[None]
    keep = (slot < lens[:, None]) & ~jnp.isin(list_ids, drop_ids)
    perm = jnp.argsort(~keep, axis=1, stable=True)   # kept slots to the front
    list_ids = jnp.take_along_axis(list_ids, perm, axis=1)
    payload = jnp.take_along_axis(
        payload, perm.reshape(perm.shape + (1,) * (payload.ndim - 2)), axis=1)
    lens = keep.sum(axis=1).astype(lens.dtype)
    list_ids = jnp.where(slot < lens[:, None], list_ids, PAD_ID)
    return list_ids, payload, lens


# ---------------------------------------------------------------------------
# indexes
# ---------------------------------------------------------------------------

class FlatIndex:
    """Exact MIPS over the full corpus — the fallback and recall oracle."""

    kind = "exact"

    def __init__(self, dim: int):
        self.dim = dim
        self._vecs = np.zeros((0, dim), np.float32)
        self._ids = np.zeros((0,), np.int64)

    @property
    def ntotal(self) -> int:
        return self._vecs.shape[0]

    def train(self, key, vectors):   # noqa: ARG002 - uniform API
        return self

    def remove(self, ids):
        keep = ~np.isin(self._ids, np.asarray(ids, np.int64))
        self._vecs, self._ids = self._vecs[keep], self._ids[keep]

    def add(self, ids, vectors):
        """Upsert: a re-added id replaces its previous row."""
        self.remove(ids)
        self._vecs = np.concatenate(
            [self._vecs, np.asarray(vectors, np.float32)])
        self._ids = np.concatenate([self._ids, np.asarray(ids, np.int64)])

    def snapshot(self, version: int = 0):
        """Freeze the current state into an immutable IndexSnapshot."""
        from .snapshot import snapshot_from_index
        return snapshot_from_index(self, version)

    def search(self, queries, k: int):
        return self.snapshot().search(queries, k)


class IVFFlatIndex:
    """IVF coarse quantizer + full-precision scoring of probed cells,
    on padded-CSR device storage with a jitted end-to-end search (one
    warm executable per cap bucket)."""

    kind = "ivf-flat"

    def __init__(self, dim: int, cfg: IVFConfig = IVFConfig()):
        self.dim, self.cfg = dim, cfg
        self.centroids = None                  # [nlist, d] np, unit norm
        self.centroids_raw = None              # [nlist, d] np, raw cell means
        self._cent_dev = None                  # unit centroids, device
        self._cent_raw_dev = None              # raw cell means, device
        self._cap = MIN_CAP
        self._ids_dev = jnp.full((cfg.nlist, MIN_CAP), PAD_ID, jnp.int32)
        self._payload_dev = self._empty_payload_dev(MIN_CAP)
        self._lens = jnp.zeros((cfg.nlist,), jnp.int32)

    # --- storage hooks (overridden by IVFPQIndex) ---------------------
    def _empty_payload_dev(self, cap: int):
        return jnp.zeros((self.cfg.nlist, cap, self.dim), jnp.float32)

    def _encode_payload_dev(self, vectors, assign):   # noqa: ARG002
        return vectors

    # ------------------------------------------------------------------
    @property
    def ntotal(self) -> int:
        return int(jnp.sum(self._lens))

    @property
    def cap(self) -> int:
        """Current power-of-two per-list capacity bucket."""
        return self._cap

    @property
    def is_trained(self) -> bool:
        return self.centroids is not None

    def train(self, key, vectors):
        """Spherical k-means: the partition lives on the unit sphere, and
        assignment and probing share that one metric (the old raw-L2
        partition probed by inner product ranked cells by a metric that
        never built them).  Raw-space cell means are kept alongside: they
        are the PQ residual origin / coarse score term and the legacy
        "ip" probe ranking.

        The quantizer fits on at most ``cfg.train_sample`` sampled rows
        via mini-batch k-means (fit_kmeans), so training cost — and the
        compiled training executables' shapes — stop growing with ntotal;
        only the O(n) cell assignment / raw-mean pass sees every row.
        """
        vectors = jnp.asarray(vectors, jnp.float32)
        with obs.span("index_build_sample", kind=self.kind):
            xs = sample_rows(jax.random.fold_in(key, 0x11),
                             _normalize(vectors), self.cfg.train_sample)
        t0 = time.perf_counter()
        with obs.span("index_build_train", kind=self.kind):
            cent, _ = fit_kmeans(key, xs, self.cfg.nlist,
                                 iters=self.cfg.train_iters,
                                 batch=self.cfg.train_batch)
            self._cent_dev = _normalize(cent)
            assign = self._assign_cells(vectors)
            ones = jnp.ones((vectors.shape[0],), vectors.dtype)
            counts = jax.ops.segment_sum(ones, assign,
                                         num_segments=self.cfg.nlist)
            sums = jax.ops.segment_sum(vectors, assign,
                                       num_segments=self.cfg.nlist)
            means = sums / jnp.maximum(counts, 1.0)[:, None]
            self._cent_raw_dev = jnp.where(counts[:, None] > 0, means,
                                           self._cent_dev)
            self.centroids = np.asarray(self._cent_dev)
            self.centroids_raw = np.asarray(self._cent_raw_dev)
            self._post_train(key, vectors, assign)
        obs.histogram("index_build_train_ms", kind=self.kind).observe(
            (time.perf_counter() - t0) * 1e3)
        return self

    def _post_train(self, key, vectors, assign):
        pass

    def _assign_cells(self, vectors):
        """Nearest cell on the unit sphere -> [n] int32.  With unit
        centroids, argmin ||v_hat - c||^2 == argmax <v, c> (each row's
        norm is a per-row constant), so assignment is one matmul."""
        return jnp.argmax(vectors @ self._cent_dev.T, axis=1).astype(
            jnp.int32)

    def _grow(self, new_cap: int):
        pad = new_cap - self._cap
        self._ids_dev = jnp.pad(self._ids_dev, ((0, 0), (0, pad)),
                                constant_values=PAD_ID)
        spec = ((0, 0), (0, pad)) + ((0, 0),) * (self._payload_dev.ndim - 2)
        self._payload_dev = jnp.pad(self._payload_dev, spec)
        self._cap = new_cap

    def remove(self, ids):
        ids = self._check_ids(ids)
        if ids.size == 0:
            return
        self._ids_dev, self._payload_dev, self._lens = _csr_remove(
            self._ids_dev, self._payload_dev, self._lens,
            jnp.asarray(ids, jnp.int32))

    def _check_ids(self, ids):
        """Device lists store ids as int32; reject ids that would wrap
        (silent truncation would corrupt search results and could even
        collide with PAD_ID)."""
        ids = np.asarray(ids, np.int64)
        if ids.size and (ids.max() >= 2 ** 31 or ids.min() < 0):
            raise ValueError("device layout requires ids in [0, 2**31)")
        return ids

    def add(self, ids, vectors):
        """Upsert: a re-added id replaces its previous (stale) entry."""
        assert self.is_trained, "train() before add()"
        ids = self._check_ids(ids)
        if self.ntotal:        # nothing to displace on a bulk build —
            self.remove(ids)   # the isin scan is ~1s at 100k drop ids
        vecs = jnp.asarray(vectors, jnp.float32)
        assign = self._assign_cells(vecs)
        counts = np.bincount(np.asarray(assign), minlength=self.cfg.nlist)
        needed = int((np.asarray(self._lens) + counts).max())
        if needed > self._cap:
            self._grow(_next_cap(needed))
        payload = self._encode_payload_dev(vecs, assign)
        self._ids_dev, self._payload_dev, self._lens = _csr_append(
            self._ids_dev, self._payload_dev, self._lens, assign,
            jnp.asarray(ids, jnp.int32), payload)

    def snapshot(self, version: int = 0):
        """Freeze the current state into an immutable IndexSnapshot (zero
        copy: all mutations rebind fresh device arrays)."""
        from .snapshot import snapshot_from_index
        return snapshot_from_index(self, version)

    def search(self, queries, k: int):
        return self.snapshot().search(queries, k)


class IVFPQIndex(IVFFlatIndex):
    """IVF + residual product quantization, scored via the Pallas LUT kernel.

    Vectors are encoded as uint8 PQ codes of the *residual* x -
    centroid[cell] (4x less code memory than the pre-PR-4 int32 storage);
    a candidate's score decomposes as <q, centroid[cell]> + LUT-sum over
    its codes (the first term is one [B, nlist] matmul, the second is the
    kernels/pq_scoring.py hot path).  With ``pq_cfg.opq_iters > 0`` the
    codebooks carry an OPQ rotation, applied transparently by every
    encode/LUT path.
    """

    kind = "ivf-pq"

    def __init__(self, dim: int, cfg: IVFConfig = IVFConfig(),
                 pq_cfg: PQConfig = PQConfig()):
        self.pq_cfg = pq_cfg
        self.codebook: PQCodebook | None = None
        super().__init__(dim, cfg)

    def _empty_payload_dev(self, cap: int):
        return jnp.zeros((self.cfg.nlist, cap, self.pq_cfg.n_subvec),
                         jnp.uint8)

    @property
    def code_dtype(self):
        """Storage dtype of one PQ code (uint8 since PR 4)."""
        return self._payload_dev.dtype

    @property
    def code_bytes_per_vec(self) -> int:
        """Bytes of code storage per indexed vector."""
        return self.pq_cfg.n_subvec * self._payload_dev.itemsize

    def _post_train(self, key, vectors, assign):
        residuals = vectors - self._cent_raw_dev[assign]
        fit = opq_train if self.pq_cfg.opq_iters > 0 else pq_train
        self.codebook = fit(jax.random.fold_in(key, 1), residuals,
                            self.pq_cfg)

    def _encode_payload_dev(self, vectors, assign):
        residuals = vectors - self._cent_raw_dev[assign]
        n = residuals.shape[0]
        if n <= ENCODE_CHUNK:
            return pq_encode(self.codebook, residuals)
        # chunked: pq_encode materializes a [n, M, K] distance buffer —
        # at million-row bulk adds that is GBs; cap it per chunk.  The
        # tail is padded up to a full chunk so every chunk (at every
        # corpus size) runs the SAME compiled shape.
        pad = -n % ENCODE_CHUNK
        residuals = jnp.pad(residuals, ((0, pad), (0, 0)))
        return jnp.concatenate(
            [pq_encode(self.codebook, residuals[i:i + ENCODE_CHUNK])
             for i in range(0, n + pad, ENCODE_CHUNK)])[:n]


def make_index(kind: str, dim: int, *, ivf: IVFConfig = IVFConfig(),
               pq: PQConfig = PQConfig()):
    """Factory: 'exact' | 'ivf-flat' | 'ivf-pq' (IVF kinds are padded-CSR
    device-resident with a jitted end-to-end search)."""
    if kind == "exact":
        return FlatIndex(dim)
    if kind == "ivf-flat":
        return IVFFlatIndex(dim, ivf)
    if kind == "ivf-pq":
        return IVFPQIndex(dim, ivf, pq)
    raise ValueError(f"unknown index kind: {kind!r}")
