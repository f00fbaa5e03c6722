"""Device-sharded IVF retrieval: padded-CSR lists partitioned across a mesh.

One device's HBM bounds the unsharded ``IndexSnapshot`` — the ``[nlist,
cap]`` id/payload arrays live whole on a single device.  Here the rows are
partitioned contiguously across the ``data`` axis of a 1-axis mesh: shard
``s`` owns global cells ``[s*R, (s+1)*R)`` with ``R = ceil(nlist /
n_shards)`` (the tail shard padded with empty rows), stored as stacked
``[S, R, cap]`` arrays committed with ``PartitionSpec("data")`` so each
device holds exactly its ``[R, cap]`` block.

Search stays ONE jitted executable per (kind, cap bucket, shard count):

  probe   global — every shard ranks the same full ``[nlist, d]`` centroid
          table (replicated; it is tiny next to the payloads), so the
          probed cell set is IDENTICAL to the unsharded index's and the
          sharded top-k provably equals the unsharded top-k.
  score   per shard — a vmap over the stacked shard dim, which GSPMD
          partitions across devices: each shard masks the probes it owns
          (``probe_valid = cell // R == s``), gathers only its local
          ``[R, cap]`` window, scores, and takes a local top-k.
  merge   cross-shard — the per-shard ``[S, B, k]`` results transpose into
          ``[B, S*k]`` (XLA inserts the all-gather) and one final top-k
          yields the answer.  Per-shard ``k`` equals the global ``k_eff``,
          so the true top-k survives local truncation even if every winner
          lives on one shard.

The PQ path scores ADC with a plain XLA LUT gather (the same math the
Pallas kernel's "gather" variant computes — the variant "auto" already
picks on CPU); the Pallas call has no GSPMD partitioning rule, so routing
device-sharded codes through it would force a replicating all-gather.

``shard_snapshot``/``unshard_snapshot`` convert between the two snapshot
forms; ``ShardedIndexSnapshot`` is API-compatible with ``IndexSnapshot``
(version/kind/ntotal/member_ids/search/built_at), so the delta tier,
``hybrid_search`` and ``RetrievalService`` work unchanged on top of it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .index import FULL, PAD_ID, _masked_topk, _probe_cells
from .pq import PQCodebook, pq_lut
from .snapshot import IndexSnapshot


def shard_mesh(devices) -> Mesh:
    """1-axis ``("data",)`` mesh over an explicit device list."""
    return Mesh(np.asarray(devices), ("data",))


def _row_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, P("data"))


def _replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# jitted sharded search kernels (module-level: one warm executable per
# (kind, cap bucket, shard count) across every snapshot of that shape)
# ---------------------------------------------------------------------------

def _shard_gather(ids_r, lens_r, local, pv, cap, B):
    """One shard's fixed-width candidate window: ids [B, P*cap] and the
    validity mask combining slot-fill with probe ownership."""
    lp = jnp.where(pv, local, 0)
    cand = ids_r[lp].reshape(B, -1)
    valid = ((jnp.arange(cap)[None, None] < lens_r[lp][:, :, None])
             & pv[:, :, None]).reshape(B, -1)
    return lp, cand, valid


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "metric"))
def _search_flat_sharded(q, cent_unit, cent_raw, ids_s, vecs_s, lens_s, *,
                         nprobe: int, k: int, metric: str):
    """Sharded IVF-Flat search.  ids_s [S, R, cap] / vecs_s [S, R, cap, d] /
    lens_s [S, R] committed P("data"); q and centroids replicated."""
    S, R, cap = ids_s.shape
    B = q.shape[0]
    probes = _probe_cells(q, cent_unit, cent_raw, nprobe, metric)  # [B, P]
    shard_of, local = probes // R, probes % R

    def per_shard(s, ids_r, vecs_r, lens_r):
        pv = shard_of == s
        lp, cand, valid = _shard_gather(ids_r, lens_r, local, pv, cap, B)
        sc = jnp.einsum("bd,bpcd->bpc", q, vecs_r[lp],
                        precision=FULL).reshape(B, -1)
        return _masked_topk(sc, cand, valid, k)

    s_sc, s_ids = jax.vmap(per_shard)(jnp.arange(S), ids_s, vecs_s, lens_s)
    merged_sc = s_sc.transpose(1, 0, 2).reshape(B, -1)   # [B, S*k]
    merged_ids = s_ids.transpose(1, 0, 2).reshape(B, -1)
    return _masked_topk(merged_sc, merged_ids,
                        jnp.isfinite(merged_sc), k)


def _adc_gather(lut, codes):
    """XLA LUT gather: lut [B, M, K], codes [B, N, M] uint8 -> [B, N]."""
    g = jnp.take_along_axis(lut[:, None], codes[..., None].astype(jnp.int32),
                            axis=-1)                      # [B, N, M, 1]
    return g[..., 0].sum(-1)


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "metric"))
def _search_pq_sharded(q, cent_unit, cent_raw, ids_s, codes_s, lens_s,
                       cb_centers, cb_rot=None, *, nprobe: int, k: int,
                       metric: str):
    """Sharded IVF-PQ search: per-shard ADC via the XLA LUT gather plus the
    global coarse term <q, cell-mean> (computed once from the replicated
    raw centroids)."""
    S, R, cap = ids_s.shape
    B = q.shape[0]
    probes = _probe_cells(q, cent_unit, cent_raw, nprobe, metric)
    shard_of, local = probes // R, probes % R
    lut = pq_lut(PQCodebook(cb_centers, cb_rot), q)       # [B, M, K]
    coarse = jnp.take_along_axis(q @ cent_raw.T, probes, axis=1)  # [B, P]

    def per_shard(s, ids_r, codes_r, lens_r):
        pv = shard_of == s
        lp, cand, valid = _shard_gather(ids_r, lens_r, local, pv, cap, B)
        adc = _adc_gather(lut, codes_r[lp].reshape(B, -1,
                                                   codes_r.shape[-1]))
        sc = adc + jnp.repeat(coarse, cap, axis=1)
        return _masked_topk(sc, cand, valid, k)

    s_sc, s_ids = jax.vmap(per_shard)(jnp.arange(S), ids_s, codes_s, lens_s)
    merged_sc = s_sc.transpose(1, 0, 2).reshape(B, -1)
    merged_ids = s_ids.transpose(1, 0, 2).reshape(B, -1)
    return _masked_topk(merged_sc, merged_ids,
                        jnp.isfinite(merged_sc), k)


# ---------------------------------------------------------------------------
# sharded snapshot
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedIndexSnapshot:
    """Immutable device-sharded view of one IVF build.

    API-compatible with ``IndexSnapshot`` for everything the serving tier
    touches (``version``/``kind``/``ntotal``/``built_at``/``member_ids``/
    ``search``); the CSR arrays are stacked per shard and committed across
    the mesh instead of living whole on one device.
    """
    version: int
    kind: str                      # "ivf-flat" | "ivf-pq"
    dim: int
    ntotal: int
    nprobe: int
    metric: str
    nlist: int                     # true cell count (rows may be padded)
    mesh: Mesh
    cent_unit: Any                 # [nlist, d] replicated
    cent_raw: Any                  # [nlist, d] replicated
    ids_s: Any                     # [S, R, cap] int32, P("data")
    payload_s: Any                 # [S, R, cap, d] f32 | [S, R, cap, M] u8
    lens_s: Any                    # [S, R] int32, P("data")
    pq_centers: Any = None         # replicated PQ codebooks (ivf-pq)
    pq_rot: Any = None             # replicated OPQ rotation (or None)
    built_at: float = 0.0

    @property
    def n_shards(self) -> int:
        return int(self.ids_s.shape[0])

    @property
    def rows_per_shard(self) -> int:
        return int(self.ids_s.shape[1])

    @property
    def cap(self) -> int:
        return int(self.ids_s.shape[2])

    @functools.cached_property
    def member_ids(self) -> np.ndarray:
        ids_h = np.asarray(self.ids_s).reshape(-1, self.cap)
        lens_h = np.asarray(self.lens_s).reshape(-1)
        mask = np.arange(self.cap)[None, :] < lens_h[:, None]
        return ids_h[mask].astype(np.int64)

    def search(self, queries, k: int):
        """(scores [B, k], ids [B, k]) np — identical results to the
        unsharded snapshot (global probe => identical candidate set)."""
        B = queries.shape[0]
        if self.ntotal == 0:
            return (np.full((B, k), -np.inf, np.float32),
                    np.full((B, k), PAD_ID, np.int64))
        q = jax.device_put(jnp.asarray(queries, jnp.float32),
                           _replicated(self.mesh))
        k_eff = min(k, self.nprobe * self.cap)
        if self.kind == "ivf-flat":
            s, ids = _search_flat_sharded(
                q, self.cent_unit, self.cent_raw, self.ids_s,
                self.payload_s, self.lens_s,
                nprobe=self.nprobe, k=k_eff, metric=self.metric)
        else:
            s, ids = _search_pq_sharded(
                q, self.cent_unit, self.cent_raw, self.ids_s,
                self.payload_s, self.lens_s, self.pq_centers, self.pq_rot,
                nprobe=self.nprobe, k=k_eff, metric=self.metric)
        s, ids = np.asarray(s, np.float32), np.asarray(ids, np.int64)
        if k_eff < k:
            s = np.pad(s, ((0, 0), (0, k - k_eff)), constant_values=-np.inf)
            ids = np.pad(ids, ((0, 0), (0, k - k_eff)),
                         constant_values=PAD_ID)
        return s, ids


def shard_snapshot(snap: IndexSnapshot, mesh: Mesh) -> ShardedIndexSnapshot:
    """Partition an IVF snapshot's CSR rows across ``mesh``'s data axis.

    Rows are padded up to ``S * ceil(nlist / S)`` with empty cells (len 0,
    PAD ids) so every shard holds an identical-shape block; the padded
    cells are unreachable (probing ranks only the true ``nlist``
    centroids).
    """
    if snap.kind not in ("ivf-flat", "ivf-pq"):
        raise ValueError(f"cannot device-shard kind {snap.kind!r} "
                         "(only the IVF kinds have CSR rows)")
    S = mesh.devices.size
    nlist, cap = snap.list_ids.shape
    R = -(-nlist // S)
    pad = S * R - nlist
    ids = np.pad(np.asarray(snap.list_ids), ((0, pad), (0, 0)),
                 constant_values=PAD_ID)
    payload_h = np.asarray(snap.payload)
    payload = np.pad(payload_h,
                     ((0, pad),) + ((0, 0),) * (payload_h.ndim - 1))
    lens = np.pad(np.asarray(snap.lens), (0, pad))
    rows, rep = _row_sharding(mesh), _replicated(mesh)
    return ShardedIndexSnapshot(
        version=snap.version, kind=snap.kind, dim=snap.dim,
        ntotal=snap.ntotal, nprobe=snap.nprobe, metric=snap.metric,
        nlist=nlist, mesh=mesh,
        cent_unit=jax.device_put(jnp.asarray(snap.cent_unit), rep),
        cent_raw=jax.device_put(jnp.asarray(snap.cent_raw), rep),
        ids_s=jax.device_put(ids.reshape(S, R, cap), rows),
        payload_s=jax.device_put(
            payload.reshape((S, R) + payload_h.shape[1:]), rows),
        lens_s=jax.device_put(lens.reshape(S, R).astype(np.int32), rows),
        pq_centers=(jax.device_put(jnp.asarray(snap.pq_centers), rep)
                    if snap.pq_centers is not None else None),
        pq_rot=(jax.device_put(jnp.asarray(snap.pq_rot), rep)
                if snap.pq_rot is not None else None),
        built_at=snap.built_at)


def unshard_snapshot(ssnap: ShardedIndexSnapshot) -> IndexSnapshot:
    """Reassemble the single-device snapshot (host gather + strip the row
    padding) — the off-path route for compaction on a sharded build."""
    nlist, cap = ssnap.nlist, ssnap.cap
    ids = np.asarray(ssnap.ids_s).reshape(-1, cap)[:nlist]
    payload = np.asarray(ssnap.payload_s)
    payload = payload.reshape((-1,) + payload.shape[2:])[:nlist]
    lens = np.asarray(ssnap.lens_s).reshape(-1)[:nlist]
    return IndexSnapshot(
        version=ssnap.version, kind=ssnap.kind, dim=ssnap.dim,
        ntotal=ssnap.ntotal, nprobe=ssnap.nprobe, metric=ssnap.metric,
        cent_unit=jnp.asarray(np.asarray(ssnap.cent_unit)),
        cent_raw=jnp.asarray(np.asarray(ssnap.cent_raw)),
        list_ids=jnp.asarray(ids), payload=jnp.asarray(payload),
        lens=jnp.asarray(lens),
        pq_centers=(jnp.asarray(np.asarray(ssnap.pq_centers))
                    if ssnap.pq_centers is not None else None),
        pq_rot=(jnp.asarray(np.asarray(ssnap.pq_rot))
                if ssnap.pq_rot is not None else None),
        built_at=ssnap.built_at)
