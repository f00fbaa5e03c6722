# Serving & retrieval: ANN indexes (IVF-Flat / IVF-PQ with Pallas LUT
# scoring) behind a versioned snapshot lifecycle — immutable IndexSnapshot
# (the one query object), IndexBuilder (full rebuild + off-path compaction),
# atomic swap, online delta tier, the two-stage retrieve->re-rank
# RetrievalService, and the continuous-batching request front end
# (RequestScheduler + the open-loop Poisson load harness in loadgen).
from . import loadgen
from .builder import IndexBuilder
from .index import (PAD_ID, FlatIndex, IVFConfig, IVFFlatIndex, IVFPQIndex,
                    make_index)
from .online import (DeltaBuffer, DeltaOverflowError, DeltaView, hybrid_search,
                     ingest_from_cache, merge_topk_dedup)
from .pq import (PQCodebook, PQConfig, fit_kmeans, kmeans, kmeans_minibatch,
                 opq_train, pq_config_for, pq_decode, pq_encode, pq_lut,
                 pq_search, pq_train, sample_rows)
from .scheduler import (DeadlineExceededError, RequestCancelledError,
                        RequestScheduler, ScheduledRequest, bucket_for,
                        pow2_buckets)
from .service import BackpressureError, RetrievalService, ServiceView
from .sharded import (ShardedIndexSnapshot, shard_mesh, shard_snapshot,
                      unshard_snapshot)
from .snapshot import IndexSnapshot, empty_snapshot, snapshot_from_index
from .store import EmbeddingStore
from .tune import TuneResult, autotune, tune_service
