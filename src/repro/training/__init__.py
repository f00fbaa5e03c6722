"""Unified training runtime: TrainState + bucketed donated executables +
async device prefetch.

Why this subsystem exists (paper §4.2.2 + ROADMAP "fast as the hardware
allows"): SpeedyFeed's throughput claim is only realized when the loop
around the encoder never stalls the accelerator. Three design points:

**Warm executables, one per batch shape.** The dynamic batcher emits
batches whose news tokens are padded only to their seg-length *bucket*
(8/16/24/32...), not the global max. One ``jax.jit`` state step serves all
buckets: jit's shape-keyed executable cache compiles each shape once and
reuses it warm thereafter.  SpeedyFeed's BusLM step picks each encode-set
chunk's segment length on the device, so its Trainer pads every batch to
the global max on the host (``prepare_batch``) and N steps over K buckets
cost one compilation; an expert encoder keeps a program per bucket, K
compilations (both tested).

**Donated TrainState.** Params, optimizer moments, the news-embedding cache
(O(n_news * news_dim) — by far the largest train-state tensor at production
scale), step and rng travel as one pytree donated to every step executable
(``donate_argnums=(0,)``). XLA then updates Adam moments and scatters cache
refreshes into the *input* buffers instead of allocating + copying a second
full state per step: at the production config the cache alone is ~3.7 GB
(1.2M x 768 fp32), so donation halves peak train-state HBM and removes a
full state copy from the step's critical path.

**Async host->device prefetch + lazy metrics.** A background thread feeds
device-resident batches from the DynamicBatcher through a bounded
double-buffered queue (``jax.device_put`` overlaps H2D with compute on
TPU), and epoch turnover happens inside the prefetcher via the explicit
``data.EPOCH_END`` sentinel. Step metrics stay device scalars in a
``MetricsBuffer`` and are fetched in a single transfer every ``log_every``
steps — the step thread issues XLA launches back-to-back and only ever
blocks at log/checkpoint cadence. ``TrainResult.host_stall_fraction``
reports the residual input-wait share of wall time
(``benchmarks/train_throughput.py`` tracks it against the legacy loop).

Checkpoints keep the pre-Trainer on-disk layout (``{params, opt, cache}``,
with ``cache::age`` accepted as a legacy alias of ``cache::written_step``)
so old snapshots restore into the new runtime unchanged.
"""
from .prefetch import STREAM_END, DevicePrefetcher, PrefetchedBatch
from .registry import get_trainer, register_trainer, registered_trainers
from .state import (CKPT_ALIASES, TrainState, from_ckpt_tree, make_state,
                    restore_state, save_state, state_shardings, state_specs,
                    to_ckpt_tree)
from .trainer import CompileCounter, MetricsBuffer, Trainer, TrainResult
