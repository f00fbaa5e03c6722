"""Async host->device input pipeline (double-buffered prefetch).

The step thread must never block on the host: while bucket executable N runs,
the next batch is already being assembled by the DynamicBatcher threads,
converted, and `jax.device_put` by the prefetch thread. On TPU `device_put`
enqueues an async H2D copy, so with ``depth=2`` the transfer of batch N+1
overlaps the compute of batch N (classic double buffering); the bounded
queue gives backpressure so at most ``depth`` batches are in flight.

The prefetcher also owns epoch turnover: when the batcher reports
``EPOCH_END`` (the explicit sentinel — a ``None`` from ``get`` is a timeout,
not end-of-data) it tears the exhausted batcher down and starts the next
epoch's, so the consumer sees one uninterrupted batch stream.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time

import warnings

import jax

from repro import data, obs
from repro.resilience import faults


# producer finished cleanly (max_epochs reached, queue drained) — distinct
# from None, which means timeout
STREAM_END = data.batching.Sentinel("STREAM_END")


@dataclasses.dataclass
class PrefetchedBatch:
    bucket: int          # seg-length bucket key (selects the executable)
    arrays: dict         # device-resident batch tensors
    stats: dict | None   # host-side loader stats (data efficiency etc.)
    epoch: int = 0


class DevicePrefetcher:
    """Background thread: DynamicBatcher -> device arrays -> bounded queue.

    ``make_batcher(epoch)`` must return a *started* DynamicBatcher; a fresh
    one is created per epoch with the epoch index available for reseeding.
    """

    def __init__(self, make_batcher, *, depth: int = 2,
                 max_epochs: int | None = None, device=None,
                 poll: float = 0.25, sharding=None, prepare=None):
        self._make = make_batcher
        self._depth = depth
        self._max_epochs = max_epochs
        self._device = device
        self._poll = poll
        # mesh placement: a Sharding applied to every leaf, or a callable
        # ``(arrays) -> pytree of Shardings`` (the Trainer passes its batch
        # sharding builder) — batches land committed to their final layout,
        # so the step jit never reshards input
        self._sharding = sharding
        # host batch -> host batch before the copy (the Trainer's
        # ``prepare_batch``)
        self._prepare = prepare
        self._q = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._finished = threading.Event()
        self._error: BaseException | None = None
        self._thread: threading.Thread | None = None
        self.epochs_done = 0
        # depth of device-ready batches waiting for the step thread: 0 at
        # steady state means the consumer is input-bound, == depth means
        # the producer keeps ahead (what double buffering is for)
        self._g_depth = obs.gauge("prefetch_queue_depth")

    def start(self) -> "DevicePrefetcher":
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _run(self):
        epoch = 0
        batcher = None
        try:
            batcher = self._make(epoch)
            while not self._stop.is_set():
                item = batcher.get(timeout=self._poll)
                if item is None:               # timeout: loader still busy
                    continue
                if item is data.EPOCH_END:
                    batcher.stop()
                    batcher = None
                    epoch += 1
                    self.epochs_done = epoch
                    if self._max_epochs is not None \
                            and epoch >= self._max_epochs:
                        return
                    batcher = self._make(epoch)
                    continue
                stats = item.pop("_stats", None)
                bucket = int(item.pop("_bucket",
                                      (stats or {}).get("seg_len", 0)))
                if self._prepare is not None:
                    item = self._prepare(item)
                faults.fire("prefetch.h2d", step=epoch)
                with obs.span("prefetch_h2d"):
                    if self._sharding is not None:
                        target = self._sharding(item) \
                            if callable(self._sharding) else self._sharding
                        arrays = jax.device_put(item, target)
                    else:
                        arrays = {k: jax.device_put(v, self._device)
                                  for k, v in item.items()}
                pb = PrefetchedBatch(bucket, arrays, stats, epoch)
                while not self._stop.is_set():
                    try:
                        self._q.put(pb, timeout=0.1)   # backpressure
                        self._g_depth.set(self._q.qsize())
                        break
                    except queue.Full:
                        continue
        except BaseException as e:      # surfaced on the consumer side
            self._error = e
        finally:
            if batcher is not None:
                batcher.stop()
            self._finished.set()

    def get(self, timeout: float = 30.0):
        """Next device batch; ``STREAM_END`` once the producer finished
        cleanly (max_epochs reached) and the queue drained; ``None`` only on
        timeout (producer alive but slow). Raises the producer's error, if
        any — the same three-way contract as ``DynamicBatcher.get``."""
        end = time.monotonic() + timeout
        while True:
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            try:
                pb = self._q.get(timeout=0.05)
                self._g_depth.set(self._q.qsize())
                return pb
            except queue.Empty:
                if self._finished.is_set() and self._q.empty():
                    if self._error is not None:   # crash is not a clean end:
                        continue                  # re-loop raises it
                    return STREAM_END
                if time.monotonic() >= end:
                    return None

    def stop(self, timeout: float = 5.0):
        """Shut the producer down. Never raises (safe in ``finally``);
        producer errors surface through ``get``.

        A producer that does not join within ``timeout`` (wedged in a
        device_put or a loader read) is abandoned as a daemon thread —
        but never silently: the leak is counted
        (``prefetch_thread_leaks_total``) and warned about, so a
        supervisor restarting the trainer can see threads pile up
        instead of debugging a mystery OOM."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
            if t.is_alive():
                obs.counter("prefetch_thread_leaks_total").inc()
                warnings.warn(
                    f"prefetch producer thread did not stop within "
                    f"{timeout}s and was abandoned (daemon); it may hold "
                    f"queue/device buffers until it dies", stacklevel=2)
            self._thread = None
